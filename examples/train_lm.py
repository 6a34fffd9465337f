"""End-to-end training driver example: a ~few-hundred-step run of the
(reduced) SmolLM config on a 4×2 CPU mesh, with checkpointing, a simulated
mid-run failure, and a bit-exact elastic resume on fewer devices.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/train_lm.py [--steps 300]

(For the full-size architectures use repro.launch.train with --arch; the
100M-scale end-to-end budget on CPU is covered by --reduced configs.)
"""

import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import models as M
from repro.checkpoint import restore, save
from repro.data import DataConfig, SyntheticStream
from repro.dist.sharding import to_shardings
from repro.ft.elastic import elastic_restore
from repro.optim.adamw import adamw_init
from repro.train import TrainConfig, build_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--fail-at", type=int, default=120)
    args = ap.parse_args()

    cfg = M.reduced(M.get("smollm-360m"), n_layers=4, d_model=256,
                    n_heads=8, n_kv_heads=4, head_dim=32, d_ff=512,
                    vocab_size=4096)
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(4, 2), ("data", "model"))
    print(f"arch={cfg.name} params={M.count_params(cfg)/1e6:.1f}M "
          f"mesh={mesh.devices.shape} {mesh.axis_names}")

    stream = SyntheticStream(
        DataConfig(vocab_size=cfg.vocab_size, batch_size=8, seq_len=64,
                   seed=0), cfg)
    bs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
          for k, v in stream.batch(0).items()}
    tcfg = TrainConfig(base_lr=1e-3, warmup_steps=20, total_steps=args.steps,
                       microbatches=2)
    step_fn, pspecs, ospecs, bspecs = build_train_step(cfg, mesh, tcfg, bs)
    params = jax.device_put(M.init_params(jax.random.key(0), cfg),
                            to_shardings(pspecs, mesh))
    opt = jax.device_put(adamw_init(params), to_shardings(ospecs, mesh))

    ckdir = tempfile.mkdtemp(prefix="trainlm_")
    first_loss = None
    failed_once = False
    i = 0
    while i < args.steps:
        batch = jax.device_put(stream.batch(i), to_shardings(bspecs, mesh))
        params, opt, m = step_fn(params, opt, batch, jnp.asarray(i))
        if first_loss is None:
            first_loss = float(m["loss"])
        if i % 25 == 0:
            print(f"step {i:4d} loss={float(m['loss']):.4f} "
                  f"gnorm={float(m['grad_norm']):.2f}")
        if (i + 1) % 20 == 0:
            save(ckdir, i + 1, {"params": params, "opt": opt},
                 {"params": pspecs, "opt": ospecs}, data_index=i + 1)
        i += 1
        if i == args.fail_at and not failed_once:
            failed_once = True
            print(f"--- simulated node failure at step {i}; "
                  f"elastic restart on 4 devices ---")
            ks = jax.eval_shape(lambda: jax.random.key(0))
            pshapes = jax.eval_shape(
                lambda k: M.init_params(k, cfg),
                jax.ShapeDtypeStruct(ks.shape, ks.dtype))
            st, di, state, mesh = elastic_restore(ckdir, devs[:4], pshapes)
            step_fn, pspecs, ospecs, bspecs = build_train_step(
                cfg, mesh, tcfg, bs)
            params, opt = state["params"], state["opt"]
            i = di
            print(f"--- resumed from step {di} on mesh {mesh.devices.shape} ---")

    final = float(m["loss"])
    print(f"\nfirst loss {first_loss:.3f} -> final {final:.3f} "
          f"(dropped {first_loss - final:.3f} nats over {args.steps} steps)")


if __name__ == "__main__":
    main()
