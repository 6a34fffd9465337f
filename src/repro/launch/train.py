"""End-to-end training driver (CPU-runnable; the same code path the dry-run
AOT-compiles for the production mesh).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
        --steps 200 --batch 8 --seq 128 --mesh 1x1 --ckpt /tmp/run1

Every step is dispatched through the paper's offload model: per-step scalars
ride the multicast path (replicated shardings), the loss reduction is the
completion-unit arrival psum, and the host tracks completion + stragglers
through CompletionUnit/StepWatchdog.  ``--resume`` continues bit-for-bit
from the newest checkpoint (same data indices).
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import latest_step, restore, save
from repro.core.completion import CompletionUnit
from repro.data import DataConfig, SyntheticStream
from repro.dist.sharding import to_shardings
from repro.ft.straggler import StepWatchdog
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import get, init_params, reduced
from repro.optim.adamw import adamw_init
from repro.train import TrainConfig, build_train_step


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized sibling of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x2")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))

    stream = SyntheticStream(
        DataConfig(vocab_size=cfg.vocab_size, batch_size=args.batch,
                   seq_len=args.seq, seed=args.seed), cfg)
    ex = stream.batch(0)
    batch_shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                    for k, v in ex.items()}
    tcfg = TrainConfig(base_lr=args.lr, warmup_steps=max(1, args.steps // 20),
                       total_steps=args.steps, microbatches=args.microbatches)
    step_fn, pspecs, ospecs, bspecs = build_train_step(
        cfg, mesh, tcfg, batch_shapes)

    start = 0
    if args.resume and args.ckpt and latest_step(args.ckpt) is not None:
        start, data_index, state = restore(
            args.ckpt, mesh, {"params": pspecs, "opt": ospecs})
        params, opt = state["params"], state["opt"]
        print(f"[train] resumed step {start} (data index {data_index})")
    else:
        params = jax.device_put(init_params(jax.random.key(args.seed), cfg),
                                to_shardings(pspecs, mesh))
        opt = jax.device_put(adamw_init(params, tcfg.adamw),
                             to_shardings(ospecs, mesh))

    unit = CompletionUnit(n_units=4)
    watchdog = StepWatchdog()
    bshard = to_shardings(bspecs, mesh)
    t_start = time.time()
    for i in range(start, args.steps):
        batch = jax.device_put(stream.batch(i), bshard)
        unit.program(1, i)                      # offload register (fig. 6)
        t0 = time.monotonic()
        params, opt, metrics = step_fn(params, opt, batch, jnp.asarray(i))
        arrivals = int(metrics["arrivals"])    # fused completion reduction
        unit.arrive(i, arrivals)
        assert unit.clear() == i
        watchdog.observe(time.monotonic() - t0)
        if i % args.log_every == 0 or i == args.steps - 1:
            print(f"[train] step {i:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} "
                  f"deadline={watchdog.deadline():.2f}s")
        if args.ckpt and (i + 1) % args.ckpt_every == 0:
            save(args.ckpt, i + 1, {"params": params, "opt": opt},
                 {"params": pspecs, "opt": ospecs}, data_index=i + 1)
    dt = time.time() - t_start
    steps_run = args.steps - start
    print(f"[train] done: {steps_run} steps in {dt:.1f}s "
          f"({steps_run / max(dt, 1e-9):.2f} steps/s)")
    if args.ckpt:
        save(args.ckpt, args.steps, {"params": params, "opt": opt},
             {"params": pspecs, "opt": ospecs}, data_index=args.steps)


if __name__ == "__main__":
    main()
