"""Batched serving example: prefill + decode with KV/SSM caches for three
different architecture families (dense GQA, MLA, hybrid SSM), driven by the
ServeEngine with completion-unit tracking per step.

It builds a 4x2 mesh, so it needs 8 devices; on the CPU:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_batch.py
"""

import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro import models as M
from repro.api import ServeConfig, ServeEngine, Staging
from repro.data import DataConfig, SyntheticStream


def demo(arch: str, batch: int = 4, prompt: int = 16, new: int = 24) -> None:
    cfg = M.reduced(M.get(arch))
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(4, 2), ("data", "model"))
    params = jax.device_get(M.init_params(jax.random.key(0), cfg))

    engine = ServeEngine(cfg, params, mesh,
                         ServeConfig(batch=batch, max_len=prompt + new + 1,
                                     temperature=0.8, seed=7,
                                     staging=Staging.TREE))
    # typed tree staging: replicated weight leaves cross the host link
    # once and fan out device-to-device (stats below count the bytes)
    engine.place_params(params)
    stream = SyntheticStream(
        DataConfig(vocab_size=cfg.vocab_size, batch_size=batch,
                   seq_len=prompt, seed=1), cfg)
    ex = stream.batch(0)
    extra = {k: v for k, v in ex.items() if k == "patches"}
    t0 = time.time()
    out = engine.generate(ex["tokens"], new, extra or None)
    dt = time.time() - t0
    cache_kind = ("compressed-KV (MLA)" if cfg.mla
                  else "SSM state" if cfg.ssm else "KV")
    print(f"{arch:24s} [{cfg.family:6s}] cache={cache_kind:20s} "
          f"{batch * new} tokens in {dt:5.1f}s ({batch * new / dt:6.1f} tok/s)")
    print(f"  weight placement: {engine.stats['h2d_bytes'] / 1e6:.1f} MB "
          f"host-link, {engine.stats['d2d_bytes'] / 1e6:.1f} MB d2d "
          f"(staging={engine.scfg.staging.value})")
    print(f"  sample: {out[0][:12].tolist()}")


def main() -> None:
    for arch in ("smollm-360m", "deepseek-v2-lite-16b", "zamba2-2.7b"):
        demo(arch)


if __name__ == "__main__":
    main()
