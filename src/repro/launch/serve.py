"""Batched serving driver (CPU-runnable).

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \\
        --batch 4 --prompt-len 16 --new-tokens 32

Continuous batching (variable-length requests streamed into the fixed
decode batch under a Poisson-ish arrival trace):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \\
        --batch 4 --continuous --requests 8 --arrival-rate 0.5
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import (
    FabricScheduler, ServeConfig, ServeEngine, ServeTenant, Staging,
)
from repro.data import DataConfig, SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import get, init_params, reduced

import jax


def _continuous_trace(args, cfg):
    """The streamed-request trace both engines share: variable-length
    prompts under a Poisson-ish arrival process."""
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(max(2, args.prompt_len // 2),
                        args.prompt_len + 1, size=args.requests)
    reqs = [(rng.integers(0, cfg.vocab_size, (int(s),)).astype(np.int32),
             args.new_tokens) for s in lens]
    gaps = rng.poisson(1.0 / max(args.arrival_rate, 1e-6),
                       size=args.requests)
    arrivals = np.cumsum(gaps) - gaps[0]
    return reqs, arrivals, lens


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--decode-mode", default="step",
                    choices=["step", "chunk", "host"],
                    help="decode loop: device-resident step, lax.scan chunk, "
                         "or the legacy host round-trip")
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="tokens per dispatch in chunk mode")
    ap.add_argument("--staging", default="direct",
                    choices=["direct", "tree", "tree_reshard"],
                    help="replicated-placement strategy for weights and "
                         "prefill inserts (repro.api.Staging)")
    ap.add_argument("--fabric", action="store_true",
                    help="serve as a lease-holding fabric tenant: hold a "
                         "--serve-floor cluster floor, grow to the free "
                         "fabric per decode burst, shrink back between "
                         "bursts (repro.api.FabricScheduler)")
    ap.add_argument("--serve-floor", type=int, default=1,
                    help="resident lease size between bursts (fabric mode)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: stream --requests variable-"
                         "length prompts through the slot scheduler")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of streamed requests (continuous mode)")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="mean arrivals per decode step of the Poisson-ish "
                         "trace (continuous mode)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))

    params = jax.device_get(init_params(jax.random.key(args.seed), cfg))

    scfg = ServeConfig(batch=args.batch,
                       max_len=args.prompt_len + args.new_tokens + 1,
                       temperature=args.temperature, seed=args.seed,
                       decode_mode=args.decode_mode,
                       decode_chunk=args.decode_chunk,
                       staging=Staging(args.staging))
    if args.fabric:
        # serve as a fabric tenant: a resident floor lease, elastically
        # grown to the free fabric for each decode burst; the clusters
        # released between bursts are leasable by offload tenants
        sched = FabricScheduler(jax.devices())
        tenant = ServeTenant(sched, cfg, params, scfg,
                             floor=min(args.serve_floor, sched.num_clusters))
        t0 = time.time()
        if args.continuous:
            reqs, arrivals, _ = _continuous_trace(args, cfg)
            outs = tenant.generate_many(reqs,
                                        arrival_steps=arrivals.tolist())
            dt = time.time() - t0
            total = sum(len(o) for o in outs)
            head = f"continuous, {args.requests} requests"
            samples = [o[:12].tolist() for o in outs[:2]]
        else:
            stream = SyntheticStream(
                DataConfig(vocab_size=cfg.vocab_size,
                           batch_size=args.batch,
                           seq_len=args.prompt_len, seed=args.seed), cfg)
            out = tenant.generate(stream.batch(0)["tokens"],
                                  args.new_tokens)
            dt = time.time() - t0
            total = args.batch * args.new_tokens
            head = f"batch {args.batch}"
            samples = [out[b][:12].tolist() for b in range(min(2, args.batch))]
        print(f"[serve] fabric tenant ({head}): {total} tokens in "
              f"{dt:.2f}s ({total / dt:.1f} tok/s); lease floor "
              f"{tenant.lease.n}/{sched.num_clusters} clusters, burst "
              f"window {tenant.peak_burst}, free between bursts: "
              f"{len(sched.free_clusters())}")
        for i, s in enumerate(samples):
            print(f"  slot {i}: -> {s}")
        tenant.close()
        return
    engine = ServeEngine(cfg, params, mesh, scfg)
    # weight placement honours --staging: under "tree" every replicated
    # leaf crosses the host link once and fans out device-to-device
    engine.place_params(params)

    if args.continuous:
        reqs, arrivals, lens = _continuous_trace(args, cfg)
        t0 = time.time()
        outs = engine.generate_many(reqs, arrival_steps=arrivals.tolist())
        dt = time.time() - t0
        total = sum(len(o) for o in outs)
        print(f"[serve] continuous: {args.requests} requests, {total} tokens "
              f"in {dt:.2f}s ({total / dt:.1f} tok/s, batch {args.batch}, "
              f"{engine.stats['prefill_inserts']} inserts)")
        for r in range(min(2, args.requests)):
            print(f"  req {r}: prompt_len={lens[r]} arrival={arrivals[r]} "
                  f"-> {outs[r][:12].tolist()}")
        return

    stream = SyntheticStream(
        DataConfig(vocab_size=cfg.vocab_size, batch_size=args.batch,
                   seq_len=args.prompt_len, seed=args.seed), cfg)
    ex = stream.batch(0)
    prompts = ex["tokens"]
    extra = {k: v for k, v in ex.items() if k == "patches"}

    t0 = time.time()
    out = engine.generate(prompts, args.new_tokens, extra or None)
    dt = time.time() - t0
    total = args.batch * args.new_tokens
    print(f"[serve] generated {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s, batch {args.batch})")
    for b in range(min(2, args.batch)):
        print(f"  slot {b}: prompt={prompts[b][:8].tolist()}... "
              f"-> {out[b][:16].tolist()}")


if __name__ == "__main__":
    main()
