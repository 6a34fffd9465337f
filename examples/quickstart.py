"""Quickstart: the paper's offload runtime in two minutes.

Drives the paper's AXPY kernel through the session API (typed policies,
one submit path, ``policy=AUTO`` model-driven mode selection), compares
both offload implementations' collective structure, and asks the
analytical model for the optimal offload width.  It offloads onto every
local device, up to 8 clusters.  On the CPU, 8 virtual devices and the
paper's float64:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_ENABLE_X64=true PYTHONPATH=src python examples/quickstart.py

On a TPU host, ``PYTHONPATH=src python examples/quickstart.py`` uses its
chips, in float32 (x64 off).
"""

import jax
import numpy as np

from repro.api import (
    AUTO, InfoDist, OffloadPolicy, Residency, Session,
)
from repro.core import jobs, model, simulator
from repro.core.multicast import CLUSTER_OFFSET_BITS, MulticastRequest
from repro.core.offload import count_collectives


def close(got, want) -> bool:
    """Normwise agreement with numpy's float64 result; with x64 off the
    job runs in float32, ~1e-7 relative error."""
    return bool(np.linalg.norm(np.asarray(got) - want)
                <= 1e-6 * np.linalg.norm(want))


def main() -> None:
    job = jobs.make_axpy(4096)
    n = min(len(jax.devices()), 8)
    sess = Session(jax.devices()[:n])   # default policy=AUTO

    print(f"=== 1. one submit path, both implementations ({n} clusters) ===")
    for label, pol in (("baseline ", OffloadPolicy(
                            info_dist=InfoDist.P2P_CHAIN,
                            completion="central_counter")),
                       ("multicast", AUTO)):
        operands, expected = job.make_instance(0)
        got = sess.submit(job, operands, n=n, policy=pol).wait()
        colls = count_collectives(sess.runtime(pol).lowered_text(job, n))
        print(f"  {label}: close={close(got, expected)}  "
              f"chain={colls['collective-permute']} collective-permutes, "
              f"{colls['all-reduce']} all-reduce")

    print("\n=== 2. AUTO: fused + pipelined + resident, planner-picked ===")
    instances, exps = jobs.make_instances(job, 16, seed0=2)
    handle = sess.submit(job, instances, n=n)     # policy=AUTO
    results = handle.wait()
    ok = all(close(r, e) for r, e in zip(results, exps))
    d = handle.decision
    print(f"  16 jobs -> fuse={d.fuse}, window={d.window}, "
          f"staging={d.staging.value}; close={ok}")
    print("  predicted vs measured (handle.explain()):")
    for line in str(handle.explain()).splitlines():
        print(f"    {line}")
    sess.stage(job, instances[0], n=n)            # prime residency
    got = sess.submit(job, Residency.RESIDENT, n=n).wait()
    print(f"  resident redispatch: close={close(got, exps[0])}")

    print("\n=== 3. cluster selection via the paper's address-mask (fig. 5) ===")
    # the odd clusters: cluster bit 0 set, the higher bits free
    # (mask 0b110 on 8 clusters)
    req = MulticastRequest(addr=int(n > 1) << CLUSTER_OFFSET_BITS,
                           mask=((n - 1) & ~1) << CLUSTER_OFFSET_BITS)
    devs, ids = sess.runtime().select_clusters(request=req)
    operands, expected = job.make_instance(1)
    got = sess.submit(job, operands, request=req).wait()
    print(f"  mask {bin((n - 1) & ~1)} over cluster bits -> clusters {ids}; "
          f"close={close(got, expected)}")

    print("\n=== 4. the simulator: what this offload costs on Occamy ===")
    for n in (1, 4, 8, 32):
        base = simulator.simulate(job.spec, n, 'baseline').total
        ext = simulator.simulate(job.spec, n, 'multicast').total
        print(f"  n={n:2d}: baseline={base:7.0f} cyc  multicast={ext:7.0f} cyc "
              f"  speedup={base/ext:.2f}x")

    print("\n=== 5. the analytical model: how wide should we offload? ===")
    for N in (64, 1024, 65536):
        n_opt, t = model.optimal_clusters(lambda: jobs.axpy_spec(N))
        print(f"  AXPY N={N:6d}: optimal n={n_opt:2d} "
              f"(predicted {t:.0f} cycles; eq.5 t̂=400+N/4+2.47N/8n)")


if __name__ == "__main__":
    main()
