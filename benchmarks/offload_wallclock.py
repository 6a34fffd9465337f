"""Real-runtime benchmark of the framework's *own* offload overheads.

Subprocess-isolated measurements (the bench process keeps 1 device):

* **dispatch sweep** (``offload_wallclock``) — for n ∈ {1, 2, 4, 8}
  clusters, the host-side dispatch overhead of ``OffloadRuntime.offload()``
  (time to launch, excluding the blocking wait) in three regimes:

    - ``cold``      first dispatch: plan build + compile + staging
    - ``warm``      warm plan, operands re-``device_put`` each job (the
                    seed's re-staging path)
    - ``resident``  warm plan, resident operands — zero ``device_put``

  plus the end-to-end µs/job and, at n=8, the baseline-vs-multicast
  wallclock and HLO collective structure (the paper's fig.-7 signature),
  and µs/token of ``ServeEngine`` for the legacy host round-trip loop vs
  the device-resident single-step and ``lax.scan`` chunk paths.

* **stream suite** (``stream_wallclock``) — jobs/s through the one
  ``Session.submit`` path under typed policies: sequential resident
  dispatch (``fuse=1, window=1``) vs the pipelined window in both modes —
  resident redispatch (same data movement as sequential, so the delta is
  launch+fetch hidden behind compute) and fresh staging per job (the slot
  double-buffer overlapping phase E with compute, against the sequential
  re-staging baseline) — vs fused dispatch batching at B ∈ {1, 2, 4, 8}
  (per-job share of one batched launch), with the fused HLO collective
  counts at B=2 vs B=8 (must not grow with B).  ``policy=AUTO`` rows
  record what the model-driven planner picks and what it measures —
  the acceptance surface for "AUTO is never slower than the best
  hand-picked mode" (asserted against this recording by
  ``tests/test_session.py``).

* **serve-throughput suite** (``serve_throughput``) — tokens/s of static
  fixed-batch ``generate`` calls vs continuous-batching ``generate_many``
  under a Poisson-ish arrival trace of variable-length prompts.

* **staging sweep** (``staging_wall``) — real-runtime phase-E staging of a
  32 MiB replicated operand through ``DispatchPlan.stage`` for n ∈
  {1, 2, 4, 8} clusters, ``host_fanout`` (the O(n) sequential host-link
  baseline) vs ``tree`` (hierarchical broadcast staging), cold and warm,
  with the exact ``h2d_bytes``/``d2d_bytes`` counters per point.  A
  :class:`~repro.core.simulator.StagingCostModel` is calibrated from the
  warm host-fanout n ∈ {1, 2} and tree n=4 points and its predictions are
  recorded against every measurement as ``model_residual`` rows.  These
  rows are deliberately *not* named ``model_error``: the CPU test
  substrate's host link is parallel and cache-dominated (copies of a hot
  source can be near-free, device-to-device transfers take an unoptimized
  path), so wallclock residuals carry tens of percent of machine noise —
  the paper's <15 % bar is enforced where its serial-link premise holds,
  on the deterministic ``staging`` suite's ``model_error`` rows
  (``benchmarks/staging.py``, wired into CI).

Each suite returns printable rows; the raw nested dict is kept on the
function's ``last_raw`` for ``benchmarks/run.py --json``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import List, Tuple

Row = Tuple[str, float, str]

_DISPATCH_CHILD = """
import json, statistics, time
import numpy as np
from repro.core import jobs
from repro.core.offload import OffloadRuntime, OffloadConfig, count_collectives
from repro.core.policy import Residency

# Large-enough operands that phase-E staging is a real cost (the paper's
# fine-grained regime is the *ratio* of overhead to work, not tiny data).
job = jobs.make_axpy(16384)
operands, _ = job.make_instance(0)
ITERS = 60
out = {"sweep": {}}

def median_dispatch(fn, iters):
    # dispatch-only: time offload() (async launch), wait outside the timer;
    # medians — CPU-mesh collectives make per-call means noisy
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        h = fn()
        ts.append(time.perf_counter() - t0)
        h.wait()
    return statistics.median(ts) * 1e6

def median_e2e(fn, iters):
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn().wait()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6

for n in (1, 2, 4, 8):
    rt = OffloadRuntime(config=OffloadConfig.extended())
    t0 = time.perf_counter()
    rt.offload(job, operands, n=n).wait()
    cold_us = (time.perf_counter() - t0) * 1e6
    warm_us = median_dispatch(lambda: rt.offload(job, operands, n=n), ITERS)
    warm_e2e_us = median_e2e(lambda: rt.offload(job, operands, n=n), ITERS)
    resident_us = median_dispatch(
        lambda: rt.offload(job, Residency.RESIDENT, n=n), ITERS)
    resident_e2e_us = median_e2e(
        lambda: rt.offload(job, Residency.RESIDENT, n=n), ITERS)
    out["sweep"][str(n)] = {
        "cold_us": cold_us,
        "warm_dispatch_us": warm_us,
        "resident_dispatch_us": resident_us,
        "warm_e2e_us": warm_e2e_us,
        "resident_e2e_us": resident_e2e_us,
        "recompiles_after_warm": len(rt._compiled) - 1,
    }

cmp = {}
for label, cfg in (("multicast", OffloadConfig.extended()),
                   ("baseline", OffloadConfig.baseline())):
    rt = OffloadRuntime(config=cfg)
    rt.offload(job, operands, n=8).wait()          # compile + warm
    cmp[label] = {
        "us": median_e2e(lambda: rt.offload(job, operands, n=8), 30),
        "collectives": count_collectives(rt.lowered_text(job, 8)),
    }
out["compare"] = cmp
print(json.dumps(out))
"""

_SERVE_CHILD = """
import json, time
import jax, numpy as np
from jax.sharding import Mesh
from repro import models as M
from repro.dist.sharding import param_specs, to_shardings
from repro.serve import ServeConfig, ServeEngine

cfg = M.reduced(M.get("smollm-360m"))
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
params = M.init_params(jax.random.key(0), cfg)
params = jax.device_put(params, to_shardings(param_specs(params, mesh), mesh))
prompts = np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 12)).astype(np.int32)

N_NEW = 48
out = {}
for mode in ("host", "step", "chunk"):
    eng = ServeEngine(cfg, params, mesh,
                      ServeConfig(batch=4, max_len=80, decode_mode=mode,
                                  decode_chunk=8))
    eng.generate(prompts, N_NEW)                    # compile + warm
    base = dict(eng.stats)
    t0 = time.perf_counter()
    toks = eng.generate(prompts, N_NEW)
    dt = time.perf_counter() - t0
    out[mode] = {
        "us_per_token": dt / N_NEW * 1e6,
        "h2d_token_puts_per_step": (eng.stats["h2d_token_puts"]
                                    - base["h2d_token_puts"]) / N_NEW,
        "dispatches": eng.stats["xla_dispatches"] - base["xla_dispatches"],
    }
print(json.dumps(out))
"""


_STREAM_CHILD = """
import json, statistics, time
import numpy as np
from repro.api import AUTO, OffloadPolicy, Residency, Session
from repro.core import jobs
from repro.core.offload import count_collectives

# Stream measurement wants the t_compute > t_stage + t_dispatch regime,
# where pipelining hides the whole per-job host cost behind compute (the
# amortization model's max(t_stage, t_compute) term): a mid-size matmul.
# Every mode is a typed policy through the one Session.submit path; the
# legacy hand-picked modes pin their knobs, `auto` lets the planner pick.
job = jobs.make_matmul(256, 256, 256)
N_JOBS = 32
REPEATS = 8
insts, _ = jobs.make_instances(job, 8, seed0=0)
out = {}

SEQ = OffloadPolicy(fuse=1, window=1)
PIPE = OffloadPolicy(fuse=1)                   # window -> planner (n_units)
sess = Session(n_units=4)
sess.submit(job, insts[0], n=8, policy=SEQ).wait()   # warm plan + compile
sess.stage(job, insts[0], n=8)                       # prime residency

def seq_resident():
    for _ in range(N_JOBS):
        sess.submit(job, Residency.RESIDENT, n=8, policy=SEQ).wait()

def seq_restage():
    for i in range(N_JOBS):
        sess.submit(job, insts[i % 8], n=8, policy=SEQ).wait()

# warm the pipelined slot path (and its estimate cache)
sess.submit(job, insts[:4], n=8, policy=PIPE).wait()

def pipelined():
    handles = [sess.submit(job, insts[i % 8], n=8, policy=PIPE)
               for i in range(N_JOBS)]
    for h in handles:
        h.wait()

def pipelined_resident():
    # same data movement as seq_resident (none): isolates what the
    # in-flight window buys — launch+fetch hidden behind compute
    handles = [sess.submit(job, Residency.RESIDENT, n=8, policy=PIPE)
               for _ in range(N_JOBS)]
    for h in handles:
        h.wait()

# AUTO: one list submit, the planner picks fuse/window/staging from the
# cost models (fused launches pipelined through the window)
auto_work = [insts[i % 8] for i in range(N_JOBS)]
auto_handle = sess.submit(job, auto_work, n=8)       # compile + warm
auto_handle.wait()
auto_decision = auto_handle.decision

def auto_submit():
    sess.submit(job, auto_work, n=8).wait()

# Round-robin measurement: this substrate's throughput drifts over the
# child's lifetime (a small CPU share under an 8-device mesh), so timing
# each mode in its own block would bias whichever runs first.  Interleave
# one run of every mode per round and keep each mode's best round.
modes = {
    "seq_resident": seq_resident,
    "seq_restage": seq_restage,
    "pipelined": pipelined,
    "pipelined_resident": pipelined_resident,
    "auto": auto_submit,
}
best = {k: 0.0 for k in modes}
for _ in range(REPEATS):
    for k, fn in modes.items():
        t0 = time.perf_counter()
        fn()
        best[k] = max(best[k], N_JOBS / (time.perf_counter() - t0))

out["stream"] = {
    "seq_resident_jobs_s": best["seq_resident"],
    "seq_restage_jobs_s": best["seq_restage"],
    "pipelined_jobs_s": best["pipelined"],
    "pipelined_resident_jobs_s": best["pipelined_resident"],
    "auto_jobs_s": best["auto"],
    "auto_decision": {"fuse": auto_decision.fuse,
                      "window": auto_decision.window,
                      "staging": auto_decision.staging.value},
    "window": auto_decision.window,
}

# fused dispatch batching: per-job share of one batched launch.  The
# fine-grained regime (tiny job, dispatch floor dominates) is where
# fusing pays — the paper's axpy.
job = jobs.make_axpy(16384)
insts, _ = jobs.make_instances(job, 8, seed0=0)
sf = Session()
sf.stage(job, insts[0], n=8)
res_ts = []
for _ in range(60):
    t0 = time.perf_counter()
    h = sf.submit(job, Residency.RESIDENT, n=8, policy=OffloadPolicy(window=1))
    res_ts.append(time.perf_counter() - t0)
    h.wait()
# least-interference samples: this substrate's 8-device mesh oversubscribes
# a small CPU share, so medians still carry scheduler spikes (same practice
# as the staging_wall suite)
resident_single_us = min(res_ts) * 1e6

fused = {}
for B in (1, 2, 4, 8):
    if B == 1:
        # B=1 is the unfused resident dispatch (the amortization anchor)
        polB = OffloadPolicy(window=1)
    else:
        bi, _ = jobs.make_instances(job, B, seed0=0)
        polB = OffloadPolicy(fuse=B, window=1)
        sf.stage(job, bi, n=8)                 # compile + stage fused batch
    ts = []
    for _ in range(40):
        t0 = time.perf_counter()
        h = sf.submit(job, Residency.RESIDENT, n=8, policy=polB)
        ts.append((time.perf_counter() - t0) / B)
        h.wait()
    fused[str(B)] = {"dispatch_us_per_job": min(ts) * 1e6}

rtf = sf.runtime()
out["fused"] = {
    "resident_single_dispatch_us": resident_single_us,
    "per_job": fused,
    "auto_fuse_pick": sf.estimate(job, batch=8, n=8).decision.fuse,
    "collectives_B2": count_collectives(rtf.lowered_text(job, 8, fuse=2)),
    "collectives_B8": count_collectives(rtf.lowered_text(job, 8, fuse=8)),
}
print(json.dumps(out))
"""

_CONT_SERVE_CHILD = """
import json, time
import jax, numpy as np
from jax.sharding import Mesh
from repro import models as M
from repro.dist.sharding import param_specs, to_shardings
from repro.serve import ServeConfig, ServeEngine

cfg = M.reduced(M.get("smollm-360m"))
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
params = M.init_params(jax.random.key(0), cfg)
params = jax.device_put(params, to_shardings(param_specs(params, mesh), mesh))

BATCH, N_NEW, R = 4, 16, 6
rng = np.random.default_rng(0)
lens = [6, 10, 14, 8, 12, 6][:R]
reqs = [(rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32), N_NEW)
        for s in lens]
# Poisson-ish arrival trace: exponential-ish integer gaps, ~1 every 2 steps
arrivals = np.cumsum(rng.poisson(2.0, size=R))
arrivals = (arrivals - arrivals[0]).tolist()

scfg = ServeConfig(batch=BATCH, max_len=48, prefill_bucket=8)
out = {}

# continuous batching: slots refill from the queue as requests retire
eng = ServeEngine(cfg, params, mesh, scfg)
eng.generate_many(reqs, arrival_steps=arrivals)          # compile + warm
base = dict(eng.stats)
t0 = time.perf_counter()
outs = eng.generate_many(reqs, arrival_steps=arrivals)
dt = time.perf_counter() - t0
total = sum(len(o) for o in outs)
out["continuous"] = {
    "tok_s": total / dt,
    "us_per_token": dt / total * 1e6,
    "dispatches": eng.stats["xla_dispatches"] - base["xla_dispatches"],
    "inserts": eng.stats["prefill_inserts"] - base["prefill_inserts"],
}

# static batching: fixed-shape generate per group of BATCH (last group
# padded through the sub-batch path), prompts right-padded to group max
eng2 = ServeEngine(cfg, params, mesh, scfg)
groups = [list(range(i, min(i + BATCH, R))) for i in range(0, R, BATCH)]
def run_static():
    n = 0
    for g in groups:
        smax = max(lens[r] for r in g)
        prompts = np.zeros((len(g), smax), np.int32)
        for k, r in enumerate(g):
            prompts[k, :lens[r]] = reqs[r][0]
        n += eng2.generate(prompts, N_NEW).size
    return n
run_static()                                             # compile + warm
t0 = time.perf_counter()
total_static = run_static()
dt = time.perf_counter() - t0
out["static"] = {
    "tok_s": total_static / dt,
    "us_per_token": dt / total_static * 1e6,
}
print(json.dumps(out))
"""


_STAGING_CHILD = """
import json, time
import jax, numpy as np
from repro.core import jobs
from repro.core.offload import OffloadRuntime
from repro.core.policy import Staging

# One big replicated operand (the covariance data matrix, broadcast class):
# 32 MiB stays bandwidth-bound — well past the cache sizes below which this
# substrate's host "link" degenerates into near-free hot-cache copies.
M_, N_ = 512 * 32, 256
job = jobs.make_covariance(M_, N_)
operands = {"data": np.random.default_rng(0).standard_normal((M_, N_))}
SIZE = operands["data"].nbytes
ITERS = 11
rt = OffloadRuntime()
out = {"size_bytes": SIZE, "sweep": {}}

for n in (1, 2, 4, 8):
    plan = rt.plan(job, operands, n=n)
    entry = {}
    for mode in ("host_fanout", "tree"):
        h0, d0 = plan.stats.h2d_bytes, plan.stats.d2d_bytes
        ts = []
        cold_ms = None
        for i in range(ITERS + 1):
            t0 = time.perf_counter()
            staged = plan.stage(operands, via=Staging(mode))
            jax.block_until_ready(list(staged.values()))
            dt = (time.perf_counter() - t0) * 1e3
            if i == 0:
                cold_ms = dt
            else:
                ts.append(dt)
            # drop the buffers between iterations: a flat memory profile,
            # so late sweep points don't pay allocator pressure the early
            # ones dodged (and the byte counters stay per-call exact)
            del staged
            plan.invalidate()
        h2d = (plan.stats.h2d_bytes - h0) // (ITERS + 1)
        d2d = (plan.stats.d2d_bytes - d0) // (ITERS + 1)
        entry[mode] = {
            "cold_ms": cold_ms,
            "warm_ms": min(ts),   # least-interference sample on a noisy VM
            "h2d_bytes": h2d,
            "d2d_bytes": d2d,
        }
    out["sweep"][str(n)] = entry
print(json.dumps(out))
"""


def _run_child(code: str, timeout: int = 570, x64: bool = True) -> dict:
    env = dict(os.environ)
    # the children measure the 8-virtual-device CPU substrate; pinning them
    # to the CPU keeps them off an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # paper jobs are float64; the serving model stack is 32-bit only
    env["JAX_ENABLE_X64"] = "true" if x64 else "false"
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed: {proc.stderr[-400:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def offload_wallclock() -> Tuple[List[Row], str]:
    rows: List[Row] = []
    raw = {}

    data = _run_child(_DISPATCH_CHILD)
    raw["dispatch"] = data
    for n, d in sorted(data["sweep"].items(), key=lambda kv: int(kv[0])):
        rows.append((f"offload/axpy/{n}dev/cold", d["cold_us"], "us"))
        rows.append((f"offload/axpy/{n}dev/warm_dispatch",
                     d["warm_dispatch_us"], "us/job"))
        rows.append((f"offload/axpy/{n}dev/resident_dispatch",
                     d["resident_dispatch_us"], "us/job"))
        rows.append((f"offload/axpy/{n}dev/warm_e2e",
                     d["warm_e2e_us"], "us/job"))
        rows.append((f"offload/axpy/{n}dev/resident_e2e",
                     d["resident_e2e_us"], "us/job"))
    cmp = data["compare"]
    rows.append(("offload/axpy/multicast/8dev", cmp["multicast"]["us"], "us"))
    rows.append(("offload/axpy/baseline/8dev", cmp["baseline"]["us"], "us"))
    rows.append(("offload/multicast/chain_depth",
                 cmp["multicast"]["collectives"]["collective-permute"],
                 "collective-permutes"))
    rows.append(("offload/baseline/chain_depth",
                 cmp["baseline"]["collectives"]["collective-permute"],
                 "collective-permutes"))

    serve_note = ""
    try:
        serve = _run_child(_SERVE_CHILD, x64=False)
        raw["serve"] = serve
        for mode, d in serve.items():
            rows.append((f"serve/decode/{mode}", d["us_per_token"], "us/token"))
            rows.append((f"serve/decode/{mode}/h2d_token_puts_per_step",
                         d["h2d_token_puts_per_step"], "puts/step"))
        serve_note = (
            f"; serve us/token host={serve['host']['us_per_token']:.0f} "
            f"step={serve['step']['us_per_token']:.0f} "
            f"chunk={serve['chunk']['us_per_token']:.0f} "
            f"(resident h2d/step = {serve['step']['h2d_token_puts_per_step']:.0f})")
    except Exception as e:                              # noqa: BLE001
        rows.append(("serve/decode/error", 0.0, repr(e)[:120]))

    d8 = data["sweep"]["8"]
    gain = (1 - d8["resident_dispatch_us"] / d8["warm_dispatch_us"]) * 100
    bl, mc = cmp["baseline"], cmp["multicast"]
    derived = (
        f"resident dispatch {d8['resident_dispatch_us']:.0f}us/job vs "
        f"re-staging {d8['warm_dispatch_us']:.0f}us/job at n=8 "
        f"({gain:.0f}% less); baseline chain = "
        f"{bl['collectives']['collective-permute']} ppermutes (= 2(n-1)); "
        f"multicast = {mc['collectives']['all-reduce']} all-reduce; "
        f"wallclock ratio {bl['us'] / mc['us']:.2f}x" + serve_note)
    offload_wallclock.last_raw = raw
    return rows, derived


offload_wallclock.last_raw = {}


def stream_wallclock() -> Tuple[List[Row], str]:
    """Stream suite: hand-picked policies vs the AUTO planner, jobs/s.

    Every mode runs through ``Session.submit``; the legacy modes pin
    their policy knobs (``fuse=1, window=1`` = sequential, ``fuse=1`` =
    pipelined, ``fuse=B, window=1`` = fused) and ``auto`` lets the
    planner pick — its decision is recorded as exact-compare rows.
    """
    rows: List[Row] = []
    data = _run_child(_STREAM_CHILD)
    st, fu = data["stream"], data["fused"]
    rows.append(("stream/matmul256/8dev/seq_resident", st["seq_resident_jobs_s"],
                 "jobs/s"))
    rows.append(("stream/matmul256/8dev/seq_restage", st["seq_restage_jobs_s"],
                 "jobs/s"))
    rows.append(("stream/matmul256/8dev/pipelined", st["pipelined_jobs_s"],
                 "jobs/s"))
    rows.append(("stream/matmul256/8dev/pipelined_resident",
                 st["pipelined_resident_jobs_s"], "jobs/s"))
    rows.append(("stream/matmul256/8dev/auto", st["auto_jobs_s"], "jobs/s"))
    rows.append(("stream/matmul256/8dev/auto/fuse",
                 st["auto_decision"]["fuse"], "jobs"))
    rows.append(("stream/matmul256/8dev/auto/window",
                 st["auto_decision"]["window"], "count"))
    rows.append(("stream/fused/resident_single_dispatch",
                 fu["resident_single_dispatch_us"], "us/job"))
    for b, d in sorted(fu["per_job"].items(), key=lambda kv: int(kv[0])):
        rows.append((f"stream/fused/B{b}/dispatch",
                     d["dispatch_us_per_job"], "us/job"))
    rows.append(("stream/fused/auto_fuse_pick", fu["auto_fuse_pick"],
                 "jobs"))
    rows.append(("stream/fused/allreduce_B2",
                 fu["collectives_B2"]["all-reduce"], "collectives"))
    rows.append(("stream/fused/allreduce_B8",
                 fu["collectives_B8"]["all-reduce"], "collectives"))

    amort = (fu["resident_single_dispatch_us"]
             / max(fu["per_job"]["8"]["dispatch_us_per_job"], 1e-9))
    speedup = (st["pipelined_resident_jobs_s"]
               / max(st["seq_resident_jobs_s"], 1e-9))
    best_fresh = max(st["seq_restage_jobs_s"], st["pipelined_jobs_s"])
    auto_margin = st["auto_jobs_s"] / max(best_fresh, 1e-9)
    ad = st["auto_decision"]
    derived = (
        f"AUTO (fuse={ad['fuse']}, window={ad['window']}, "
        f"staging={ad['staging']}) {st['auto_jobs_s']:.0f} jobs/s = "
        f"{auto_margin:.2f}x the best hand-picked fresh mode "
        f"({best_fresh:.0f} jobs/s); pipelined resident "
        f"{st['pipelined_resident_jobs_s']:.0f} jobs/s vs sequential "
        f"resident {st['seq_resident_jobs_s']:.0f} jobs/s ({speedup:.2f}x); "
        f"fused B=8 dispatch "
        f"{fu['per_job']['8']['dispatch_us_per_job']:.0f}us/job vs resident "
        f"single {fu['resident_single_dispatch_us']:.0f}us/job "
        f"({amort:.1f}x amortization); fused all-reduce count "
        f"B=2 {fu['collectives_B2']['all-reduce']} == "
        f"B=8 {fu['collectives_B8']['all-reduce']}")
    stream_wallclock.last_raw = data
    return rows, derived


stream_wallclock.last_raw = {}


def serve_throughput() -> Tuple[List[Row], str]:
    """Serve suite: continuous batching vs static batches, tokens/s."""
    rows: List[Row] = []
    data = _run_child(_CONT_SERVE_CHILD, x64=False)
    co, stat = data["continuous"], data["static"]
    rows.append(("serve/throughput/continuous", co["tok_s"], "tok/s"))
    rows.append(("serve/throughput/static", stat["tok_s"], "tok/s"))
    rows.append(("serve/throughput/continuous/us_per_token",
                 co["us_per_token"], "us/token"))
    rows.append(("serve/throughput/static/us_per_token",
                 stat["us_per_token"], "us/token"))
    rows.append(("serve/throughput/inserts", co["inserts"], "prefills"))
    ratio = co["tok_s"] / max(stat["tok_s"], 1e-9)
    derived = (
        f"continuous batching {co['tok_s']:.1f} tok/s vs static "
        f"{stat['tok_s']:.1f} tok/s ({ratio:.2f}x) over a Poisson-ish "
        f"arrival trace ({co['inserts']} prefill-inserts, "
        f"{co['dispatches']} decode dispatches)")
    serve_throughput.last_raw = data
    return rows, derived


serve_throughput.last_raw = {}


def staging_wall() -> Tuple[List[Row], str]:
    """Staging sweep: host_fanout vs tree wallclock + calibrated model."""
    from repro.core.simulator import StagingCostModel, model_error

    rows: List[Row] = []
    data = _run_child(_STAGING_CHILD)
    sweep = data["sweep"]
    for n, entry in sorted(sweep.items(), key=lambda kv: int(kv[0])):
        for mode, d in entry.items():
            base = f"staging_wall/cov32MiB/{mode}/n={n}"
            rows.append((f"{base}/cold", d["cold_ms"] * 1e3, "us"))
            rows.append((f"{base}/warm", d["warm_ms"] * 1e3, "us"))
            rows.append((f"{base}/h2d", d["h2d_bytes"], "bytes"))
            rows.append((f"{base}/d2d", d["d2d_bytes"], "bytes"))

    # Calibrate the substrate cost model (host-fanout n in {1, 2} isolate
    # one upload; tree n=4 averages the edge cost over 3 edges) and record
    # measured-vs-predicted per point.  Residual rows are informational on
    # this substrate — see the module docstring; the <15% bar binds the
    # deterministic `staging` suite's model_error rows.  A hot-cache run
    # can measure hf2 <= hf1 (near-free copies), which is uncalibratable —
    # keep the measured rows and skip the residuals rather than fail.
    errs = {}
    try:
        cm = StagingCostModel.calibrate(
            hf1=sweep["1"]["host_fanout"]["warm_ms"],
            hf2=sweep["2"]["host_fanout"]["warm_ms"],
            tree_k=sweep["4"]["tree"]["warm_ms"], k=4,
        )
    except ValueError as e:
        cm = None
        rows.append(("staging_wall/cov32MiB/uncalibratable", 1.0, repr(e)[:80]))
    if cm is not None:
        for n, entry in sweep.items():
            for mode, d in entry.items():
                err = model_error(cm.predict(mode, int(n)), d["warm_ms"])
                errs[f"{mode}/n={n}"] = err
                rows.append((f"staging_wall/cov32MiB/{mode}/n={n}/"
                             "model_residual", err * 100, "percent"))
    hf8 = sweep["8"]["host_fanout"]["warm_ms"]
    tree8 = sweep["8"]["tree"]["warm_ms"]
    rows.append(("staging_wall/cov32MiB/tree_vs_hf/n=8",
                 hf8 / max(tree8, 1e-9), "speedup"))
    h2d_ratio = (sweep["8"]["host_fanout"]["h2d_bytes"]
                 / sweep["8"]["tree"]["h2d_bytes"])
    residual_note = (
        f"calibrated-model worst residual {max(errs.values()) * 100:.1f}% "
        "(substrate-noisy; the <15% bar binds the deterministic staging "
        "suite)" if errs else
        "cost model uncalibratable this run (hot-cache measurements)")
    derived = (
        f"tree {tree8:.1f}ms vs host_fanout {hf8:.1f}ms at n=8 "
        f"({hf8 / tree8:.2f}x, 32MiB operand); tree h2d is 1 upload at "
        f"every n (host_fanout moves {h2d_ratio:.0f}x the host-link bytes "
        f"at n=8); " + residual_note)
    staging_wall.last_raw = data
    return rows, derived


staging_wall.last_raw = {}
