"""Perf-linter acceptance bench — seeded inefficiencies, measured regret.

One deliberately suboptimal configuration per ``OFLP1##`` code; each is
linted, every machine-applicable autofix is applied, and the unfixed /
autofixed / model-optimal configurations are *measured* in the
deterministic cycle domain — the discrete-event simulator where one
exists (``simulate_staging`` for OFLP101, ``simulate_graph`` for
OFLP104), the shared amortization model otherwise.  Each case records

    perflint/<code>/regret_unfixed   measured(unfixed)  / measured(optimal)
    perflint/<code>/regret_fixed     measured(autofixed) / measured(optimal)

and self-asserts ``regret_fixed <= REGRET_BAR`` (1.05): the linter's
advice must recover the seeded waste, not merely shuffle it.  Two more
deterministic rows pin the CI gate itself (``perflint/corpus/*``: the
checked-in graphs carry zero non-baselined findings), and a subprocess
measures the wallclock cost of ``Session.submit(lint=True)`` on a warm
dispatch — self-asserting overhead < 5 % (``perflint/lint/*`` rows are
excluded from ``--check`` like every other pure-wallclock row).
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path
from typing import List, Tuple

Row = Tuple[str, float, str]

#: autofixed cycles may exceed model-optimal cycles by at most this factor
REGRET_BAR = 1.05

#: lint=True on a warm dispatch may cost at most this much extra wallclock
OVERHEAD_BAR_PCT = 5.0

_ROOT = Path(__file__).resolve().parent.parent


def _regret_rows() -> Tuple[List[Row], dict]:
    import numpy as np

    from repro.analysis import perflint
    from repro.core import jobs, simulator
    from repro.core import model as amodel
    from repro.core.params import DEFAULT_PARAMS
    from repro.core.policy import AUTO, Staging
    from repro.core.scoreboard import GraphNode, Ref
    from repro.core.session import Planner

    planner = Planner()
    p = DEFAULT_PARAMS
    rows: List[Row] = []
    raw: dict = {}

    def record(code: str, unfixed: float, fixed: float,
               optimal: float) -> None:
        ru, rf = unfixed / optimal, fixed / optimal
        assert fixed <= unfixed, (code, unfixed, fixed)
        assert rf <= REGRET_BAR, (code, fixed, optimal, rf)
        rows.append((f"perflint/{code}/regret_unfixed", ru, "ratio"))
        rows.append((f"perflint/{code}/regret_fixed", rf, "ratio"))
        raw[code] = {"unfixed": unfixed, "fixed": fixed,
                     "optimal": optimal}

    def codes(findings) -> set:
        return {f.code for f in findings}

    ids8 = list(range(8))

    # OFLP101 — staging pinned to the O(n) host fan-out on a large
    # replicated operand; measured by the discrete-event staging model.
    job = jobs.make_atax(64, 4096)
    ops, _ = job.make_instance(0)
    pinned = AUTO.pinned(staging=Staging.HOST_FANOUT)
    fs = perflint.lint(job, ops, policy=pinned, clusters=ids8)
    assert "OFLP101" in codes(fs), codes(fs)
    fixed_pol = perflint.suggested_policy(fs, pinned)
    rep = planner.replicated_bytes(job, ops)
    record("OFLP101",
           planner.staging_cost(rep, ids8, Staging.HOST_FANOUT),
           planner.staging_cost(rep, ids8, fixed_pol.staging),
           min(planner.staging_cost(rep, ids8, m)
               for m in (Staging.HOST_FANOUT, Staging.TREE)))

    # OFLP102/OFLP103 — fine-grained batch with fuse (resp. window)
    # pinned below the model's pick; measured by the amortization model
    # over the decisions the session would actually run.
    job = jobs.make_axpy(256)
    ops, _ = job.make_instance(0)
    batch = 16

    def batch_total(policy) -> float:
        d = planner.decide(job, 8, batch, policy, 4, operands=ops)
        return batch * planner.per_job_cycles(job.spec, 8, d.fuse, d.window)

    for code, pin in (("OFLP102", {"fuse": 1}), ("OFLP103", {"window": 1})):
        pinned = AUTO.pinned(**pin)
        fs = perflint.lint(job, ops, policy=pinned, batch=batch, n=8)
        assert code in codes(fs), (code, codes(fs))
        record(code, batch_total(pinned),
               batch_total(perflint.suggested_policy(fs, pinned)),
               batch_total(AUTO))

    # OFLP104 — the reshard chain from the checked-in corpus; measured
    # by the discrete-event graph simulator.  Autofixing realigns one
    # edge per round, so apply to a fixpoint (bounded).
    job = jobs.make_axpy(2048)
    ops, _ = job.make_instance(0)
    ops = {k: np.asarray(v) for k, v in ops.items()}

    def serial(clusters_mid, clusters_tail):
        return [
            GraphNode(job, ops, name="wide"),
            GraphNode(job, {"x": ops["x"], "y": Ref("wide")}, name="narrow",
                      clusters=clusters_mid),
            GraphNode(job, {"x": ops["x"], "y": Ref("narrow")}, name="tail",
                      clusters=clusters_tail),
        ]

    def makespan(nds) -> float:
        gjobs, _ = perflint.graph_jobs(nds, default_width=8)
        return simulator.simulate_graph(gjobs).makespan

    nodes = serial([0, 1, 2, 3], None)
    fs = perflint.lint_graph(nodes, default_width=8)
    assert "OFLP104" in codes(fs), codes(fs)
    cur, fix_rounds = nodes, 0
    for _ in range(8):
        fs = perflint.lint_graph(cur, default_width=8)
        if not fs:
            break
        applied = perflint.apply(fs, nodes=cur)
        assert applied.nodes is not None
        cur = applied.nodes
        fix_rounds += 1
    record("OFLP104", makespan(nodes), makespan(cur),
           makespan(serial(None, None)))
    raw["OFLP104"]["fix_rounds"] = fix_rounds

    # OFLP105 — a misaligned 8-wide selection needing 4 multicast
    # requests; measured as the job total plus the replayed dispatch
    # constant per extra request.
    job = jobs.make_axpy(2048)
    ops, _ = job.make_instance(0)
    mis = list(range(1, 9))
    fs = perflint.lint(job, ops, clusters=mis)
    assert "OFLP105" in codes(fs), codes(fs)
    fixed_sel = perflint.apply(fs, clusters=mis).clusters
    assert fixed_sel is not None

    def sel_cost(sel) -> float:
        reqs = simulator.selection_requests(sel)
        return (amodel.predict_total_v2(job.spec, len(list(sel)), p)
                + (reqs - 1) * perflint.dispatch_replay_cycles(
                    job.spec, len(list(sel)), p))

    record("OFLP105", sel_cost(mis), sel_cost(fixed_sel),
           sel_cost(ids8))

    # OFLP106 — a staged residency never redispatched: the dead stage's
    # cycles (the session ledger's formula) are pure waste on top of the
    # dispatch the submit actually pays.
    job = jobs.make_axpy(2048)
    ops, _ = job.make_instance(0)
    total_b = sum(int(np.asarray(v).nbytes) for v in ops.values())
    rep_b = planner.replicated_bytes(job, ops)
    waste = 0.0
    if rep_b > 0:
        waste += planner.staging_cost(rep_b, ids8,
                                      planner.pick_staging(rep_b, ids8))
    if total_b > rep_b:
        waste += (p.dma_setup_one
                  + (total_b - rep_b) / p.wide_bw_bytes_per_cycle
                  + p.dma_latency)
    base = amodel.predict_total_v2(job.spec, 8, p)
    record("OFLP106", base + waste, base, base)

    # OFLP107 — donation off on a fused batch whose stacked input dies
    # at launch: each launch pays a copy of the fused output buffer.
    job = jobs.make_axpy(256)
    ops, _ = job.make_instance(0)
    batch = 16
    fs = perflint.lint(job, ops, batch=batch, n=8)
    assert "OFLP107" in codes(fs), codes(fs)
    d = planner.decide(job, 8, batch, AUTO, 4, operands=ops)
    launches = math.ceil(batch / d.fuse)
    out_b = int(np.asarray(ops["y"]).nbytes)
    copy_waste = launches * perflint.donation_copy_cycles(
        out_b * d.fuse, p)
    base = batch_total(AUTO)
    record("OFLP107", base + copy_waste, base, base)

    return rows, raw


def _corpus_rows() -> Tuple[List[Row], dict]:
    """The CI gate, as rows: zero non-baselined findings over the
    checked-in graphs (the same corpus/baseline ``make lint-graphs``
    loads)."""
    from repro import lint as lint_cli

    corpus = lint_cli.load_corpus(lint_cli.DEFAULT_CORPUS, root=_ROOT)
    results = lint_cli.lint_corpus(corpus)
    baseline = lint_cli.load_baseline(_ROOT / lint_cli.DEFAULT_BASELINE)
    fresh = lint_cli.new_findings(results, baseline)
    assert not fresh, [f"{g}: {f}" for g, f in fresh]
    total = sum(len(f) for _, f in results)
    rows = [
        ("perflint/corpus/graphs", float(len(corpus)), "graphs"),
        ("perflint/corpus/findings", float(total), "findings"),
        ("perflint/corpus/nonbaselined_findings", float(len(fresh)),
         "findings"),
    ]
    return rows, {"graphs": len(corpus), "findings": total,
                  "fresh": len(fresh)}


_OVERHEAD_CHILD = """
import json, time
import numpy as np
from repro.core import jobs
from repro.core.session import Session

job = jobs.make_axpy(16384)
ops, _ = job.make_instance(0)
sess = Session()
sess.submit(job, ops).wait()            # warm plan + compile
ITERS, REPS = 100, 5

def measure(lint):
    sess.submit(job, ops, lint=lint).wait()     # cold lint paid here
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            sess.submit(job, ops, lint=lint).wait()
        best = min(best, (time.perf_counter() - t0) / ITERS * 1e6)
    return best

from repro.analysis import perflint
t0 = time.perf_counter()
perflint.lint(job, ops, batch=1, n=8)
cold_us = (time.perf_counter() - t0) * 1e6

off_us = measure(False)
on_us = measure(True)
print(json.dumps({"cold_us": cold_us, "off_us": off_us, "on_us": on_us}))
"""


def _overhead_rows() -> Tuple[List[Row], dict]:
    env = dict(os.environ)
    # an 8-virtual-device CPU measurement: pinned to the CPU so it never
    # contends for an accelerator the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_ENABLE_X64"] = "true"
    env["PYTHONPATH"] = (str(_ROOT / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_OVERHEAD_CHILD)],
        capture_output=True, text=True, env=env, timeout=570)
    if proc.returncode != 0:
        raise RuntimeError(f"overhead subprocess failed: "
                           f"{proc.stderr[-400:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    pct = (data["on_us"] - data["off_us"]) / data["off_us"] * 100.0
    assert pct < OVERHEAD_BAR_PCT, (data, pct)
    rows = [
        ("perflint/lint/cold_us", data["cold_us"], "us"),
        ("perflint/lint/warm_submit_us", data["off_us"], "us"),
        ("perflint/lint/warm_submit_lint_us", data["on_us"], "us"),
        ("perflint/lint/overhead_pct", pct, "percent"),
    ]
    return rows, dict(data, overhead_pct=pct)


def perflint_suite() -> Tuple[List[Row], str]:
    rows, raw = _regret_rows()
    crows, craw = _corpus_rows()
    orows, oraw = _overhead_rows()
    rows += crows + orows
    worst = max(v for n, v, _ in rows if n.endswith("regret_fixed"))
    derived = (f"autofixed regret <= {worst:.3f} (bar {REGRET_BAR}) on "
               f"{len(raw)} seeded codes; corpus {craw['fresh']} "
               f"non-baselined finding(s); lint overhead "
               f"{oraw['overhead_pct']:+.2f}% (< {OVERHEAD_BAR_PCT}%)")
    perflint_suite.last_raw = {"regret": raw, "corpus": craw,
                               "overhead": oraw}
    return rows, derived


perflint_suite.last_raw = {}
