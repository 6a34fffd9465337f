"""The program's spans and programs in a trace (``bench/spans.py``) and the
per-layer metrics that read them: by hand on made-up spans, on a CPU run
of ``Session.submit(...).wait()``, and on a trace recorded on a TPU v5e."""

import _chipbench_tiny as tiny

import pytest

from bench import spans
from bench.harness import reader
from bench.record import Record, Traced
from bench.trace import Trace, find_xplane

FIXTURE = tiny.BENCH / "tests" / "data" / "spans.xplane.pb"
PROBE = tiny.BENCH / "tests" / "data" / "probe.xplane.pb"

SPAN_METRICS = ("verify_ms", "plan_ms", "stage_ms", "launch_ms", "fetch_ms")
SERVE_METRICS = ("decode_step_ms", "prefill_share.serve")


def made_up(program_spans, window=(0, 100)):
    pt = object.__new__(spans.ProgramTrace)
    pt.program_spans = sorted(
        [(s, e, n, {}) for s, e, n in program_spans],
        key=lambda sp: (sp[0], -sp[1]))
    pt.modules, pt.window = {}, window
    return pt


#: a submit holding verify and stage, then a wait holding a fetch
NESTED = [(10, 40, "offload.submit"), (12, 15, "offload.verify"),
          (20, 35, "offload.stage"), (50, 90, "offload.wait"),
          (55, 85, "offload.fetch")]


def test_self_time_is_less_the_children():
    pt = made_up(NESTED)
    assert pt.self_ns() == {"offload.submit": 30 - 3 - 15,
                            "offload.verify": 3, "offload.stage": 15,
                            "offload.wait": 10, "offload.fetch": 30}
    # clipped to the window: the wait loses 10 ns, its fetch 5
    assert made_up(NESTED, (0, 80)).self_ns()["offload.fetch"] == 25


def test_innermost_span_cuts_the_window():
    B = spans.BETWEEN
    assert made_up(NESTED).innermost() == [
        (0, 10, B), (10, 12, "offload.submit"), (12, 15, "offload.verify"),
        (15, 20, "offload.submit"), (20, 35, "offload.stage"),
        (35, 40, "offload.submit"), (40, 50, B), (50, 55, "offload.wait"),
        (55, 85, "offload.fetch"), (85, 90, "offload.wait"), (90, 100, B)]


def test_idle_time_by_innermost_span():
    pt = made_up(NESTED)
    ops = [(0, 14, "a"), (36, 60, "b"), (80, 100, "c")]
    # idle 14-36: verify 1 ns, submit 5 + 1, stage 15; idle 60-80: fetch 20
    idle = spans.idle_by_span(pt, ops)
    assert idle == pytest.approx({
        "offload.verify": 1e-9, "offload.submit": 6e-9,
        "offload.stage": 15e-9, "offload.fetch": 20e-9})


def offload_record(trace, jobs, counters=None):
    rec = Record("offload", {}, {}, 1, None)
    rec.traced = Traced(trace=trace, jobs=list(jobs),
                        counters=counters or {})
    return rec


def test_a_cpu_submit_writes_nested_spans_with_one_job_id(tmp_path,
                                                         monkeypatch):
    """Under the profiler, each ``submit`` holds the gate, the planner,
    staging and the launch, each ``wait`` the fetch; the launch and the
    wait carry the same job id."""
    import jax

    from repro.api import PAPER_JOBS, Session

    sess = Session(devices=jax.devices()[:1])
    jobs = [PAPER_JOBS[k](**tiny.TINY_JOBS[k]) for k in ("axpy", "bfs")]
    ops = [j.make_instance(0)[0] for j in jobs]
    for j, o in zip(jobs, ops):
        sess.submit(j, o).wait()
    logdir = tmp_path / "trace"
    jax.profiler.start_trace(str(logdir))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(2):
                for j, o in zip(jobs, ops):
                    sess.submit(j, o).wait()
    finally:
        jax.profiler.stop_trace()
    sess.close()
    pt = spans.ProgramTrace(find_xplane(str(logdir)))
    tops = [sp for sp in pt.program_spans
            if sp[2] in ("offload.submit", "offload.wait")]
    assert [n for _, _, n, _ in tops] == ["offload.submit",
                                          "offload.wait"] * 4
    assert [m["job_name"] for _, _, n, m in tops
            if n == "offload.submit"] == [j.spec.name for j in jobs] * 2
    for (s0, e0, _, _), (s1, e1, _, wait_meta) in zip(tops[::2], tops[1::2]):
        inner = {n: m for s, e, n, m in pt.program_spans
                 if s0 <= s and e <= e0 and n != "offload.submit"}
        assert set(inner) == {"offload.verify", "offload.plan",
                              "offload.stage", "offload.launch"}
        assert inner["offload.launch"]["job"] == wait_meta["job"]
        assert [n for s, e, n, _ in pt.program_spans
                if s1 <= s and e <= e1] == ["offload.wait", "offload.fetch"]
    # every span metric reads it through the record, as a traced run does
    monkeypatch.setattr(spans, "SEARCH", [tmp_path])
    rec = offload_record(Trace(find_xplane(str(logdir))), ["j"] * 4)
    for name in SPAN_METRICS:
        value = reader(name)(rec)
        assert value is not None and value > 0
    assert reader("fetch_ms")(rec) == pytest.approx(
        sum(e - s for s, e in pt.in_window("offload.fetch")) * 1e-6 / 4)


def test_an_older_program_gives_no_span_metrics(monkeypatch):
    """The recorded probe has only the benchmark's own spans: the span and
    program readers find nothing and return None."""
    monkeypatch.setattr(spans, "SEARCH", [PROBE.parent])
    rec = offload_record(Trace(str(PROBE)), ["axpy"] * 4)
    for name in SPAN_METRICS:
        assert reader(name)(rec) is None
    rec.kind = "serve"
    for name in SERVE_METRICS:
        assert reader(name)(rec) is None


def test_readers_off_their_cells():
    serve = Record("serve", {}, {}, 1, None)
    offload = offload_record(None, ["axpy"], {"d2d_bytes": 8})
    for name in SPAN_METRICS + ("d2d_bytes_per_job",):
        assert reader(name)(serve) is None
    for name in SERVE_METRICS:
        assert reader(name)(offload) is None
    assert reader("d2d_bytes_per_job")(offload) == 8.0
    offload.traced = None
    assert reader("d2d_bytes_per_job")(offload) is None


@pytest.fixture(scope="module")
def recorded():
    """A trace recorded on one TPU v5e: two jobs (axpy N=1024, bfs V=64)
    through ``Session.submit``/``wait`` inside the benchmark's ``submit``
    and ``wait`` spans, then one ``generate_many`` of three requests into
    two slots of reduced smollm-360m (3 admissions, 4 decode steps), all
    inside the benchmark's ``window`` span."""
    return Trace(str(FIXTURE))


def raw_events(plane_test, line_test):
    """[(start_ns, end_ns, name)] of the fixture's events, read apart from
    the code under test."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(FIXTURE)).planes:
        for line in plane.lines:
            if plane_test(plane.name) and line_test(line.name):
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events]
    return sorted(out)


def merged_ns(intervals):
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        total += max(0.0, e - max(s, end))
        end = max(end, e)
    return total


@pytest.mark.parametrize("metric, span, want", [
    ("verify_ms", "offload.verify", 0.03057),
    ("plan_ms", "offload.plan", 0.073795),
    ("stage_ms", "offload.stage", 0.601965),
    ("launch_ms", "offload.launch", 0.326275),
    ("fetch_ms", "offload.fetch", 0.92742),
])
def test_recorded_span_metrics(recorded, monkeypatch, metric, span, want):
    monkeypatch.setattr(spans, "SEARCH", [FIXTURE.parent])
    host = raw_events(lambda p: p.startswith("/host:"), lambda _: True)
    by_hand = sum(e - s for s, e, n in host if n == span) * 1e-6 / 2
    rec = offload_record(recorded, ["axpy", "bfs"])
    assert reader(metric)(rec) == pytest.approx(by_hand)
    assert by_hand == pytest.approx(want)


def test_recorded_serve_metrics(recorded, monkeypatch):
    monkeypatch.setattr(spans, "SEARCH", [FIXTURE.parent])
    dev = lambda p: p == "/device:TPU:0"                        # noqa: E731
    modules = raw_events(dev, lambda n: n == "XLA Modules")
    steps = [e - s for s, e, n in modules
             if n.startswith("jit_serve_decode_step(")]
    assert len(steps) == 4
    rec = Record("serve", {}, {}, 1, None)
    rec.traced = Traced(trace=recorded)
    assert reader("decode_step_ms")(rec) == pytest.approx(
        sum(steps) / 4 * 1e-6)
    assert reader("decode_step_ms")(rec) == pytest.approx(0.03763075)
    # admission: operations that ran inside a prefill or insert program,
    # over every operation's time (operations nest: count each ns once)
    admit = [(s, e) for s, e, n in modules
             if n.startswith(("jit_serve_prefill(", "jit_serve_insert("))]
    ops = raw_events(dev, lambda n: n == "XLA Ops")
    inside = [(s, e) for s, e, _ in ops
              if any(a <= s < b for a, b in admit)]
    by_hand = 100 * merged_ns(inside) / merged_ns([(s, e) for s, e, _ in ops])
    assert reader("prefill_share.serve")(rec) == pytest.approx(by_hand)
    assert by_hand == pytest.approx(18.7125158)
    # the programs are the program's own names, none of the old ones
    names = {n.split("(")[0] for _, _, n in modules}
    assert {"jit_offload_axpy", "jit_offload_bfs", "jit_serve_prefill",
            "jit_serve_insert", "jit_serve_decode_step"} <= names
    assert not names & {"jit_program", "jit__lambda", "jit_ins", "jit_step"}


def test_recorded_trace_keeps_what_it_had(recorded):
    """The program's spans do not reach the benchmark's own reduction:
    its spans, window and busy time read as before."""
    assert [n for _, _, n in recorded.spans] == ["submit", "wait"] * 2 + [
        "generate_many"]
    assert recorded.window == spans.ProgramTrace(str(FIXTURE)).window
