"""The program's own spans and programs in a profiler trace.

The program writes host spans through ``repro.tracing.span``: ``offload.*``
inside ``Session.submit`` and ``SessionHandle.wait``, ``serve.*`` inside
``ServeEngine.generate_many``.  Each device's ``XLA Modules`` line names the
program that each stretch of its time belongs to (``jit_offload_axpy``,
``jit_serve_decode_step``).  ``ProgramTrace`` keeps both, read from the same
file as ``bench.trace.Trace`` and on the same clock.

A metric reader gets the run's record, which holds the ``Trace`` but not its
file: ``of(rec)`` reads the trace under ``chipbench_traces/`` whose
``window`` span is the record's, once a run.  A program without these spans
or names (an older one) gives readers nothing to read, and they return None.

    python chipbench/bench/spans.py <trace directory or .xplane.pb>

prints, as JSON, each program span's total and self time in the window, and
the first device's idle time by the innermost program span open over it.
"""

from __future__ import annotations

import collections
import glob
import os
import sys
import weakref
from pathlib import Path
from typing import Optional

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.trace import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, WINDOW_SPAN, clip, gaps, union,
)

#: the program's span names start with these
PREFIXES = ("offload.", "serve.")
#: where the benchmark writes its traces (``run.py``)
SEARCH = [Path(__file__).resolve().parents[2] / "chipbench_traces"]
#: what ``innermost`` calls a stretch that no program span covers
BETWEEN = "between spans"

Span = tuple[float, float, str, dict]      # (start_ns, end_ns, name, meta)


class ProgramTrace:
    """The program's spans and each device's programs in one trace."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        #: host spans of the program, sorted by start
        self.program_spans: list[Span] = []
        #: device index -> [(start_ns, end_ns, program)], sorted
        self.modules: dict[int, list[tuple[float, float, str]]] = {}
        windows = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        self.modules[int(m.group(1))] = sorted(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name.split("(")[0]) for ev in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        end = ev.start_ns + ev.duration_ns
                        if ev.name.startswith(PREFIXES):
                            self.program_spans.append(
                                (ev.start_ns, end, ev.name, dict(ev.stats)))
                        elif ev.name == WINDOW_SPAN:
                            windows.append((ev.start_ns, end))
        self.program_spans.sort(key=lambda sp: (sp[0], -sp[1]))
        self.window = ((min(s for s, _ in windows),
                        max(e for _, e in windows)) if windows else None)

    def in_window(self, name: str) -> list[tuple[float, float]]:
        """The spans called ``name``, clipped to the window."""
        lo, hi = self.window
        return clip([(s, e) for s, e, n, _ in self.program_spans
                     if n == name], lo, hi)

    def self_ns(self) -> dict[str, float]:
        """Each span's time in the window less its children's, by name."""
        lo, hi = self.window
        out: collections.Counter = collections.Counter()
        stack: list[list] = []              # [end, name, self time]
        for s, e, n, _ in self.program_spans:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            while stack and stack[-1][0] <= s:
                _, pn, t = stack.pop()
                out[pn] += t
            if stack:
                stack[-1][2] -= e - s
            stack.append([e, n, e - s])
        for _, pn, t in stack:
            out[pn] += t
        return dict(out)

    def innermost(self) -> list[tuple[float, float, str]]:
        """The window cut into stretches, each with the innermost span
        open over it (``between spans`` where none is)."""
        lo, hi = self.window
        out, stack, t = [], [], lo     # stack: [(end, name)]

        def close_until(limit):
            nonlocal t
            while stack and stack[-1][0] <= limit:
                end, name = stack.pop()
                if end > t:
                    out.append((t, end, name))
                    t = end

        for s, e, n, _ in self.program_spans:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            close_until(s)
            if s > t:
                out.append((t, s, stack[-1][1] if stack else BETWEEN))
                t = s
            stack.append((e, n))
        close_until(hi)
        if hi > t:
            out.append((t, hi, BETWEEN))
        return out


def idle_by_span(pt: ProgramTrace, ops: list) -> dict[str, float]:
    """Seconds of the window in which the device ran none of ``ops``
    (``[(start_ns, end_ns, name)]``), by the innermost span open then."""
    lo, hi = pt.window
    idle = gaps(union(clip([(s, e) for s, e, _ in ops], lo, hi)), lo, hi)
    out: collections.Counter = collections.Counter()
    i = 0
    for s, e, name in pt.innermost():
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < e:
            out[name] += (min(e, idle[j][1]) - max(s, idle[j][0])) * 1e-9
            j += 1
    return dict(out)


_READ: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def of(rec) -> Optional[ProgramTrace]:
    """The program's side of the run's trace, or None without one."""
    t = rec.traced
    if t is None or t.trace is None:
        return None
    if t.trace not in _READ:
        _READ[t.trace] = _find(t.trace.window)
    return _READ[t.trace]


def _find(window) -> Optional[ProgramTrace]:
    paths = [p for root in SEARCH
             for p in glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                                recursive=True)]
    for path in sorted(paths, key=_mtime, reverse=True):
        try:
            pt = ProgramTrace(path)
        except Exception:           # a file being written or removed
            continue
        if pt.window == window:
            return pt
    return None


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:                 # removed since the glob
        return 0.0


def per_job_ms(rec, name: str) -> Optional[float]:
    """Milliseconds in the program's span ``name`` per job completed in
    the traced window, or None where the program wrote no such span."""
    t = rec.traced
    if rec.kind != "offload" or t is None or not t.jobs:
        return None
    pt = of(rec)
    spans = pt.in_window(name) if pt is not None else []
    if not spans:
        return None
    return sum(e - s for s, e in spans) * 1e-6 / len(t.jobs)


def module_ms(rec, program: str) -> list[float]:
    """Device milliseconds of each run of ``program`` (``jit_<name>``) that
    began and ended inside the traced window, on every device."""
    pt = of(rec)
    if pt is None:
        return []
    lo, hi = pt.window
    return [(e - s) * 1e-6 for mods in pt.modules.values()
            for s, e, p in mods if p == program and s >= lo and e <= hi]


def main(argv: list[str]) -> int:
    import json

    from bench.trace import Trace, find_xplane

    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    pt, tr = ProgramTrace(path), Trace(path)
    total: collections.Counter = collections.Counter()
    count: collections.Counter = collections.Counter()
    lo, hi = pt.window
    for s, e, n, _ in pt.program_spans:
        if e > lo and s < hi:
            total[n] += (min(e, hi) - max(s, lo)) * 1e-9
            count[n] += 1
    device = min(tr.ops) if tr.ops else None
    print(json.dumps({
        "window_s": tr.window_s,
        "spans": {n: {"count": count[n], "total_s": total[n]}
                  for n in sorted(total)},
        "self_s": {n: t * 1e-9 for n, t in sorted(pt.self_ns().items())},
        "idle_by_span_s": (idle_by_span(pt, tr.ops[device])
                           if device is not None else {}),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
