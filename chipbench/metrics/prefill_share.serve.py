"""Share of the device's busy time in the traced part of the window that
went to admission: operations of the programs ``jit_serve_prefill`` and
``jit_serve_insert`` (their union, the mean over the cell's chips), serve
cells.  The traced part is the first ``trace_seconds`` of a call, which
holds the first fill of every slot, so the share reads above a whole
call's."""

from bench.trace import clip, covered, union

PROGRAMS = ("jit_serve_prefill/", "jit_serve_insert/")


def read(rec):
    t = rec.traced
    if rec.kind != "serve" or t is None or t.trace is None:
        return None
    tr = t.trace
    lo, hi = tr.window
    shares = []
    for d, busy in tr.busy_s().items():
        mine = [(s, e) for s, e, n in tr.ops[d] if n.startswith(PROGRAMS)]
        if busy and mine:
            shares.append(covered(union(clip(mine, lo, hi))) * 1e-9 / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
