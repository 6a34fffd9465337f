"""Share of the serve cache that the decode steps copy from HBM, in the
traced part of a serve cell's window.

Each ``serve.decode`` span of the program that began and ended in the
traced window carries ``kv_read_positions``: the cached positions the
decode attention copies for the step's live slots, each slot's prefix and
its own cell rounded up to the kernel's chunks.  The share is their sum over
those spans, over the slots x ``max_len`` positions of the cache for each
span.  A program whose spans carry no such count gives nothing to read."""

from bench import spans


def read(rec):
    if rec.kind != "serve":
        return None
    pt = spans.of(rec)
    if pt is None:
        return None
    lo, hi = pt.window
    got = [float(meta["kv_read_positions"])
           for s, e, n, meta in pt.program_spans
           if n == "serve.decode" and lo <= s and e <= hi
           and "kv_read_positions" in meta]
    if not got:
        return None
    serve = rec.config["serve"]
    return 100.0 * sum(got) / (len(got) * serve["batch"] * serve["max_len"])
