"""Mean device milliseconds of one decode step: the runs of the program
``jit_serve_decode_step`` (``XLA Modules`` line of the trace) that began
and ended in the traced part of the window, serve cells."""

from bench.spans import module_ms


def read(rec):
    if rec.kind != "serve":
        return None
    steps = module_ms(rec, "jit_serve_decode_step")
    return sum(steps) / len(steps) if steps else None
