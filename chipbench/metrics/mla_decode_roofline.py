"""Share of the latent decode attention's device time that its work needs
at the roofline, in the traced decode steps of an MLA serve cell.

Pairs the program's ``serve.decode`` spans with the runs of
``jit_serve_decode_step`` on the first chip, in order, for the runs that
began and ended in the traced window.  The work is counted from shapes
(``bench/counts_mla.py``): each step's ``kv_positions`` (the cached
positions its live slots attend) latent columns read once, every layer.
The least time is the larger of those bytes over the HBM peak and the
kernel's operations over the bfloat16 peak; the time is that of the
operations named ``mla_decode_attention`` inside those runs.  A program
without the span's count or the kernel gives nothing to read."""

from bench import spans
from bench.counts_mla import MLAShare

PROGRAM = "jit_serve_decode_step"
KERNEL = f"{PROGRAM}/mla_decode_attention"


def read(rec):
    t = rec.traced
    if (rec.kind != "serve" or "kv_lora_rank" not in rec.config
            or t is None or t.trace is None or rec.peaks is None):
        return None
    pt = spans.of(rec)
    if pt is None or not pt.modules:
        return None
    lo, hi = pt.window
    dev = min(pt.modules)
    steps = [(s, e) for s, e, p in pt.modules[dev]
             if p == PROGRAM and s >= lo and e <= hi]
    kv = [float(meta["kv_positions"]) for s, e, n, meta in pt.program_spans
          if n == "serve.decode" and s >= lo and "kv_positions" in meta]
    k = min(len(steps), len(kv))
    if not k:
        return None
    steps = steps[:k]
    kernel_ns = sum(e - s for s, e, n in t.trace.ops.get(dev, [])
                    if n.startswith(KERNEL)
                    and steps[0][0] <= s and e <= steps[-1][1])
    if not kernel_ns:
        return None
    flops, nbytes = MLAShare.of(rec.config).kernel_cost(sum(kv[:k]))
    least = max(flops / rec.peaks.flops_bf16, nbytes / rec.peaks.hbm_bw)
    return 100.0 * least / (kernel_ns * 1e-9)
