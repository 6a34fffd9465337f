"""Forward operations that the window's requests need of this chip's share
of an MLA + MoE model (every prompt token, non-absorbed, and every
position that yields a served token, absorbed; ``bench/counts_mla.py``),
over the window's seconds times the chips' peak bfloat16 FLOP/s: the whole
step's share of the chip's peak."""

from bench.counts_mla import MLAShare


def read(rec):
    if (rec.kind != "serve" or "kv_lora_rank" not in rec.config
            or rec.peaks is None or not rec.requests):
        return None
    share = MLAShare.of(rec.config)
    flops = sum(share.prefill_flops(p) + share.decode_flops(p, m)
                for p, m in rec.requests)
    return 100.0 * flops / (rec.window_s * rec.chips * rec.peaks.flops_bf16)
