"""Operations and bytes of the MLA + MoE block (DeepSeek-V2), counted from
the configuration's shapes for the share of the model that this chip holds.

Like ``bench/counts.py`` these read nothing of the implementation.  Routed
work is the expected share of a token's ``top_k`` experts that lie here:
``top_k * held / router_width`` expert FFNs a token.  Prefill computes
attention the long way (keys and values up-projected per head); a decode
position absorbs ``W_UK`` into the query and ``W_UV`` into the output, and
attends the latent cache directly.
"""

from __future__ import annotations

import dataclasses

BF16 = 2


@dataclasses.dataclass(frozen=True)
class MLAShare:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    nope: int
    rope: int
    rank: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    held: int
    router_width: int
    top_k: int
    shared: int
    vocab: int

    @classmethod
    def of(cls, cfg: dict) -> "MLAShare":
        return cls(cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
                   cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["kv_lora_rank"], cfg["v_head_dim"],
                   cfg["intermediate_size"], cfg["moe_intermediate_size"],
                   cfg["n_routed_experts"],
                   cfg["published"]["n_routed_experts"],
                   cfg["num_experts_per_tok"], cfg["n_shared_experts"],
                   cfg["vocab_size"])

    @property
    def cache_row_bytes(self) -> int:
        """Bytes of one position's latent column, every layer, bfloat16."""
        return self.layers * (self.rank + self.rope) * BF16

    def _proj(self) -> int:
        """Multiply-adds a token of the attention projections both forms
        share: W_Q, W_DKV and W_O."""
        d, h = self.d_model, self.heads
        return (d * h * (self.nope + self.rope) + d * (self.rank + self.rope)
                + h * self.v_dim * d)

    def _ffn(self) -> float:
        """Multiply-adds a token of every layer's FFN, router included."""
        d = self.d_model
        moe = (d * self.router_width
               + 3 * d * self.expert_ff * (self.shared + self.top_k
                                           * self.held / self.router_width))
        return (self.dense_layers * 3 * d * self.dense_ff
                + (self.layers - self.dense_layers) * moe)

    @staticmethod
    def _keys(first: int, count: int) -> float:
        """Keys attended by positions first .. first+count-1, each attending
        itself and every earlier position."""
        return count * first + count * (count + 1) / 2.0

    def prefill_flops(self, prompt: int) -> float:
        """Forward over prompt[:-1], non-absorbed: fills the cache, needs no
        logits."""
        s = prompt - 1
        h = self.heads
        up = self.rank * h * (self.nope + self.v_dim)      # W_UK, W_UV
        attn = h * (self.nope + self.rope + self.v_dim) * self._keys(0, s)
        return 2.0 * (self.layers * ((self._proj() + up) * s + attn)
                      + self._ffn() * s)

    def decode_flops(self, prompt: int, new: int) -> float:
        """The ``new`` positions that each yield a served token, absorbed:
        the query through W_UK^T, scores and values over the latent cache,
        the output through W_UV."""
        h = self.heads
        absorb = h * self.rank * (self.nope + self.v_dim)
        attn = (self.layers * h * (2 * self.rank + self.rope)
                * self._keys(prompt - 1, new))
        per = self.layers * (self._proj() + absorb) + self._ffn() \
            + self.d_model * self.vocab
        return 2.0 * (per * new + attn)

    def kernel_cost(self, kv_positions: float) -> tuple[float, float]:
        """-> (operations, least bytes) of the latent decode attention over
        ``kv_positions`` cached positions, every layer: each position's
        latent column read once; scores over rank + rope, values over
        rank, for every head."""
        flops = (2.0 * self.layers * self.heads * (2 * self.rank + self.rope)
                 * kv_positions)
        return flops, float(self.cache_row_bytes) * kv_positions
