"""Mixture-of-Experts block: top-k routing, capacity-based dispatch, shared
experts, load-balancing auxiliary loss.

Dispatch is the production (E, C, D) buffer pattern: tokens scatter into
per-expert capacity slots, a single batched einsum runs all experts (exact
FLOPs — no dense-over-experts redundancy), and per-k gathers combine the
results.  The (E, C, D) buffer is the tensor that shards over the `model`
axis for expert parallelism: resharding it from token-sharded to
expert-sharded is XLA's all-to-all, which the roofline's collective term
picks up.  Overflowing tokens beyond capacity are dropped (their combine
weight is zero) — capacity_factor 1.25 keeps drops rare at convergence.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import gated_mlp

CAPACITY_FACTOR = 1.25


def capacity(tokens: int, n_experts: int, top_k: int,
             no_drop: bool = False) -> int:
    if no_drop:
        # Exact worst case: a token's k choices are DISTINCT experts, so no
        # expert can receive more than `tokens` entries.  (§Perf move M3:
        # was tokens*top_k, a k× overallocation that dominated MoE decode
        # FLOPs — see EXPERIMENTS.md.)
        return tokens
    c = int(tokens * top_k / n_experts * CAPACITY_FACTOR)
    return max(8, -(-c // 8) * 8)  # round up to 8 for tiling


def moe_block(
    x: jnp.ndarray,                 # (B, S, D)
    p: Dict[str, jnp.ndarray],      # this layer's MoE params
    cfg: ModelConfig,
    no_drop: bool = False,          # exact routing (serving / eval)
    buffer_sharding=None,           # EP constraint on the (E, C, D) buffer
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_load_balance_loss).

    The router scores all ``n_experts``; the experts computed are the ones
    held (``MoEConfig.experts_held``): a (token, expert) pair routed to an
    expert held elsewhere contributes nothing here and takes no capacity.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = m.n_experts, m.top_k
    first = m.experts_held[0] if m.experts_held else 0
    eh = m.n_held
    c = capacity(t, e, k, no_drop)
    xf = x.reshape(t, d)

    # --- router (f32) ---------------------------------------------------------
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (T, K)
    if m.norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # --- load-balancing aux loss (Switch-style) --------------------------------
    me = probs.mean(axis=0)                                    # (E,)
    one_hot_top1 = jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32)
    ce = one_hot_top1.mean(axis=0)
    aux = m.aux_loss_coef * e * jnp.sum(me * ce)

    # --- dispatch: positions within each held expert's capacity -----------------
    # flat (T*K,) expert choices, priority by (k, token) order; an expert
    # held elsewhere has an all-zero one-hot row
    e_flat = gate_idx.T.reshape(-1) - first                    # (K*T,)
    held = (e_flat >= 0) & (e_flat < eh)
    onehot = jax.nn.one_hot(e_flat, eh, dtype=jnp.int32)       # (K*T, Eh)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot             # (K*T, Eh)
    e_flat = jnp.clip(e_flat, 0, eh - 1)
    pos_flat = jnp.take_along_axis(pos_in_e, e_flat[:, None], axis=1)[:, 0]
    keep = held & (pos_flat < c)
    pos_clamped = jnp.minimum(pos_flat, c - 1)

    buf = jnp.zeros((eh, c, d), x.dtype)
    tok_idx = jnp.tile(jnp.arange(t), k)
    contrib = jnp.where(keep[:, None], xf[tok_idx], 0.0)
    buf = buf.at[e_flat, pos_clamped].add(contrib)             # (E, C, D)
    if buffer_sharding is not None:
        buf = jax.lax.with_sharding_constraint(buf, buffer_sharding)

    # --- expert FFNs: one batched einsum over stacked experts -------------------
    dt = x.dtype
    h = jnp.einsum("ecd,edf->ecf", buf, p["experts"]["wi"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", buf, p["experts"]["wg"].astype(dt))
    g = jax.nn.silu(g) if cfg.act == "silu" else jax.nn.gelu(g, approximate=True)
    y_buf = jnp.einsum("ecf,efd->ecd", g * h, p["experts"]["wo"].astype(dt))

    # --- combine: per-k weighted gathers (keeps transients at (T, D)) ------------
    y = jnp.zeros((t, d), jnp.float32)
    w_flat = gate_vals.T.reshape(-1)                           # (K*T,)
    for kk in range(k):
        sl = slice(kk * t, (kk + 1) * t)
        ek, pk = e_flat[sl], pos_clamped[sl]
        wk = jnp.where(keep[sl], w_flat[sl], 0.0)
        y = y + wk[:, None] * y_buf[ek, pk].astype(jnp.float32)
    y = y.astype(x.dtype)

    # --- shared experts (always-on) ----------------------------------------------
    if m.n_shared:
        y = y + gated_mlp(xf, p["shared"]["wi"], p["shared"]["wg"],
                          p["shared"]["wo"], cfg.act)
    return y.reshape(b, s, d), aux
