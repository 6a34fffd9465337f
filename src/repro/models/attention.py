"""Attention: GQA + RoPE (+ QKV bias), MLA (DeepSeek-V2), prefix-LM masking.

Three implementations behind one switch:
  * "xla"     — dense masked attention (small sequences, smoke tests);
  * "chunked" — lax.scan over KV blocks with online softmax in pure jnp:
                O(S·chunk) memory, the dry-run-compatible sub-quadratic path
                for 32k prefill (XLA lowers it on any backend);
  * "pallas"  — the flash-attention kernel (TPU; interpret-validated on CPU).

Decode (single query token against a cache) is a separate path — a matvec
whose roofline is HBM-bound cache streaming: ``kops.decode_attention`` (GQA)
and ``kops.mla_decode_attention`` (MLA's latent cache) read each layer's
slab of the stacked cache once and write the token in place (the Pallas
kernel on TPU, its XLA oracle elsewhere).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, rms_norm, yarn_mscale

NEG_INF = -1e30


def _split_heads(x: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1).transpose(0, 2, 1, 3)  # (B, H, S, d)


def _merge_heads(x: jnp.ndarray) -> jnp.ndarray:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _mask(sq: int, skv: int, prefix_len: int = 0) -> jnp.ndarray:
    """Causal mask, optionally bidirectional over the first `prefix_len`
    positions (PaliGemma prefix-LM)."""
    rows = jnp.arange(sq)[:, None] + (skv - sq)   # absolute query positions
    cols = jnp.arange(skv)[None, :]
    allowed = cols <= rows
    if prefix_len > 0:
        allowed = allowed | (cols < prefix_len)
    return allowed


def _xla_attention(q, k, v, mask, scale=None) -> jnp.ndarray:
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / (d ** 0.5) if scale is None else s * scale
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _chunked_attention(q, k, v, *, prefix_len: int, chunk: int = 512,
                       remat_chunk: bool = False,
                       q_sharding=None, scale=None) -> jnp.ndarray:
    """Online-softmax over KV chunks (flash-attention in pure jnp/lax.scan).

    k and v may have different head dims (MLA: qk = nope+rope, v = v_dim).

    §Perf knobs:
      * ``remat_chunk`` — rematerialize the chunk body in the backward pass
        instead of saving the (B,H,Sq,chunk) probability tiles per step;
        trades ~1 extra forward of chunk compute for an O(S²/chunk)→O(S)
        reduction of saved residuals (the XLA-path analogue of the Pallas
        flash kernel's recomputed backward).
      * ``q_sharding`` — explicit sharding for the scaled query (sequence
        dim over the model axis): pins XLA to replicated-KV × local-scores
        partitioning instead of sharding the QK contraction (which inserts
        per-chunk score all-reduces).
    """
    b, h, sq, d = q.shape
    dv = v.shape[-1]
    skv = k.shape[2]
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kc = k.reshape(b, h, n_chunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, h, n_chunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    q32 = (q.astype(jnp.float32) / (d ** 0.5) if scale is None
           else q.astype(jnp.float32) * scale)
    if q_sharding is not None:
        q32 = jax.lax.with_sharding_constraint(q32, q_sharding)
    rows = jnp.arange(sq)[:, None] + (skv - sq)

    def step(carry, inp):
        m, l, acc = carry
        idx, kb, vb = inp
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb.astype(jnp.float32))
        cols = idx * chunk + jnp.arange(chunk)[None, :]
        allowed = cols <= rows
        if prefix_len > 0:
            allowed = allowed | (cols < prefix_len)
        allowed = allowed & (cols < skv)
        s = jnp.where(allowed[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    if remat_chunk:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.nothing_saveable)

    m0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc)
    )
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def multihead_attention(
    q: jnp.ndarray,           # (B, Hq, Sq, d)
    k: jnp.ndarray,           # (B, Hkv, Skv, d)
    v: jnp.ndarray,
    *,
    impl: str = "xla",
    prefix_len: int = 0,
    chunk: int = 512,
    remat_chunk: bool = False,
    q_sharding=None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """``scale``: the softmax scale, 1/sqrt(d) where None."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:  # GQA: repeat KV heads
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    if scale is not None and impl in ("pallas", "stub"):
        # these take 1/sqrt(d): fold the rest of the scale into q
        q = q * jnp.asarray(scale * q.shape[-1] ** 0.5, q.dtype)
    if impl == "pallas":
        if prefix_len:
            raise NotImplementedError("prefix-LM uses xla/chunked")
        return kops.attention(q, k, v, causal=True, impl="pallas")
    if impl == "stub":
        # Measurement stub (§Perf flash substitution): preserves all shapes
        # and gradients at negligible FLOPs/traffic, so a cell compiled with
        # it isolates the everything-but-attention cost; the Pallas flash
        # kernel's analytic terms are then added back (launch/flashsub.py).
        o = jnp.mean(v, axis=2, keepdims=True) + 1e-6 * jnp.mean(
            q.astype(v.dtype), axis=-1, keepdims=True)
        return jnp.broadcast_to(
            o, q.shape[:3] + (v.shape[-1],)).astype(q.dtype)
    if impl == "chunked":
        return _chunked_attention(q, k, v, prefix_len=prefix_len, chunk=chunk,
                                  remat_chunk=remat_chunk,
                                  q_sharding=q_sharding, scale=scale)
    mask = _mask(q.shape[2], k.shape[2], prefix_len)
    return _xla_attention(q, k, v, mask, scale)


# ---------------------------------------------------------------------------
# GQA block (dense/moe/vlm/audio families)
# ---------------------------------------------------------------------------


def gqa_project(x, p, cfg: ModelConfig, positions):
    """x -> rotated q, k, v with head split.  p: this layer's attn params."""
    dt = x.dtype
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = _split_heads(q, cfg.n_heads)
    k = _split_heads(k, cfg.n_kv_heads)
    v = _split_heads(v, cfg.n_kv_heads)
    if cfg.pos_embedding == "rope":
        q = apply_rope(q, positions[:, None], cfg.rope_theta)
        k = apply_rope(k, positions[:, None], cfg.rope_theta)
    return q, k, v


def gqa_attention(x, p, cfg: ModelConfig, positions, *, impl="xla",
                  prefix_len=0, chunk=512, remat_chunk=False,
                  q_sharding=None) -> jnp.ndarray:
    q, k, v = gqa_project(x, p, cfg, positions)
    o = multihead_attention(q, k, v, impl=impl, prefix_len=prefix_len,
                            chunk=chunk, remat_chunk=remat_chunk,
                            q_sharding=q_sharding)
    return jnp.einsum("bsk,kd->bsd", _merge_heads(o), p["wo"].astype(x.dtype))


def gqa_decode_ragged(x, p, cfg: ModelConfig, k_cache, v_cache, pos_b,
                      layer=0):
    """One-token decode with a *per-row* position (continuous batching).

    ``pos_b``: (B,) int32 — row b attends over ``cache[layer, b, :,
    :pos_b[b]]`` and this token, whose K/V is written in place at position
    ``pos_b[b]``, so slots whose requests joined the batch at different
    times (different prompt lengths / arrival steps) decode together in one
    program.  RoPE/positional encoding uses each row's own absolute
    position.  k_cache/v_cache: the stacked (L, B, Hkv*dh, Smax) caches,
    positions minor; each layer's slab is read once, in its stored dtype
    (``kops.decode_attention``).  Returns (out, k_cache, v_cache).
    """
    b = x.shape[0]
    q, k, v = gqa_project(x, p, cfg, pos_b[:, None])        # (B,H,1,d)
    o, k_cache, v_cache = kops.decode_attention(
        q[:, :, 0], k[:, :, 0], v[:, :, 0], k_cache, v_cache, pos_b, layer)
    o = o.astype(x.dtype).reshape(b, 1, -1)
    return (jnp.einsum("bsk,kd->bsd", o, p["wo"].astype(x.dtype)),
            k_cache, v_cache)


def gqa_decode(x, p, cfg: ModelConfig, k_cache, v_cache, pos, layer=0):
    """One-token decode of every row at one position ``pos``: attend over
    cache[layer, :, :, :pos] and this token, write it at ``pos``.

    k_cache/v_cache: (L, B, Hkv*dh, Smax).  Returns (out, k_cache, v_cache).
    """
    pos_b = jnp.full((x.shape[0],), pos, jnp.int32)
    return gqa_decode_ragged(x, p, cfg, k_cache, v_cache, pos_b, layer)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank KV compression; the cache stores only
# (c_kv, k_rope) — kv_lora_rank + rope_dim per token instead of 2·H·d.
# ---------------------------------------------------------------------------


def mla_scale(cfg: ModelConfig) -> float:
    """The softmax scale: 1/sqrt(nope + rope), times YaRN's mscale² where
    ``rope_scaling`` sets ``mscale_all_dim`` (DeepSeek-V2)."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_project_q(x, p, cfg: ModelConfig, positions):
    m = cfg.mla
    dt = x.dtype
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"].astype(dt))
    q = q.reshape(x.shape[0], x.shape[1], cfg.n_heads,
                  m.qk_nope_head_dim + m.qk_rope_head_dim).transpose(0, 2, 1, 3)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions[:, None], cfg.rope_theta,
                        cfg.rope_scaling)
    return q_nope, q_rope


def mla_compress_kv(x, p, cfg: ModelConfig, positions):
    """x -> (c_kv normed, k_rope rotated): exactly what the MLA cache stores."""
    m = cfg.mla
    dt = x.dtype
    ckv = jnp.einsum("bsd,dk->bsk", x, p["wdkv"].astype(dt))
    c, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :].transpose(0, 2, 1, 3),
                        positions[:, None], cfg.rope_theta,
                        cfg.rope_scaling)                  # (B,1,S,rope)
    return c, k_rope


def mla_attention(x, p, cfg: ModelConfig, positions, *, impl="xla",
                  c=None, k_rope=None, chunk=512, remat_chunk=False,
                  q_sharding=None) -> jnp.ndarray:
    """Full-sequence MLA attention (c/k_rope may be precomputed for prefill)."""
    m = cfg.mla
    dt = x.dtype
    b, s, _ = x.shape
    if c is None:
        c, k_rope = mla_compress_kv(x, p, cfg, positions)
    q_nope, q_rope = mla_project_q(x, p, cfg, positions)
    k_nope = jnp.einsum("bsr,rk->bsk", c, p["wuk"].astype(dt))
    v = jnp.einsum("bsr,rk->bsk", c, p["wuv"].astype(dt))
    k_nope = _split_heads(k_nope, cfg.n_heads)
    v = _split_heads(v, cfg.n_heads)
    k_rope_b = jnp.broadcast_to(
        k_rope, (b, cfg.n_heads, k_rope.shape[2], m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    o = multihead_attention(q, k, v, impl=impl, chunk=chunk,
                            remat_chunk=remat_chunk, q_sharding=q_sharding,
                            scale=mla_scale(cfg))
    return jnp.einsum("bsk,kd->bsd", _merge_heads(o), p["wo"].astype(dt))


def mla_decode_ragged(x, p, cfg: ModelConfig, cache, pos_b, layer=0):
    """One-token MLA decode with a per-row position, absorbed, against the
    stacked latent cache ``(L, B, rank + rope, S)``, positions minor.

    ``W_UK`` folds into the query, so each head scores the latent column
    ``c ‖ k_rope`` directly: ``(q_nope·W_UKᵀ) ‖ q_rope``; the latent
    output (the first ``rank`` rows of the column are the values) then
    goes through ``W_UV`` and ``W_O``.  ``kops.mla_decode_attention`` reads
    each row's slab once and writes this token's column at ``pos_b`` in
    place.  Returns (out, cache).
    """
    m = cfg.mla
    dt = x.dtype
    b = x.shape[0]
    positions = pos_b[:, None]
    c, k_rope = mla_compress_kv(x, p, cfg, positions)     # (B,1,r), (B,1,1,ρ)
    q_nope, q_rope = mla_project_q(x, p, cfg, positions)  # (B,H,1,·)
    wuk = p["wuk"].astype(dt).reshape(m.kv_lora_rank, cfg.n_heads,
                                      m.qk_nope_head_dim)
    q = jnp.concatenate([jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0], wuk),
                         q_rope[:, :, 0]], axis=-1)      # (B,H,r+ρ)
    col = jnp.concatenate([c[:, 0], k_rope[:, 0, 0]], axis=-1)
    o, cache = kops.mla_decode_attention(q, col, cache, pos_b, layer,
                                         scale=mla_scale(cfg),
                                         v_dim=m.kv_lora_rank)
    wuv = p["wuv"].astype(dt).reshape(m.kv_lora_rank, cfg.n_heads,
                                      m.v_head_dim)
    o = jnp.einsum("bhr,rhn->bhn", o.astype(dt), wuv).reshape(b, 1, -1)
    return jnp.einsum("bsk,kd->bsd", o, p["wo"].astype(dt)), cache
