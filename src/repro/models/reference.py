"""A plain float32 forward pass of the MLA + MoE family (DeepSeek-V2), for
the tests to compare the serving path against.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``
over one sequence, on the program's parameter layout: RMSNorm; MLA written
out the long way (no absorption: keys ``c·W_UK ‖ k_rope`` and values
``c·W_UV`` per head), with YaRN's frequencies and softmax scale; the
leading dense SwiGLU layers, then MoE layers whose router scores every
expert with a float32 softmax and takes the top k, with gates renormalised
only where ``norm_topk_prob`` says; shared experts always on.  No cache,
kernel, batching or capacity.  It uses nothing of the program but the
configuration.

Departure from the checkpoint: rotary positions rotate halves (as the
program does), where DeepSeek-V2 rotates interleaved pairs; that is a fixed
permutation of the rope columns of ``wq`` and ``wdkv``, which random
weights cannot tell apart.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig


def yarn_inv_freq(dim: int, base: float, yarn) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies, in float64."""
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    if yarn is None:
        return extra

    def corr(rot):
        return dim * math.log(yarn.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: ModelConfig) -> float:
    m, y = cfg.mla, cfg.rope_scaling
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if y is not None and y.mscale_all_dim:
        scale *= _mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, cfg: ModelConfig):
    """x: (S, ..., d), rotated by position along axis 0 (half rotation)."""
    y = cfg.rope_scaling
    d = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(d, cfg.rope_theta, y), jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    m = 1.0 if y is None else (_mscale(y.factor, y.mscale)
                               / _mscale(y.factor, y.mscale_all_dim))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    cos = cos.reshape(cos.shape[:1] + (1,) * (x.ndim - 2) + cos.shape[1:])
    sin = sin.reshape(cos.shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def mla(x, p, cfg: ModelConfig):
    """Causal MLA over x (S, D), non-absorbed."""
    m = cfg.mla
    S, H = x.shape[0], cfg.n_heads
    q = (x @ p["wq"]).reshape(S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    ckv = x @ p["wdkv"]
    c = _rms(ckv[:, :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = _rope(ckv[:, m.kv_lora_rank:], cfg)               # (S, ρ)
    q = jnp.concatenate([q_nope, _rope(q_rope, cfg)], -1)      # (S, H, ·)
    k = jnp.concatenate(
        [(c @ p["wuk"]).reshape(S, H, m.qk_nope_head_dim),
         jnp.broadcast_to(k_rope[:, None], (S, H, m.qk_rope_head_dim))], -1)
    v = (c @ p["wuv"]).reshape(S, H, m.v_head_dim)
    s = jnp.einsum("qhd,khd->hqk", q, k) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(S, H * m.v_head_dim) @ p["wo"]


def moe(x, p, cfg: ModelConfig, held: Optional[Tuple[int, int]] = None):
    """-> (routed part of the experts ``held`` = (first, count), which
    ``p["experts"]`` holds, or of all of them; the shared experts' part)."""
    m = cfg.moe
    first, count = held if held is not None else (0, m.n_experts)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)           # (S, E)
    vals, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        vals = vals / vals.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(vals)         # (S, E)
    routed = jnp.zeros_like(x)
    for e in range(count):
        expert = jax.tree.map(lambda a, e=e: a[e], p["experts"])
        routed = routed + gate[:, first + e, None] * _swiglu(x, expert)
    shared = _swiglu(x, p["shared"]) if m.n_shared else jnp.zeros_like(x)
    return routed, shared


def forward(params, cfg: ModelConfig, tokens) -> jnp.ndarray:
    """Logits (S, V) of every position of ``tokens`` (S,), float32; the
    experts computed are the ones the configuration holds."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jnp.asarray(a, jnp.float32), t)
        eps = cfg.norm_eps
        x = f32(params["embed"])[jnp.asarray(tokens)]
        stacks = ([params["dense_layers"]] if cfg.first_k_dense else []) + [
            params["layers"]]
        for stack in stacks:
            for i in range(jax.tree.leaves(stack)[0].shape[0]):
                lp = f32(jax.tree.map(lambda a, i=i: a[i], stack))
                x = x + mla(_rms(x, lp["ln1"], eps), lp["attn"], cfg)
                h = _rms(x, lp["ln2"], eps)
                if "moe" in lp:
                    routed, shared = moe(h, lp["moe"], cfg,
                                         cfg.moe.experts_held)
                    x = x + routed + shared
                else:
                    x = x + _swiglu(h, lp["mlp"])
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return _rms(x, f32(params["final_norm"]), eps) @ f32(head)
