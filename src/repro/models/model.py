"""Model assembly: init / train forward / prefill / decode for every family.

Design rules (DESIGN.md §7):
  * parameters are stacked over layers (leading L axis) and the forward pass
    is one ``lax.scan`` over the stack -> HLO and compile time are O(1) in
    depth (an 80-layer 110B config lowers as fast as an 18-layer 3B one);
  * the scan body is rematerialized (``jax.checkpoint``, nothing saveable):
    live activations are the per-layer carries only;
  * heterogeneity (Zamba2's shared attention block, prefix-LM masks) lives
    *inside* the homogeneous scan via ``lax.cond`` on the layer index, so the
    stack stays scannable;
  * every entry point is a pure function of (params, batch) — the launch
    layer owns shardings; optional ``residual_spec`` forces sequence-parallel
    residuals between layers.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ModelConfig
from repro.models.layers import (
    dense_init,
    gated_mlp,
    rms_norm,
    sinusoidal_positions,
    softcap,
)

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class CallConfig:
    """Per-call knobs owned by the launcher, not the architecture."""

    attn_impl: str = "xla"          # "xla" | "chunked" | "pallas"
    attn_chunk: int = 512
    remat: bool = True
    residual_spec: Optional[Any] = None   # PartitionSpec for the residual
    moe_no_drop: bool = False       # exact MoE routing (serving / eval)
    # --- §Perf hillclimbing knobs (EXPERIMENTS.md) -------------------------
    attn_chunk_remat: bool = False  # recompute chunk bodies in backward
    attn_q_sharding: Optional[Any] = None   # NamedSharding for scaled q
    cast_params_once: bool = False  # bf16 weight copy before the layer scan
    moe_buffer_sharding: Optional[Any] = None  # EP constraint on (E,C,D)


# =============================================================================
# Initialization
# =============================================================================


def _attn_params(key, cfg: ModelConfig, L: int, heads: int, kv_heads: int,
                 head_dim: int, dtype) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (L, d, heads * head_dim), -2, dtype),
        "wk": dense_init(ks[1], (L, d, kv_heads * head_dim), -2, dtype),
        "wv": dense_init(ks[2], (L, d, kv_heads * head_dim), -2, dtype),
        "wo": dense_init(ks[3], (L, heads * head_dim, d), -2, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((L, heads * head_dim), dtype)
        p["bk"] = jnp.zeros((L, kv_heads * head_dim), dtype)
        p["bv"] = jnp.zeros((L, kv_heads * head_dim), dtype)
    return p


def _mla_params(key, cfg: ModelConfig, L: int, dtype) -> Params:
    m = cfg.mla
    ks = jax.random.split(key, 5)
    d, h = cfg.d_model, cfg.n_heads
    return {
        "wq": dense_init(ks[0], (L, d, h * (m.qk_nope_head_dim + m.qk_rope_head_dim)), -2, dtype),
        "wdkv": dense_init(ks[1], (L, d, m.kv_lora_rank + m.qk_rope_head_dim), -2, dtype),
        "kv_norm": jnp.ones((L, m.kv_lora_rank), dtype),
        "wuk": dense_init(ks[2], (L, m.kv_lora_rank, h * m.qk_nope_head_dim), -2, dtype),
        "wuv": dense_init(ks[3], (L, m.kv_lora_rank, h * m.v_head_dim), -2, dtype),
        "wo": dense_init(ks[4], (L, h * m.v_head_dim, d), -2, dtype),
    }


def _mlp_params(key, L: int, d: int, f: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], (L, d, f), -2, dtype),
        "wg": dense_init(ks[1], (L, d, f), -2, dtype),
        "wo": dense_init(ks[2], (L, f, d), -2, dtype),
    }


def _moe_params(key, cfg: ModelConfig, L: int, dtype) -> Params:
    m = cfg.moe
    ks = jax.random.split(key, 5)
    d, e, fe = cfg.d_model, m.n_experts, m.d_ff_expert
    eh = m.n_held                   # the router scores all e experts
    p = {
        "router": dense_init(ks[0], (L, d, e), -2, jnp.float32),
        "experts": {
            "wi": dense_init(ks[1], (L, eh, d, fe), -2, dtype),
            "wg": dense_init(ks[2], (L, eh, d, fe), -2, dtype),
            "wo": dense_init(ks[3], (L, eh, fe, d), -2, dtype),
        },
    }
    if m.n_shared:
        p["shared"] = _mlp_params(ks[4], L, d, m.n_shared * fe, dtype)
    return p


def _mamba1_params(key, cfg: ModelConfig, L: int, dtype) -> Params:
    s = cfg.ssm
    din, d, N = cfg.d_inner, cfg.d_model, s.d_state
    R = s.dt_rank or -(-d // 16)
    ks = jax.random.split(key, 5)
    return {
        "in_proj": dense_init(ks[0], (L, d, 2 * din), -2, dtype),
        "conv_w": dense_init(ks[1], (L, din, s.d_conv), -1, dtype),
        "conv_b": jnp.zeros((L, din), dtype),
        "x_proj": dense_init(ks[2], (L, din, R + 2 * N), -2, dtype),
        "dt_proj": dense_init(ks[3], (L, R, din), -2, dtype),
        "dt_bias": jnp.full((L, din), -4.6, dtype),    # softplus^-1(0.01)
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (L, din, N))).astype(dtype),
        "D": jnp.ones((L, din), dtype),
        "out_proj": dense_init(ks[4], (L, din, d), -2, dtype),
    }


def _mamba2_params(key, cfg: ModelConfig, L: int, dtype) -> Params:
    s = cfg.ssm
    din, d, N = cfg.d_inner, cfg.d_model, s.d_state
    H = din // s.headdim
    conv_dim = din + 2 * N
    ks = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(ks[0], (L, d, 2 * din + 2 * N + H), -2, dtype),
        "conv_w": dense_init(ks[1], (L, conv_dim, s.d_conv), -1, dtype),
        "conv_b": jnp.zeros((L, conv_dim), dtype),
        "A_log": jnp.zeros((L, H), dtype),
        "D": jnp.ones((L, H), dtype),
        "dt_bias": jnp.full((L, H), -4.6, dtype),
        "norm": jnp.ones((L, din), dtype),
        "out_proj": dense_init(ks[2], (L, din, d), -2, dtype),
    }


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    L = cfg.n_layers
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model), -1, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[1], (cfg.d_model, cfg.vocab_size), -2, dtype)

    layer: Params = {}
    if cfg.family == "ssm":
        layer["ln"] = jnp.ones((L, cfg.d_model), dtype)
        layer["mixer"] = _mamba1_params(keys[2], cfg, L, dtype)
    elif cfg.family == "hybrid":
        layer["ln"] = jnp.ones((L, cfg.d_model), dtype)
        layer["mixer"] = _mamba2_params(keys[2], cfg, L, dtype)
        hb = cfg.hybrid
        hd = cfg.d_model // hb.shared_attn_heads
        shared_cfg = dataclasses.replace(
            cfg, n_heads=hb.shared_attn_heads, n_kv_heads=hb.shared_attn_kv_heads,
            head_dim=hd, qkv_bias=False)
        params["shared_block"] = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": jax.tree.map(
                lambda a: a[0],
                _attn_params(keys[3], shared_cfg, 1, hb.shared_attn_heads,
                             hb.shared_attn_kv_heads, hd, dtype)),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "mlp": jax.tree.map(lambda a: a[0],
                                _mlp_params(keys[4], 1, cfg.d_model, cfg.d_ff, dtype)),
        }
    else:
        k = cfg.first_k_dense
        layer = _block_params(keys[2], keys[3], cfg, L - k, dtype,
                              moe=cfg.moe is not None)
        if k:
            params["dense_layers"] = _block_params(keys[4], keys[5], cfg, k,
                                                   dtype, moe=False)
    params["layers"] = layer
    return params


def _block_params(k_attn, k_ffn, cfg: ModelConfig, L: int, dtype,
                  moe: bool) -> Params:
    """``L`` stacked attention-family layers: norms, attention, and an MoE
    block or a dense MLP of width ``d_ff``."""
    layer: Params = {"ln1": jnp.ones((L, cfg.d_model), dtype),
                     "ln2": jnp.ones((L, cfg.d_model), dtype)}
    if cfg.mla:
        layer["attn"] = _mla_params(k_attn, cfg, L, dtype)
    else:
        layer["attn"] = _attn_params(
            k_attn, cfg, L, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dtype)
    if moe:
        layer["moe"] = _moe_params(k_ffn, cfg, L, dtype)
    else:
        layer["mlp"] = _mlp_params(k_ffn, L, cfg.d_model, cfg.d_ff, dtype)
    return layer


def _scan_stacks(body, carry, params: Params, cfg: ModelConfig):
    """``lax.scan`` of ``body`` over each layer stack in turn, the leading
    dense layers (``first_k_dense``) first, each layer given as (its
    params, its index over both stacks: a cache indexes layers so).
    -> (carry, [stacked outputs of each stack])."""
    k = cfg.first_k_dense
    stacks = ([(params["dense_layers"], 0)] if k else []) + [
        (params["layers"], k)]
    outs = []
    for stack, first in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        carry, ys = jax.lax.scan(body, carry, (stack, jnp.arange(first, first + n)))
        outs.append(ys)
    return carry, outs


def _ffn(h, lp: Params, cfg: ModelConfig, no_drop: bool,
         buffer_sharding=None):
    """The layer's MoE block or dense MLP -> (delta, aux loss)."""
    if "moe" in lp:
        return moe_lib.moe_block(h, lp["moe"], cfg, no_drop=no_drop,
                                 buffer_sharding=buffer_sharding)
    return (gated_mlp(h, lp["mlp"]["wi"], lp["mlp"]["wg"], lp["mlp"]["wo"],
                      cfg.act), jnp.float32(0.0))


def _cast_layers(params: Params, cfg: ModelConfig) -> Params:
    """One compute-dtype copy of every float32 layer weight."""
    dt = jnp.dtype(cfg.compute_dtype)
    cast = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(dt) if a.dtype == jnp.float32 else a, tree)
    out = dict(params, layers=cast(params["layers"]))
    if "dense_layers" in params:
        out["dense_layers"] = cast(params["dense_layers"])
    return out


# =============================================================================
# Embedding / unembedding
# =============================================================================


def embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """-> (x, positions, prefix_len).  Handles the stub modality frontends."""
    dt = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    x = params["embed"][tokens].astype(dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    prefix_len = 0
    if cfg.frontend and cfg.frontend.kind == "vision_stub":
        patches = batch["patches"].astype(dt)       # precomputed (B, P, D)
        x = jnp.concatenate([patches, x], axis=1)
        prefix_len = patches.shape[1]
    b, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.pos_embedding == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(dt)
    return x, positions, prefix_len


def unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                 plus_one=cfg.embed_scale)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)


# =============================================================================
# Train / prefill forward (scan over the layer stack)
# =============================================================================


def _shared_attn_block(x, params, cfg: ModelConfig, positions, call: CallConfig):
    """Zamba2's shared transformer block (weights reused every application)."""
    hb = cfg.hybrid
    shared_cfg = dataclasses.replace(
        cfg, n_heads=hb.shared_attn_heads, n_kv_heads=hb.shared_attn_kv_heads,
        head_dim=cfg.d_model // hb.shared_attn_heads, qkv_bias=False)
    sb = params["shared_block"]
    h = rms_norm(x, sb["ln1"], cfg.norm_eps)
    x = x + attn.gqa_attention(h, sb["attn"], shared_cfg, positions,
                               impl=call.attn_impl, chunk=call.attn_chunk,
                               remat_chunk=call.attn_chunk_remat)
    h = rms_norm(x, sb["ln2"], cfg.norm_eps)
    return x + gated_mlp(h, sb["mlp"]["wi"], sb["mlp"]["wg"], sb["mlp"]["wo"],
                         cfg.act)


def _constrain(x, call: CallConfig):
    if call.residual_spec is not None:
        x = jax.lax.with_sharding_constraint(x, call.residual_spec)
    return x


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            call: CallConfig = CallConfig()) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full forward pass -> (logits f32, aux_loss)."""
    if call.cast_params_once:
        # One compute-dtype weight copy per step, sharded like the originals:
        # the layer scan then gathers/reads 2-byte weights instead of 4-byte
        # (halves FSDP gather traffic + weight HBM reads; §Perf move M2).
        params = _cast_layers(params, cfg)
    x, positions, prefix_len = embed_inputs(params, cfg, batch)

    def body(x, xs):
        lp, idx = xs
        aux = jnp.float32(0.0)
        if cfg.family == "ssm":
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            x = x + ssm_lib.mamba1_block(h, lp["mixer"], cfg)
        elif cfg.family == "hybrid":
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            x = x + ssm_lib.mamba2_block(h, lp["mixer"], cfg)
            period = cfg.hybrid.period
            x = jax.lax.cond(
                (idx + 1) % period == 0,
                lambda v: _shared_attn_block(v, params, cfg, positions, call),
                lambda v: v,
                x,
            )
        else:
            h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one=cfg.embed_scale)
            if cfg.mla:
                x = x + attn.mla_attention(h, lp["attn"], cfg, positions,
                                           impl=call.attn_impl,
                                           chunk=call.attn_chunk,
                                           remat_chunk=call.attn_chunk_remat,
                                           q_sharding=call.attn_q_sharding)
            else:
                x = x + attn.gqa_attention(h, lp["attn"], cfg, positions,
                                           impl=call.attn_impl,
                                           prefix_len=prefix_len,
                                           chunk=call.attn_chunk,
                                           remat_chunk=call.attn_chunk_remat,
                                           q_sharding=call.attn_q_sharding)
            h = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one=cfg.embed_scale)
            delta, aux = _ffn(h, lp, cfg, call.moe_no_drop,
                              call.moe_buffer_sharding)
            x = x + delta
        return _constrain(x, call), aux

    if call.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, aux = _scan_stacks(body, x, params, cfg)
    return unembed(params, cfg, x), sum(jnp.sum(a) for a in aux)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            call: CallConfig = CallConfig()) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross entropy (text positions only for VLM) + MoE aux."""
    logits, aux = forward(params, cfg, batch, call)
    labels = batch["labels"]
    if cfg.frontend and cfg.frontend.kind == "vision_stub":
        logits = logits[:, -labels.shape[1]:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    nll = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = nll + aux
    return total, {"nll": nll, "aux": aux}


# =============================================================================
# KV / state caches
# =============================================================================


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int,
               dtype_str: Optional[str] = None) -> Params:
    dt = jnp.dtype(dtype_str or cfg.compute_dtype)
    L = cfg.n_layers
    if cfg.family == "ssm":
        s = cfg.ssm
        return {
            "conv": jnp.zeros((L, batch_size, s.d_conv - 1, cfg.d_inner), dt),
            "h": jnp.zeros((L, batch_size, cfg.d_inner, s.d_state), jnp.float32),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "hybrid":
        s = cfg.ssm
        H = cfg.d_inner // s.headdim
        A = cfg.n_layers // cfg.hybrid.period
        hb = cfg.hybrid
        kvd = hb.shared_attn_kv_heads * (cfg.d_model // hb.shared_attn_heads)
        return {
            "conv": jnp.zeros((L, batch_size, s.d_conv - 1,
                               cfg.d_inner + 2 * s.d_state), dt),
            "h": jnp.zeros((L, batch_size, H, s.headdim, s.d_state), jnp.float32),
            "k": jnp.zeros((A, batch_size, kvd, max_len), dt),
            "v": jnp.zeros((A, batch_size, kvd, max_len), dt),
            "pos": jnp.zeros((), jnp.int32),
        }
    # K/V caches are (L, B, Hkv*dh, Smax), and MLA's latent cache (c_kv ‖
    # k_rope) is (L, B, rank + rope, Smax), positions minor: a TPU lays such
    # an array out as written, so a decode step reads each layer's slab in
    # place (kernels/decode_attention.py)
    if cfg.mla:
        return {"latent": jnp.zeros((L, batch_size, cfg.kv_dim, max_len), dt),
                "pos": jnp.zeros((), jnp.int32)}
    kvd = cfg.n_kv_heads * cfg.head_dim
    return {
        "k": jnp.zeros((L, batch_size, kvd, max_len), dt),
        "v": jnp.zeros((L, batch_size, kvd, max_len), dt),
        "pos": jnp.zeros((), jnp.int32),
    }


# =============================================================================
# Decode step (one token, cache-carried)
# =============================================================================


def decode_step(params: Params, cfg: ModelConfig, cache: Params,
                tokens: jnp.ndarray, call: CallConfig = CallConfig()
                ) -> Tuple[jnp.ndarray, Params]:
    """tokens: (B, 1) -> (logits (B, 1, V) f32, updated cache)."""
    dt = jnp.dtype(cfg.compute_dtype)
    pos = cache["pos"]
    b = tokens.shape[0]
    x = params["embed"][tokens].astype(dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    if cfg.pos_embedding == "sinusoidal":
        positions = jnp.full((b, 1), pos, jnp.int32)
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(dt)

    if cfg.family == "ssm":
        def body(x, xs):
            lp, conv, h = xs
            hin = rms_norm(x, lp["ln"], cfg.norm_eps)
            y, conv, h = ssm_lib.mamba1_decode(hin, lp["mixer"], cfg, conv, h)
            return x + y, (conv, h)

        x, (conv, h) = jax.lax.scan(
            body, x, (params["layers"], cache["conv"], cache["h"]))
        new_cache = {"conv": conv, "h": h, "pos": pos + 1}

    elif cfg.family == "hybrid":
        hb = cfg.hybrid
        period = hb.period
        shared_cfg = dataclasses.replace(
            cfg, n_heads=hb.shared_attn_heads, n_kv_heads=hb.shared_attn_kv_heads,
            head_dim=cfg.d_model // hb.shared_attn_heads, qkv_bias=False)
        sb = params["shared_block"]

        def body(carry, xs):
            x, kc, vc = carry
            lp, conv, h, idx = xs
            hin = rms_norm(x, lp["ln"], cfg.norm_eps)
            y, conv, h = ssm_lib.mamba2_decode(hin, lp["mixer"], cfg, conv, h)
            x = x + y

            def apply_shared(args):
                x, kc, vc = args
                hh = rms_norm(x, sb["ln1"], cfg.norm_eps)
                o, kc, vc = attn.gqa_decode(hh, sb["attn"], shared_cfg, kc, vc,
                                            pos, idx // period)
                x = x + o
                hh = rms_norm(x, sb["ln2"], cfg.norm_eps)
                x = x + gated_mlp(hh, sb["mlp"]["wi"], sb["mlp"]["wg"],
                                  sb["mlp"]["wo"], cfg.act)
                return x, kc, vc

            x, kc, vc = jax.lax.cond(
                (idx + 1) % period == 0, apply_shared, lambda a: a, (x, kc, vc))
            return (x, kc, vc), (conv, h)

        (x, kc, vc), (conv, h) = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], cache["conv"], cache["h"],
             jnp.arange(cfg.n_layers)))
        new_cache = {"conv": conv, "h": h, "k": kc, "v": vc, "pos": pos + 1}

    else:
        x, new_cache = _attn_decode_layers(params, cfg, cache, x,
                                           jnp.full((b,), pos, jnp.int32))
        new_cache["pos"] = pos + 1

    return unembed(params, cfg, x), new_cache


def _attn_decode_layers(params: Params, cfg: ModelConfig, cache: Params,
                        x: jnp.ndarray, pos_b: jnp.ndarray):
    """The attention families' decode layer scans.  The stacked caches (K/V,
    or MLA's latent cache) ride the carry and each layer writes only this
    token's column, in place: no layer's slab is sliced out, copied or
    stacked into a new cache.  -> (x, caches without ``pos``)."""
    names = ("latent",) if cfg.mla else ("k", "v")

    def body(carry, xs):
        x, kv = carry
        lp, idx = xs
        hin = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one=cfg.embed_scale)
        if cfg.mla:
            o, lat = attn.mla_decode_ragged(hin, lp["attn"], cfg, kv[0],
                                            pos_b, idx)
            kv = (lat,)
        else:
            o, kc, vc = attn.gqa_decode_ragged(hin, lp["attn"], cfg, *kv,
                                               pos_b, idx)
            kv = (kc, vc)
        x = x + o
        hin = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one=cfg.embed_scale)
        return (x + _ffn(hin, lp, cfg, no_drop=True)[0], kv), None

    (x, kv), _ = _scan_stacks(body, (x, tuple(cache[n] for n in names)),
                              params, cfg)
    return x, dict(zip(names, kv))


def decode_step_ragged(params: Params, cfg: ModelConfig, cache: Params,
                       tokens: jnp.ndarray, pos_b: jnp.ndarray,
                       call: CallConfig = CallConfig()
                       ) -> Tuple[jnp.ndarray, Params]:
    """One decode step with *per-row* positions (continuous batching).

    ``pos_b``: (B,) int32 — each batch row writes its KV at its own cache
    position and attends over its own prefix, so rows at different
    generation depths (late-joining requests, different prompt lengths)
    share one program.  Attention families (GQA and MLA) only: SSM/hybrid
    state caches are position-free recurrences whose shared scan carry
    cannot be row-shifted.
    """
    if cfg.family in ("ssm", "hybrid") or cfg.frontend:
        raise NotImplementedError(
            "ragged decode is implemented for the attention families only "
            "(no SSM/hybrid state, no modality-prefix frontends)")
    dt = jnp.dtype(cfg.compute_dtype)
    x = params["embed"][tokens].astype(dt)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dt)
    if cfg.pos_embedding == "sinusoidal":
        x = x + sinusoidal_positions(pos_b[:, None], cfg.d_model).astype(dt)

    x, new_cache = _attn_decode_layers(params, cfg, cache, x, pos_b)
    new_cache["pos"] = jnp.max(pos_b) + 1
    return unembed(params, cfg, x), new_cache


# =============================================================================
# Prefill: forward + cache population
# =============================================================================


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            max_len: int, call: CallConfig = CallConfig()
            ) -> Tuple[jnp.ndarray, Params]:
    """Process a full prompt, returning (last-position logits, primed cache)."""
    if call.cast_params_once:
        params = _cast_layers(params, cfg)
    x, positions, prefix_len = embed_inputs(params, cfg, batch)
    b, s = x.shape[0], x.shape[1]
    dt = jnp.dtype(cfg.compute_dtype)

    if cfg.family == "ssm":
        def body(x, lp):
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            y, (conv_tail, h_last) = ssm_lib.mamba1_block(
                h, lp["mixer"], cfg, return_state=True)
            return x + y, (conv_tail, h_last)

        if call.remat:
            body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
        x, (conv, h) = jax.lax.scan(body, x, params["layers"])
        cache = {"conv": conv, "h": h, "pos": jnp.asarray(s, jnp.int32)}
        return unembed(params, cfg, x[:, -1:]), cache

    if cfg.family == "hybrid":
        # Mamba-2 layers run full-sequence; the shared attention block's KV
        # cache rides the scan carry (written at its application index).
        cache = init_cache(cfg, b, max_len)
        period = cfg.hybrid.period

        def body(carry, xs):
            x, kc, vc = carry
            lp, idx = xs
            h = rms_norm(x, lp["ln"], cfg.norm_eps)
            y, (conv_tail, h_last) = ssm_lib.mamba2_block(
                h, lp["mixer"], cfg, return_state=True)
            x = x + y

            def apply_shared(args):
                x, kc, vc = args
                hb = cfg.hybrid
                shared_cfg = dataclasses.replace(
                    cfg, n_heads=hb.shared_attn_heads,
                    n_kv_heads=hb.shared_attn_kv_heads,
                    head_dim=cfg.d_model // hb.shared_attn_heads, qkv_bias=False)
                sb = params["shared_block"]
                hh = rms_norm(x, sb["ln1"], cfg.norm_eps)
                q, k, v = attn.gqa_project(hh, sb["attn"], shared_cfg, positions)
                o = attn.multihead_attention(q, k, v, impl=call.attn_impl)
                x = x + jnp.einsum("bsk,kd->bsd", attn._merge_heads(o),
                                   sb["attn"]["wo"].astype(dt))
                hh = rms_norm(x, sb["ln2"], cfg.norm_eps)
                x = x + gated_mlp(hh, sb["mlp"]["wi"], sb["mlp"]["wg"],
                                  sb["mlp"]["wo"], cfg.act)
                app = idx // period
                km = attn._merge_heads(k).swapaxes(1, 2).astype(kc.dtype)
                vm = attn._merge_heads(v).swapaxes(1, 2).astype(vc.dtype)
                kc = jax.lax.dynamic_update_slice(kc, km[None], (app, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(vc, vm[None], (app, 0, 0, 0))
                return x, kc, vc

            x, kc, vc = jax.lax.cond(
                (idx + 1) % period == 0, apply_shared, lambda a: a, (x, kc, vc))
            return (x, kc, vc), (conv_tail, h_last)

        (x, kc, vc), (conv, h) = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
        cache = {"conv": conv, "h": h, "k": kc, "v": vc,
                 "pos": jnp.asarray(s, jnp.int32)}
        return unembed(params, cfg, x[:, -1:]), cache

    # Attention families: run the train forward while collecting K/V (or MLA
    # compressed states) per layer.
    def body(x, xs):
        lp, idx = xs
        h = rms_norm(x, lp["ln1"], cfg.norm_eps, plus_one=cfg.embed_scale)
        if cfg.mla:
            c, krope = attn.mla_compress_kv(h, lp["attn"], cfg, positions)
            x = x + attn.mla_attention(h, lp["attn"], cfg, positions,
                                       impl=call.attn_impl, c=c, k_rope=krope,
                                       chunk=call.attn_chunk,
                                       remat_chunk=call.attn_chunk_remat,
                                       q_sharding=call.attn_q_sharding)
            stash = jnp.concatenate([c, krope[:, 0]], axis=-1)
        else:
            q, k, v = attn.gqa_project(h, lp["attn"], cfg, positions)
            o = attn.multihead_attention(q, k, v, impl=call.attn_impl,
                                         prefix_len=prefix_len,
                                         chunk=call.attn_chunk,
                                         remat_chunk=call.attn_chunk_remat,
                                         q_sharding=call.attn_q_sharding)
            x = x + jnp.einsum("bsk,kd->bsd", attn._merge_heads(o),
                               lp["attn"]["wo"].astype(dt))
            stash = (attn._merge_heads(k), attn._merge_heads(v))
        h = rms_norm(x, lp["ln2"], cfg.norm_eps, plus_one=cfg.embed_scale)
        return x + _ffn(h, lp, cfg, call.moe_no_drop)[0], stash

    if call.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, stashes = _scan_stacks(body, x, params, cfg)
    stash = stashes[0] if len(stashes) == 1 else jax.tree.map(
        lambda *a: jnp.concatenate(a), *stashes)

    cache = init_cache(cfg, b, max_len)
    seq = x.shape[1]
    if cfg.mla:
        cache["latent"] = jax.lax.dynamic_update_slice(
            cache["latent"], stash.swapaxes(2, 3).astype(cache["latent"].dtype),
            (0, 0, 0, 0))
    else:
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], stash[0].swapaxes(2, 3).astype(cache["k"].dtype),
            (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], stash[1].swapaxes(2, 3).astype(cache["v"].dtype),
            (0, 0, 0, 0))
    cache["pos"] = jnp.asarray(seq, jnp.int32)
    return unembed(params, cfg, x[:, -1:]), cache
