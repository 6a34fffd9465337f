"""Weights from the seed, and a plain forward pass of DeepSeek-V2's block:
multi-head latent attention (MLA) with YaRN, a leading dense SwiGLU layer,
then mixture-of-experts layers with shared experts.

The weights are made on the device in one jitted call, in the configuration's
``torch_dtype`` (the router in float32), in the layout the serving engine
takes.  Only the routed experts held here have weights: ``n_routed_experts``
of the configuration counts them, ``published.n_routed_experts`` is the
router's width.

The reference is straightforward ``jax.numpy`` in float32 at the highest
matmul precision, one sequence at a time: RMSNorm; MLA the long way (per
head, keys ``c W_UK`` with the shared rotary key, values ``c W_UV``), the
softmax scaled by YaRN's ``mscale(mscale_all_dim)**2 / sqrt(nope + rope)``
and computed in blocks of query rows; then the dense SwiGLU or the MoE
layer: a float32 softmax over every expert, the top k, gates renormalised
only where ``norm_topk_prob`` says, the held experts' part of the routed
sum, and the shared experts.  It imports nothing of the program, and
shares its seed, float8 and RMSNorm helpers with ``bench/refmodel.py``.
``fp8=True`` computes every matrix product with its inputs rounded to
float8 (e4m3's mantissa, one scale per tensor): the control.

Departure from the checkpoint: rotary positions rotate halves, where
DeepSeek-V2 rotates interleaved pairs.  That is a fixed permutation of the
rope columns of ``wq`` and ``wdkv``, which random weights cannot tell apart.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.refmodel import HIGHEST, _fp8, _key, _rms

#: query rows of one attention block
BLOCK = 1024
_NORMS = ("final_norm", "ln1", "ln2", "kv_norm")


def shape(cfg: dict) -> tuple:
    """The configuration's numbers that the weights and the forward use, as
    a hashable tuple of (key, value)."""
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "qk_nope_head_dim", "qk_rope_head_dim", "kv_lora_rank",
            "v_head_dim", "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "first_k_dense_replace", "vocab_size", "rms_norm_eps",
            "rope_theta", "norm_topk_prob", "torch_dtype")
    out = {k: cfg[k] for k in keys}
    out["router_width"] = cfg["published"]["n_routed_experts"]
    out["rope_scaling"] = tuple(sorted(cfg["rope_scaling"].items()))
    return tuple(sorted(out.items()))


def weight_shapes(m: dict) -> dict:
    D, H, V = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    rank, vd = m["kv_lora_rank"], m["v_head_dim"]
    kd = m["first_k_dense_replace"]
    L = m["num_hidden_layers"] - kd
    Fe, E = m["moe_intermediate_size"], m["n_routed_experts"]
    Fs = m["n_shared_experts"] * Fe

    def attn(n):
        return {"wq": (n, D, H * (nope + rope)), "wdkv": (n, D, rank + rope),
                "kv_norm": (n, rank), "wuk": (n, rank, H * nope),
                "wuv": (n, rank, H * vd), "wo": (n, H * vd, D)}

    def mlp(n, F, *lead):
        return {"wi": (n, *lead, D, F), "wg": (n, *lead, D, F),
                "wo": (n, *lead, F, D)}

    out = {"embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
           "layers": {"ln1": (L, D), "ln2": (L, D), "attn": attn(L),
                      "moe": {"router": (L, D, m["router_width"]),
                              "experts": mlp(L, Fe, E),
                              "shared": mlp(L, Fs)}}}
    if kd:
        out["dense_layers"] = {"ln1": (kd, D), "ln2": (kd, D),
                               "attn": attn(kd),
                               "mlp": mlp(kd, m["intermediate_size"])}
    return out


def make_weights(cfg: dict, seed: int, sharding=None):
    """Seeded weights on the device: matrices with fan-in scaled normals,
    norm scales near one; ``torch_dtype``, the router float32."""
    m = dict(shape(cfg))
    dtype = jnp.dtype(m["torch_dtype"])
    flat, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(m), is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shp) in zip(keys, flat):
            name = path[-1].key
            dt = jnp.dtype(jnp.float32) if name == "router" else dtype
            z = jax.random.normal(k, shp, dt)
            if name in _NORMS:
                out.append(1.0 + 0.1 * z)
            else:                       # fan-in: the model width for embed
                fan_in = shp[-1] if name == "embed" else shp[-2]
                out.append(z * dt.type(1.0 / np.sqrt(fan_in)))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make, out_shardings=sharding)(_key(seed))


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(m: dict) -> tuple[np.ndarray, float]:
    """-> (inverse frequencies of the rope dims, softmax scale), after
    DeepSeek-V2's YaRN, in float64."""
    y = dict(m["rope_scaling"])
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    inv = extra / y["factor"] * ramp + extra * (1.0 - ramp)
    scale = (_mscale(y["factor"], y["mscale_all_dim"]) ** 2
             / math.sqrt(m["qk_nope_head_dim"] + dim))
    return inv, scale


def _rope(x, ang, cos_scale):
    """Half rotation of x (S, ..., d) by angles ang (S, d/2)."""
    c = (jnp.cos(ang) * cos_scale).reshape(
        ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    s = (jnp.sin(ang) * cos_scale).reshape(c.shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("m", "rows", "fp8"))
def logits(w, tokens, start, *, m, rows: int, fp8: bool = False):
    """Logits of positions start .. start+rows-1 of ``tokens`` (S,), with
    ``m`` = ``shape(cfg)``."""
    m = dict(m)
    mm = ((lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST))
          if fp8 else (lambda a, b: jnp.matmul(a, b, precision=HIGHEST)))
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), t)
    H, eps = m["num_attention_heads"], m["rms_norm_eps"]
    nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    rank, vd = m["kv_lora_rank"], m["v_head_dim"]
    S = tokens.shape[0]
    blk = min(BLOCK, S)
    inv, scale = yarn(m)
    y = dict(m["rope_scaling"])
    cos_scale = (_mscale(y["factor"], y["mscale"])
                 / _mscale(y["factor"], y["mscale_all_dim"]))
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32))                   # (S, rope/2)
    x = w["embed"][tokens].astype(jnp.float32)

    def swiglu(h, p):
        return mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wi"]), p["wo"])

    def attention(h, p):
        q = mm(h, p["wq"]).reshape(S, H, nope + rope)
        ckv = mm(h, p["wdkv"])
        c = _rms(ckv[:, :rank], p["kv_norm"], eps)
        k_rope = _rope(ckv[:, rank:], ang, cos_scale)             # (S, ρ)
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], ang, cos_scale)], -1)
        k = jnp.concatenate(
            [mm(c, p["wuk"]).reshape(S, H, nope),
             jnp.broadcast_to(k_rope[:, None], (S, H, rope))], -1)
        v = mm(c, p["wuv"]).reshape(S, H, vd).transpose(1, 0, 2)  # (H,S,v)
        kt = k.transpose(1, 2, 0)                                  # (H,·,S)
        out = []
        for r0 in range(0, S, blk):
            qb = q[r0:r0 + blk].transpose(1, 0, 2)                # (H,b,·)
            s = mm(qb, kt) * scale                                 # (H,b,S)
            keep = (jnp.arange(S)[None, :]
                    <= jnp.arange(r0, r0 + qb.shape[1])[:, None])
            s = jnp.where(keep, s, -jnp.inf)
            out.append(mm(jax.nn.softmax(s, axis=-1), v))          # (H,b,v)
        o = jnp.concatenate(out, axis=1).transpose(1, 0, 2)
        return mm(o.reshape(S, H * vd), p["wo"])

    def moe(h, p):
        probs = jax.nn.softmax(mm(h, p["router"]), axis=-1)       # (S, E)
        vals, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
        if m["norm_topk_prob"]:
            vals = vals / vals.sum(-1, keepdims=True)
        gate = jnp.zeros_like(probs).at[
            jnp.arange(S)[:, None], idx].set(vals)
        out = swiglu(h, p["shared"])
        for e in range(m["n_routed_experts"]):     # held: 0 .. count-1
            expert = jax.tree.map(lambda a, e=e: a[e], p["experts"])
            out = out + gate[:, e, None] * swiglu(h, expert)
        return out

    def layer(ffn):
        def body(x, p):
            p = f32(p)
            x = x + attention(_rms(x, p["ln1"], eps), p["attn"])
            return x + ffn(_rms(x, p["ln2"], eps), p), None
        return body

    if "dense_layers" in w:
        x, _ = jax.lax.scan(layer(lambda h, p: swiglu(h, p["mlp"])), x,
                            w["dense_layers"])
    x, _ = jax.lax.scan(layer(lambda h, p: moe(h, p["moe"])), x, w["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, start, rows)
    return mm(_rms(x, w["final_norm"].astype(jnp.float32), eps),
              w["lm_head"].astype(jnp.float32))
