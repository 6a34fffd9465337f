"""The ragged decode step's KV path: numbers and structure.

``decode_step_ragged`` is checked against a plain decode step written here
the long way (each layer writes the token's K/V into its cache first, then
repeats the KV heads to the query heads, casts to float32 and attends over
positions ``<= pos_b``), at grouped-query ratios G = 1, 3 and 8, through
both the XLA path and the Pallas kernel (interpret mode).  The structural
test reads the jaxpr of ``build_ragged_step``'s program: no array of the
repeated cache's size, no float32 array of the cache's size, and no
cache-sized array stacked out of the layer scan.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore
from jax.sharding import Mesh

from repro import models as M
from repro.kernels import ops
from repro.models import attention as attn
from repro.models.layers import gated_mlp, rms_norm
from repro.models.model import unembed
from repro.serve.engine import build_ragged_step

B, S = 4, 64
#: (arch, n_heads, n_kv_heads): G = 1 (plain multi-head), 3, 8 (with QKV bias)
RATIOS = [("phi3-medium-14b", 4, 4), ("smollm-360m", 6, 2),
          ("qwen1.5-110b", 8, 1)]


def _cfg(arch, hq, hkv, dtype="float32"):
    return dataclasses.replace(
        M.reduced(M.get(arch), n_heads=hq, n_kv_heads=hkv),
        param_dtype="float32", compute_dtype=dtype)


def _state(cfg, seed=0):
    """Weights, a cache of random rows, tokens, and ragged positions: a row
    at 0, one at max_len - 1, one mid-way, and an inactive slot parked past
    the end (a request that retired on the cache's last cell)."""
    kp, kk, kv, kt = jax.random.split(jax.random.key(seed), 4)
    params = M.init_params(kp, cfg)
    cache = M.init_cache(cfg, B, S)
    cache["k"] = jax.random.normal(kk, cache["k"].shape).astype(cache["k"].dtype)
    cache["v"] = jax.random.normal(kv, cache["v"].shape).astype(cache["v"].dtype)
    tokens = jax.random.randint(kt, (B, 1), 0, cfg.vocab_size, jnp.int32)
    pos_b = jnp.asarray([0, S - 1, 23, S], jnp.int32)
    return params, cache, tokens, pos_b


def _plain_step(params, cfg, cache, tokens, pos_b):
    """-> (logits, per-layer new K rows, new V rows), the seed's way."""
    dt = jnp.dtype(cfg.compute_dtype)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rows = jnp.arange(B)
    at = jnp.minimum(pos_b, S - 1)
    x = params["embed"][tokens].astype(dt)
    k_new, v_new = [], []
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a, i=layer: a[i], params["layers"])
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = attn.gqa_project(h, lp["attn"], cfg, pos_b[:, None])
        k_row, v_row = k[:, :, 0].reshape(B, -1), v[:, :, 0].reshape(B, -1)
        k_new.append(k_row)
        v_new.append(v_row)
        kc = cache["k"][layer].swapaxes(1, 2).at[rows, at].set(k_row)
        vc = cache["v"][layer].swapaxes(1, 2).at[rows, at].set(v_row)
        kk = jnp.repeat(kc.reshape(B, S, hkv, d).transpose(0, 2, 1, 3),
                        hq // hkv, axis=1).astype(jnp.float32)
        vv = jnp.repeat(vc.reshape(B, S, hkv, d).transpose(0, 2, 1, 3),
                        hq // hkv, axis=1).astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk,
                       precision="highest") / d ** 0.5
        s = jnp.where(jnp.arange(S) <= pos_b[:, None, None, None], s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv,
                       precision="highest").astype(dt)
        o = o.transpose(0, 2, 1, 3).reshape(B, 1, hq * d)
        x = x + jnp.einsum("bsk,kd->bsd", o, lp["attn"]["wo"].astype(dt))
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + gated_mlp(h, lp["mlp"]["wi"], lp["mlp"]["wg"],
                          lp["mlp"]["wo"], cfg.act)
    return unembed(params, cfg, x), jnp.stack(k_new), jnp.stack(v_new)


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("arch,hq,hkv", RATIOS)
def test_ragged_step_matches_plain_attention(arch, hq, hkv, path,
                                             monkeypatch):
    if path == "pallas":    # the kernel, in interpret mode off the chip
        monkeypatch.setattr(ops, "_resolve", lambda impl: "pallas")
    cfg = _cfg(arch, hq, hkv)
    params, cache, tokens, pos_b = _state(cfg)
    logits, new = jax.jit(
        lambda p, c, t, pb: M.decode_step_ragged(p, cfg, c, t, pb))(
            params, dict(cache), tokens, pos_b)
    want, k_new, v_new = _plain_step(params, cfg, cache, tokens, pos_b)

    live = np.asarray(pos_b) < S          # the parked slot's output is unused
    np.testing.assert_allclose(np.asarray(logits)[live],
                               np.asarray(want)[live], rtol=2e-5, atol=2e-5)
    at = np.minimum(np.asarray(pos_b), S - 1)
    for name, rows_new in (("k", k_new), ("v", v_new)):
        got, old = np.asarray(new[name]), np.asarray(cache[name])
        hit = np.zeros(got.shape, bool)
        hit[:, np.arange(B), :, at] = True
        np.testing.assert_array_equal(got[~hit], old[~hit])
        # the parked slot's later layers follow its unused attention output
        np.testing.assert_allclose(got[:, np.arange(B), :, at][live],
                                   np.asarray(rows_new).swapaxes(0, 1)[live],
                                   rtol=1e-5, atol=1e-5)
    assert int(new["pos"]) == S + 1


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("arch,hq,hkv", RATIOS)
def test_ragged_program_moves_no_cache_sized_copy(arch, hq, hkv):
    cfg = _cfg(arch, hq, hkv, dtype="bfloat16")
    b, s, d, L = 3, 40, cfg.head_dim, cfg.n_layers
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    step, _, _ = build_ragged_step(cfg, mesh, b, s, 0.0)
    params = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, b, s))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    jaxpr = jax.make_jaxpr(step)(params, cache, i32(b, 1), i32(b), i32(b),
                                 jax.eval_shape(lambda: jax.random.key(0)),
                                 i32()).jaxpr
    repeated, slab = b * s * hq * d, b * s * hkv * d
    scans = 0
    for eqn in _eqns(jaxpr):
        for v in eqn.outvars:
            n = int(np.prod(v.aval.shape))
            if hq != hkv:
                assert n != repeated, f"{eqn.primitive} repeats the cache"
            assert not (n == slab and v.aval.dtype == jnp.float32), (
                f"{eqn.primitive} upcasts a cache slab")
        if eqn.primitive.name == "scan" and eqn.params["length"] == L:
            scans += 1
            ys = eqn.outvars[eqn.params["num_carry"]:]
            assert all(int(np.prod(v.aval.shape)) < L * slab for v in ys), (
                "the layer scan stacks a new cache")
    assert scans == 1
