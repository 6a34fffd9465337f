"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""

from __future__ import annotations

import jax.numpy as jnp


def axpy(x: jnp.ndarray, y: jnp.ndarray, alpha) -> jnp.ndarray:
    return jnp.asarray(alpha, x.dtype) * x + y


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.dot(
        a.astype(jnp.float32), b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(a.dtype)


def atax(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    a32 = a.astype(jnp.float32)
    return (a32.T @ (a32 @ x.astype(jnp.float32))).astype(a.dtype)


def covariance(data: jnp.ndarray) -> jnp.ndarray:
    d32 = data.astype(jnp.float32)
    centred = d32 - jnp.mean(d32, axis=1, keepdims=True)
    return (centred @ centred.T / (data.shape[1] - 1)).astype(data.dtype)


def attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal: bool = True
) -> jnp.ndarray:
    """Naive O(S²) attention, f32 accumulation — the flash oracle."""
    b, h, sq, d = q.shape
    skv = k.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / (d ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def decode_attention(q, k, v, k_cache, v_cache, pos_b, layer):
    """The decode kernel's oracle (and its path off-TPU): the same
    block-diagonal contraction of the stacked ``(L, B, Hkv*d, S)`` cache in
    its stored dtype, with float32 probabilities in the output product."""
    import jax

    b, hq, d = q.shape
    hkv = k.shape[1]
    _, _, kvd, s_len = k_cache.shape
    dt, f32 = k_cache.dtype, jnp.float32
    eye = jnp.eye(hkv, dtype=dt)
    q_blocks = jnp.einsum("bhge,hj->bhgje",
                          q.reshape(b, hkv, hq // hkv, d).astype(dt),
                          eye).reshape(b, hq, kvd)
    kc = jax.lax.dynamic_index_in_dim(k_cache, layer, 0, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(v_cache, layer, 0, keepdims=False)
    kn, vn = k.reshape(b, kvd).astype(dt), v.reshape(b, kvd).astype(dt)
    scale = 1.0 / d ** 0.5
    s = jnp.einsum("bqk,bks->bqs", q_blocks, kc,
                   preferred_element_type=f32) * scale          # (B,Hq,S)
    s = jnp.where(jnp.arange(s_len) < pos_b[:, None, None], s, -1e30)
    s_new = jnp.einsum("bqk,bk->bq", q_blocks, kn,
                       preferred_element_type=f32)[..., None] * scale
    m = jnp.maximum(s.max(-1, keepdims=True), s_new)
    p, p_new = jnp.exp(s - m), jnp.exp(s_new - m)
    o = jnp.einsum("bqs,bks->bqk", p, vc, preferred_element_type=f32)
    o = (o + p_new * vn.astype(f32)[:, None]) / (p.sum(-1, keepdims=True)
                                                 + p_new)
    o = jnp.einsum("bhgje,hj->bhge", o.reshape(b, hkv, hq // hkv, hkv, d),
                   eye.astype(f32)).reshape(b, hq, d)
    at, rows = jnp.minimum(pos_b, s_len - 1), jnp.arange(b)
    return (o, k_cache.at[layer, rows, :, at].set(kn),
            v_cache.at[layer, rows, :, at].set(vn))


def mla_decode_attention(q, kv, cache, pos_b, layer, *, scale, v_dim):
    """The latent decode kernel's oracle (and its path off-TPU): each
    head's absorbed query against layer ``layer`` of the stacked ``(L, B,
    r+ρ, S)`` latent cache in its stored dtype, values its first ``v_dim``
    rows, probabilities in the cache dtype in the output product."""
    import jax

    b = q.shape[0]
    s_len = cache.shape[-1]
    dt, f32 = cache.dtype, jnp.float32
    c = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    qd, kn = q.astype(dt), kv.astype(dt)
    s = jnp.einsum("bhk,bks->bhs", qd, c,
                   preferred_element_type=f32) * scale          # (B,H,S)
    s = jnp.where(jnp.arange(s_len) < pos_b[:, None, None], s, -1e30)
    s_new = jnp.einsum("bhk,bk->bh", qd, kn,
                       preferred_element_type=f32)[..., None] * scale
    m = jnp.maximum(s.max(-1, keepdims=True), s_new)
    p, p_new = jnp.exp(s - m), jnp.exp(s_new - m)
    o = jnp.einsum("bhs,bvs->bhv", p.astype(dt), c[:, :v_dim],
                   preferred_element_type=f32)
    o = (o + p_new * kn[:, None, :v_dim].astype(f32)) / (
        p.sum(-1, keepdims=True) + p_new)
    at, rows = jnp.minimum(pos_b, s_len - 1), jnp.arange(b)
    return o, cache.at[layer, rows, :, at].set(kn)


def ssm_scan(a: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Sequential oracle for the SSM scan kernel: h_t = a_t·h_{t-1} + b_t,
    y_t = Σ_n h_t[:, n]·c_t[n].  a, b: (B,S,D,N); c: (B,S,N) -> (B,S,D)."""
    import jax

    def step(h, abc):
        a_t, b_t, c_t = abc
        h = a_t * h + b_t
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h0 = jnp.zeros(a.shape[:1] + a.shape[2:], jnp.float32)
    _, ys = jax.lax.scan(
        step, h0,
        (a.astype(jnp.float32).swapaxes(0, 1),
         b.astype(jnp.float32).swapaxes(0, 1),
         c.astype(jnp.float32).swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(a.dtype)
