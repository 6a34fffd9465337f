"""Grouped-query decode attention over a stacked KV cache, in place (Pallas TPU).

One decode step of one layer: each batch row's query token attends over
its cached prefix and its own key/value, and that key/value is written
into the cache at the row's position.  The cache is the whole stack
``(L, B, Hkv*d, S)``, positions minor, and stays where it is: the grid
walks the batch rows, the layer arrives as a prefetched scalar, and each
step reads its row's ``(Hkv*d, S)`` slab of that layer once, in its stored
dtype.  Nothing of cache size is copied, repeated, transposed or upcast.

TPU adaptation notes:
  * positions are the cache's minor axis: the chip's own layout for a
    ``(…, Hkv*d, S)`` array, so XLA hands the slab over without a relayout,
    and both products are plain MXU shapes — scores ``(Hq, Hkv*d) @ (Hkv*d,
    S)``, output ``(Hq, S) @ (Hkv*d, S)ᵀ``;
  * grouped-query attention rides in the query: a block-diagonal
    ``(Hq, Hkv*d)`` matrix whose block h holds the ``G = Hq // Hkv`` query
    heads of KV head h, so the cache is contracted as stored; the caller
    keeps each head's own block of the ``(Hq, Hkv*d)`` result;
  * probabilities enter the output product in the cache dtype, as XLA's
    default TPU precision rounds a float32 product's inputs; scores,
    softmax and accumulation stay float32;
  * the token's column is written through an aliased output whose block is
    the lane-aligned ``(Hkv*d, 128)`` window holding that position, filled
    from the slab already in VMEM, so the write costs one window a row.

The latent mode (``mla_decode_attention``, MLA's absorbed decode) reads one
cache ``(L, B, r + ρ, S)`` as keys, and its first ``r`` rows as values: each
head's query is already ``(q_nope·W_UKᵀ) ‖ q_rope``, so there is no head
grouping, and the slab is read once for both products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default, min_tile, pad_to, round_up

NEG_INF = -1e30
LANES = 128
#: VMEM for one grid step's K and V slabs, double-buffered: under the
#: default scoped limit with room for the score tile
VMEM_BUDGET = 12 << 20


def fits_vmem(k_cache, slabs: int = 2) -> bool:
    """Whether a row's ``slabs`` (K and V: 2; a latent cache: 1) slabs of
    (Hkv*d, S), double-buffered, fit."""
    _, _, kvd, s_len = k_cache.shape
    return 2 * slabs * kvd * s_len * k_cache.dtype.itemsize <= VMEM_BUDGET


def _kernel(layer_ref, pos_ref, q_ref, krow_ref, vrow_ref, kcol_ref,
            vcol_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, *, s_len: int,
            window: int, scale: float):
    del layer_ref                       # consumed by the index maps
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0]                                        # (Hp, kvd)
    kc, vc = k_ref[0, 0], v_ref[0, 0]                   # (kvd, S)
    f32 = jnp.float32
    s = jnp.dot(q, kc, preferred_element_type=f32) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < pos, s, NEG_INF)
    s_new = jnp.sum(q.astype(f32) * krow_ref[0].astype(f32), axis=1,
                    keepdims=True) * scale              # (Hp, 1)
    m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), s_new)
    p = jnp.exp(s - m)
    p_new = jnp.exp(s_new - m)
    o = jax.lax.dot_general(p.astype(vc.dtype), vc, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)  # (Hp, kvd)
    o = o + p_new * vrow_ref[0].astype(f32)
    o_ref[0] = o / (jnp.sum(p, axis=1, keepdims=True) + p_new)

    at = jnp.minimum(pos, s_len - 1)
    start = pl.multiple_of(at // window * window, window)
    hit = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[2:], 1) == at - start
    ko_ref[0, 0] = jnp.where(hit, kcol_ref[0], k_ref[0, 0, :, pl.ds(start, window)])
    vo_ref[0, 0] = jnp.where(hit, vcol_ref[0], v_ref[0, 0, :, pl.ds(start, window)])


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, k_cache, v_cache, pos_b, layer, *,
                     interpret: bool | None = None):
    """q: (B, Hq, d); k, v: (B, Hkv, d), this token's key and value;
    k_cache/v_cache: (L, B, Hkv*d, S); pos_b: (B,) int32, each row's
    position (its cached prefix is ``[0, pos_b)``; a position past the end
    writes the last cell); layer: int32 scalar.

    -> (o (B, Hq, d) float32, k_cache, v_cache) with ``k``/``v`` written at
    ``[layer, b, :, min(pos_b[b], S - 1)]``.
    """
    if interpret is None:
        interpret = interpret_default()
    b, hq, d = q.shape
    hkv = k.shape[1]
    _, _, kvd, s_len = k_cache.shape
    dt = k_cache.dtype
    hp = round_up(hq, min_tile(dt)[0])
    window = LANES if s_len % LANES == 0 else s_len
    eye = jnp.eye(hkv, dtype=dt)
    q_blocks = jnp.einsum("bhge,hj->bhgje",
                          q.reshape(b, hkv, hq // hkv, d).astype(dt), eye)
    q_blocks = pad_to(q_blocks.reshape(b, hq, kvd), (b, hp, kvd))
    rows = (k.reshape(b, 1, kvd).astype(dt), v.reshape(b, 1, kvd).astype(dt))
    cols = (k.reshape(b, kvd, 1).astype(dt), v.reshape(b, kvd, 1).astype(dt))

    def row(i, layer_ref, pos_ref):
        return (i, 0, 0)

    def slab(i, layer_ref, pos_ref):
        return (layer_ref[0], i, 0, 0)

    def cell(i, layer_ref, pos_ref):
        return (layer_ref[0], i, 0,
                jnp.minimum(pos_ref[i], s_len - 1) // window)

    kernel = functools.partial(_kernel, s_len=s_len, window=window,
                               scale=1.0 / d ** 0.5)
    o, k_cache, v_cache = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hp, kvd), row),
                pl.BlockSpec((1, 1, kvd), row),
                pl.BlockSpec((1, 1, kvd), row),
                pl.BlockSpec((1, kvd, 1), row),
                pl.BlockSpec((1, kvd, 1), row),
                pl.BlockSpec((1, 1, kvd, s_len), slab),
                pl.BlockSpec((1, 1, kvd, s_len), slab),
            ],
            out_specs=[
                pl.BlockSpec((1, hp, kvd), row),
                pl.BlockSpec((1, 1, kvd, window), cell),
                pl.BlockSpec((1, 1, kvd, window), cell),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hp, kvd), jnp.float32),
            jax.ShapeDtypeStruct(k_cache.shape, dt),
            jax.ShapeDtypeStruct(v_cache.shape, dt),
        ],
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos_b.astype(jnp.int32),
      q_blocks, *rows, *cols, k_cache, v_cache)
    # head h*G+g keeps its own KV head's block of the (Hq, Hkv*d) output
    o = o[:, :hq].reshape(b, hkv, hq // hkv, hkv, d)
    return jnp.einsum("bhgje,hj->bhge", o, eye.astype(o.dtype)
                      ).reshape(b, hq, d), k_cache, v_cache


def _latent_kernel(layer_ref, pos_ref, q_ref, row_ref, col_ref, c_ref, o_ref,
                   co_ref, *, s_len: int, window: int, scale: float,
                   v_dim: int):
    del layer_ref                       # consumed by the index maps
    pos = pos_ref[pl.program_id(0)]
    q = q_ref[0]                                        # (Hp, r+ρ)
    kc = c_ref[0, 0]                                    # (r+ρ, S)
    row = row_ref[0]                                    # (1, r+ρ)
    f32 = jnp.float32
    s = jnp.dot(q, kc, preferred_element_type=f32) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < pos, s, NEG_INF)
    s_new = jnp.sum(q.astype(f32) * row.astype(f32), axis=1,
                    keepdims=True) * scale              # (Hp, 1)
    m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), s_new)
    p = jnp.exp(s - m)
    p_new = jnp.exp(s_new - m)
    o = jax.lax.dot_general(p.astype(kc.dtype), kc[:v_dim],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)  # (Hp, r)
    o = o + p_new * row[:, :v_dim].astype(f32)
    o_ref[0] = o / (jnp.sum(p, axis=1, keepdims=True) + p_new)

    at = jnp.minimum(pos, s_len - 1)
    start = pl.multiple_of(at // window * window, window)
    hit = jax.lax.broadcasted_iota(jnp.int32, co_ref.shape[2:], 1) == at - start
    co_ref[0, 0] = jnp.where(hit, col_ref[0],
                             c_ref[0, 0, :, pl.ds(start, window)])


@functools.partial(jax.jit, static_argnames=("scale", "v_dim", "interpret"))
def mla_decode_attention(q, kv, cache, pos_b, layer, *, scale: float,
                         v_dim: int, interpret: bool | None = None):
    """q: (B, H, r+ρ), each head's absorbed query; kv: (B, r+ρ), this
    token's latent column; cache: (L, B, r+ρ, S), whose first ``v_dim``
    rows are the values; pos_b: (B,) int32 as in ``decode_attention``;
    scale: the softmax scale.

    -> (o (B, H, v_dim) float32, cache) with ``kv`` written at
    ``[layer, b, :, min(pos_b[b], S - 1)]``.
    """
    if interpret is None:
        interpret = interpret_default()
    b, h, kvd = q.shape
    s_len = cache.shape[-1]
    dt = cache.dtype
    hp = round_up(h, min_tile(dt)[0])
    window = LANES if s_len % LANES == 0 else s_len

    def row(i, layer_ref, pos_ref):
        return (i, 0, 0)

    def slab(i, layer_ref, pos_ref):
        return (layer_ref[0], i, 0, 0)

    def cell(i, layer_ref, pos_ref):
        return (layer_ref[0], i, 0,
                jnp.minimum(pos_ref[i], s_len - 1) // window)

    kernel = functools.partial(_latent_kernel, s_len=s_len, window=window,
                               scale=scale, v_dim=v_dim)
    o, cache = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hp, kvd), row),
                pl.BlockSpec((1, 1, kvd), row),
                pl.BlockSpec((1, kvd, 1), row),
                pl.BlockSpec((1, 1, kvd, s_len), slab),
            ],
            out_specs=[
                pl.BlockSpec((1, hp, v_dim), row),
                pl.BlockSpec((1, 1, kvd, window), cell),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hp, v_dim), jnp.float32),
            jax.ShapeDtypeStruct(cache.shape, dt),
        ],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos_b.astype(jnp.int32),
      pad_to(q.astype(dt), (b, hp, kvd)), kv.reshape(b, 1, kvd).astype(dt),
      kv.reshape(b, kvd, 1).astype(dt), cache)
    return o[:, :h], cache
