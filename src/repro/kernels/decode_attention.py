"""Grouped-query decode attention over a stacked KV cache, in place (Pallas TPU).

One decode step of one layer: each batch row's query token attends over
its cached prefix and its own key/value, and that key/value is written
into the cache at the row's position.  The cache is the whole stack
``(L, B, Hkv*d, S)``, positions minor, and stays where it is: the grid
walks the batch rows, the layer and the rows' positions arrive as
prefetched scalars, and each step copies from HBM only the chunks of its
row's ``(Hkv*d, S)`` slab that hold the row's prefix, in their stored
dtype.  Nothing of cache size is copied, repeated, transposed or upcast.

TPU adaptation notes:
  * positions are the cache's minor axis: the chip's own layout for a
    ``(…, Hkv*d, S)`` array, so a chunk ``(Hkv*d, CH)`` is whole tiles, and
    both products are plain MXU shapes — scores ``(Hq, Hkv*d) @ (Hkv*d,
    CH)``, output ``(Hq, CH) @ (Hkv*d, CH)ᵀ``;
  * the slab stays in HBM (``pl.ANY``); row ``b`` copies ``ceil(n_b / CH)``
    chunks into one of two VMEM slabs, ``n_b = min(pos_b[b], S - 1) + 1``,
    and row ``b + 1``'s copies start before row ``b`` computes, so the
    reads overlap the compute as the block pipeline's did;
  * the compute walks only the copied chunks, with a running max and sum
    (online softmax): VMEM past them holds another row's data or nothing,
    and a masked score's zero probability would still carry a NaN there
    into the output;
  * grouped-query attention rides in the query: a block-diagonal
    ``(Hq, Hkv*d)`` matrix whose block h holds the ``G = Hq // Hkv`` query
    heads of KV head h, so the cache is contracted as stored; the caller
    keeps each head's own block of the ``(Hq, Hkv*d)`` result;
  * probabilities enter the output product in the cache dtype, as XLA's
    default TPU precision rounds a float32 product's inputs; scores,
    softmax and accumulation stay float32;
  * the token's column is written through an aliased output whose block is
    the lane-aligned ``(Hkv*d, 128)`` window holding that position, filled
    from the copied prefix (the window lies inside it), so the write costs
    one window a row.

The latent mode (``mla_decode_attention``, MLA's absorbed decode) reads one
cache ``(L, B, r + ρ, S)`` as keys, and its first ``r`` rows as values: each
head's query is already ``(q_nope·W_UKᵀ) ‖ q_rope``, so there is no head
grouping, and the chunks are read once for both products.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default, min_tile, pad_to, round_up

NEG_INF = -1e30
LANES = 128
#: positions a row copies at a time; a multiple of ``LANES``
CHUNK = 512
#: VMEM for one grid step's K and V slabs, double-buffered: under the
#: default scoped limit with room for the score tile
VMEM_BUDGET = 12 << 20


def fits_vmem(k_cache, slabs: int = 2) -> bool:
    """Whether a row's ``slabs`` (K and V: 2; a latent cache: 1) slabs of
    (Hkv*d, S), double-buffered, fit."""
    _, _, kvd, s_len = k_cache.shape
    return 2 * slabs * kvd * s_len * k_cache.dtype.itemsize <= VMEM_BUDGET


def chunk_len(s_len: int) -> int:
    """Positions of one copied chunk of an ``s_len``-position cache: a
    multiple of 128 that divides ``s_len``, at most ``CHUNK``; the whole
    slab where ``s_len`` is no multiple of 128."""
    return math.gcd(s_len, CHUNK) if s_len % LANES == 0 else s_len


def positions_read(positions, s_len: int) -> int:
    """Cache positions the kernel copies for rows at ``positions``: each
    row's prefix and its own cell, rounded up to whole chunks."""
    ch = chunk_len(s_len)
    return sum(round_up(min(int(p), s_len - 1) + 1, ch) for p in positions)


def _walk(layer_ref, pos_ref, caches, bufs, sems, step, carry, *,
          s_len: int, chunk: int):
    """Copy this grid row's prefix chunks of each cache in ``caches`` (HBM
    refs ``(L, B, kvd, S)``) into its slot of ``bufs`` (VMEM ``(2, kvd,
    S)``), start the next row's copies into the other slot, and fold
    ``step(off, slabs, carry)`` over the copied chunks as they land.
    -> (carry, this row's slabs)."""
    i = pl.program_id(0)
    layer = layer_ref[0]
    slot = i % 2

    def n_chunks(row):
        return jnp.minimum(pos_ref[row], s_len - 1) // chunk + 1

    def copy(row, slot, c, k):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        return pltpu.make_async_copy(caches[k].at[layer, row, :, at],
                                     bufs[k].at[slot, :, at],
                                     sems.at[slot, k, c])

    def start(row, slot):
        def body(c, _):
            for k in range(len(caches)):
                copy(row, slot, c, k).start()
        jax.lax.fori_loop(0, n_chunks(row), body, None)

    @pl.when(i == 0)
    def _():
        start(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        start(i + 1, 1 - slot)

    slabs = [b.at[slot] for b in bufs]

    def body(c, carry):
        for k in range(len(caches)):
            copy(i, slot, c, k).wait()
        off = pl.multiple_of(c * chunk, chunk)
        return step(off, slabs, carry)

    return jax.lax.fori_loop(0, n_chunks(i), body, carry), slabs


def _attend(q, row_k, row_v, pos, scale, chunk):
    """The online-softmax fold of one row: ``q`` (Hp, kvd) against its own
    key and value rows (1, kvd) and (1, v), then each copied chunk, whose
    keys are the first slab's (kvd, CH) block and whose values are the first
    v rows of the last slab's.  -> (init carry, step, finish)."""
    f32 = jnp.float32
    v_rows = row_v.shape[1]
    s_new = jnp.sum(q.astype(f32) * row_k.astype(f32), axis=1,
                    keepdims=True) * scale              # (Hp, 1)
    init = (s_new, jnp.ones_like(s_new),
            jnp.broadcast_to(row_v.astype(f32), (q.shape[0], row_v.shape[1])))

    def step(off, slabs, carry):
        m, l, acc = carry
        kc = slabs[0][:, pl.ds(off, chunk)]
        vc = slabs[-1][:v_rows, pl.ds(off, chunk)]
        s = jnp.dot(q, kc, preferred_element_type=f32) * scale  # (Hp, CH)
        cols = off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        pv = jax.lax.dot_general(p.astype(vc.dtype), vc,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)
        return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                alpha * acc + pv)

    def finish(carry):
        _, l, acc = carry
        return acc / l

    return init, step, finish


def _write_window(out_ref, slab, col_ref, pos, s_len, window):
    """The aliased ``(kvd, window)`` block holding ``min(pos, S - 1)``: the
    copied prefix's window with this token's column in it."""
    at = jnp.minimum(pos, s_len - 1)
    start = pl.multiple_of(at // window * window, window)
    hit = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape[2:], 1) == at - start
    out_ref[0, 0] = jnp.where(hit, col_ref[0], slab[:, pl.ds(start, window)])


def _kernel(layer_ref, pos_ref, q_ref, krow_ref, vrow_ref, kcol_ref,
            vcol_ref, k_hbm, v_hbm, o_ref, ko_ref, vo_ref, k_buf, v_buf,
            sems, *, s_len: int, chunk: int, window: int, scale: float):
    pos = pos_ref[pl.program_id(0)]
    init, step, finish = _attend(q_ref[0], krow_ref[0], vrow_ref[0], pos,
                                 scale, chunk)
    carry, (kc, vc) = _walk(layer_ref, pos_ref, (k_hbm, v_hbm),
                            (k_buf, v_buf), sems, step, init, s_len=s_len,
                            chunk=chunk)
    o_ref[0] = finish(carry)
    _write_window(ko_ref, kc, kcol_ref, pos, s_len, window)
    _write_window(vo_ref, vc, vcol_ref, pos, s_len, window)


def _cache_call(kernel, b, caches, row_specs, row_args, o_shape, *, name,
                interpret):
    """The ``pallas_call`` both modes share: scalar-prefetched layer and
    positions, per-row blocks ``row_specs``/``row_args``, the caches in HBM
    with two VMEM slabs each, and each cache aliased to an output whose
    block is the window at the row's position.  Off the TPU it runs in the
    TPU interpreter, which fills VMEM that no copy wrote with NaN and lands
    each copy only when it is waited on.  -> (o, *caches)."""
    _, _, kvd, s_len = caches[0].shape
    dt = caches[0].dtype
    window = LANES if s_len % LANES == 0 else s_len

    def row(i, layer_ref, pos_ref):
        return (i, 0, 0)

    def cell(i, layer_ref, pos_ref):
        return (layer_ref[0], i, 0,
                jnp.minimum(pos_ref[i], s_len - 1) // window)

    n_in = 2 + len(row_args)
    chunk = chunk_len(s_len)
    return pl.pallas_call(
        functools.partial(kernel, s_len=s_len, chunk=chunk, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec(shape, row) for shape in row_specs]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(caches),
            out_specs=[pl.BlockSpec((1,) + o_shape[1:], row)]
            + [pl.BlockSpec((1, 1, kvd, window), cell)] * len(caches),
            scratch_shapes=[pltpu.VMEM((2, kvd, s_len), dt)] * len(caches)
            + [pltpu.SemaphoreType.DMA((2, len(caches), s_len // chunk))],
        ),
        out_shape=[jax.ShapeDtypeStruct(o_shape, jnp.float32)]
        + [jax.ShapeDtypeStruct(c.shape, dt) for c in caches],
        input_output_aliases={n_in + k: 1 + k for k in range(len(caches))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )


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
    kvd = k_cache.shape[2]
    dt = k_cache.dtype
    hp = round_up(hq, min_tile(dt)[0])
    eye = jnp.eye(hkv, dtype=dt)
    q_blocks = jnp.einsum("bhge,hj->bhgje",
                          q.reshape(b, hkv, hq // hkv, d).astype(dt), eye)
    q_blocks = pad_to(q_blocks.reshape(b, hq, kvd), (b, hp, kvd))
    rows = (k.reshape(b, 1, kvd).astype(dt), v.reshape(b, 1, kvd).astype(dt))
    cols = (k.reshape(b, kvd, 1).astype(dt), v.reshape(b, kvd, 1).astype(dt))
    call = _cache_call(
        functools.partial(_kernel, scale=1.0 / d ** 0.5), b,
        (k_cache, v_cache),
        [(1, hp, kvd), (1, 1, kvd), (1, 1, kvd), (1, kvd, 1), (1, kvd, 1)],
        (q_blocks, *rows, *cols), (b, hp, kvd), name="decode_attention",
        interpret=interpret)
    o, k_cache, v_cache = call(
        jnp.reshape(layer, (1,)).astype(jnp.int32), pos_b.astype(jnp.int32),
        q_blocks, *rows, *cols, k_cache, v_cache)
    # head h*G+g keeps its own KV head's block of the (Hq, Hkv*d) output
    o = o[:, :hq].reshape(b, hkv, hq // hkv, hkv, d)
    return jnp.einsum("bhgje,hj->bhge", o, eye.astype(o.dtype)
                      ).reshape(b, hq, d), k_cache, v_cache


def _latent_kernel(layer_ref, pos_ref, q_ref, row_ref, col_ref, c_hbm, o_ref,
                   co_ref, c_buf, sems, *, s_len: int, chunk: int,
                   window: int, scale: float, v_dim: int):
    pos = pos_ref[pl.program_id(0)]
    row = row_ref[0]                                    # (1, r+ρ)
    init, step, finish = _attend(q_ref[0], row, row[:, :v_dim], pos, scale,
                                 chunk)
    carry, (cc,) = _walk(layer_ref, pos_ref, (c_hbm,), (c_buf,), sems, step,
                         init, s_len=s_len, chunk=chunk)
    o_ref[0] = finish(carry)
    _write_window(co_ref, cc, col_ref, pos, s_len, window)


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
    dt = cache.dtype
    hp = round_up(h, min_tile(dt)[0])
    args = (pad_to(q.astype(dt), (b, hp, kvd)),
            kv.reshape(b, 1, kvd).astype(dt), kv.reshape(b, kvd, 1).astype(dt))
    call = _cache_call(
        functools.partial(_latent_kernel, scale=scale, v_dim=v_dim), b,
        (cache,), [(1, hp, kvd), (1, 1, kvd), (1, kvd, 1)], args,
        (b, hp, v_dim), name="mla_decode_attention", interpret=interpret)
    o, cache = call(jnp.reshape(layer, (1,)).astype(jnp.int32),
                    pos_b.astype(jnp.int32), *args, cache)
    return o[:, :h], cache
