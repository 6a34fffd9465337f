"""Chunked linear-recurrence (SSM scan) Pallas TPU kernel.

The falcon-mamba / zamba2 train cells are memory-bound on materialized
(B, chunk, d_inner, d_state) state tiles (EXPERIMENTS.md §Roofline): the XLA
path writes every per-step state to HBM at fusion boundaries.  This kernel
keeps the recurrence state in VMEM and emits only the (B, S, d_inner)
contraction output — the same substitution the flash kernel makes for
attention.

Computes, per (batch, channel-block):

    h_t = a_t ⊙ h_{t-1} + b_t          h ∈ R^{d_blk × N}
    y_t = Σ_n h_t[:, n] · c_t[n]       y ∈ R^{d_blk}

Grid: (B, d_inner/block_d, S/chunk) with the sequence axis innermost —
the (block_d, N) state carries across chunk steps in a VMEM scratch
accumulator, never touching HBM.  Inside a chunk the recurrence runs as an
fori_loop over time steps on VMEM-resident tiles (the TPU adaptation of the
CUDA selective-scan kernel's shared-memory tiling; a log-depth associative
formulation is a further hillclimb).

Block sizes are fitted to scoped VMEM: a (…, block_d, N) tile pads N up to
the 128-lane width, and the a and b inputs are each double-buffered, so the
two input streams hold 4 · chunk · block_d · round_up(N, 128) elements.
:func:`_fit_blocks` keeps that within :data:`VMEM_INPUT_BUDGET`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default, pad_to, round_up


#: bytes of scoped VMEM the double-buffered a/b input blocks may take: half
#: of the 16 MiB a TPU v5e kernel gets by default, leaving the rest for the
#: c and y blocks and the (block_d, N) state scratch
VMEM_INPUT_BUDGET = 8 << 20
LANES = 128


def _fit_blocks(D: int, N: int, chunk: int, block_d: int,
                itemsize: int) -> tuple:
    """-> (block_d, chunk) whose input blocks fit ``VMEM_INPUT_BUDGET``.

    ``chunk`` shrinks (in steps of 8 sublanes) until a 128-channel block
    fits; ``block_d`` then takes the largest multiple of 128 that fits, or
    the whole (8-rounded) channel axis when that is smaller.
    """
    per_step_channel = 4 * round_up(N, LANES) * itemsize
    chunk = max(8, min(chunk, VMEM_INPUT_BUDGET
                       // (per_step_channel * LANES)) // 8 * 8)
    fit = VMEM_INPUT_BUDGET // (per_step_channel * chunk) // LANES * LANES
    return min(block_d, fit, round_up(D, 8)), chunk


def _ssm_kernel(a_ref, b_ref, c_ref, y_ref, h_ref, *, chunk: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        # a, b: (1, chunk, d_blk, N); c: (1, chunk, N)
        a_t = a_ref[0, t]                        # (d_blk, N)
        b_t = b_ref[0, t]
        c_t = c_ref[0, t]                        # (N,)
        h = a_t * h + b_t
        y_ref[0, t] = jnp.sum(h * c_t[None, :], axis=-1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def ssm_scan(
    a: jnp.ndarray,     # (B, S, D, N) decay
    b: jnp.ndarray,     # (B, S, D, N) input
    c: jnp.ndarray,     # (B, S, N)    output projection
    *,
    block_d: int = 512,
    chunk: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """-> y (B, S, D) with y_t = Σ_n h_t[d, n] c_t[n]."""
    if interpret is None:
        interpret = interpret_default()
    B, S, D, N = a.shape
    bd, chunk = _fit_blocks(D, N, chunk, block_d, a.dtype.itemsize)
    Dp = round_up(D, bd)
    Sp = round_up(S, chunk)
    # pad decays with 1 and inputs with 0 so padded steps hold state
    a2 = jnp.pad(a, ((0, 0), (0, Sp - S), (0, Dp - D), (0, 0)),
                 constant_values=1.0)
    b2 = pad_to(b, (B, Sp, Dp, N))
    c2 = pad_to(c, (B, Sp, N))

    y = pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(B, Dp // bd, Sp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, bd, N), lambda bi, di, si: (bi, si, di, 0)),
            pl.BlockSpec((1, chunk, bd, N), lambda bi, di, si: (bi, si, di, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, di, si: (bi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, Dp), a.dtype),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
    )(a2, b2, c2)
    return y[:, :S, :D]
