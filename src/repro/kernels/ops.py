"""Unified jit'd entry points for the Pallas kernels.

Every op takes ``impl`` ∈ {"pallas", "xla", "auto"}:
  * "pallas" — the TPU kernel (interpret mode automatically off-TPU);
  * "xla"    — the pure-jnp oracle (the dry-run path: TPU Pallas kernels do
               not lower on the CPU backend);
  * "auto"   — pallas on TPU, xla elsewhere (the framework default).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.atax import atax as _atax_pallas
from repro.kernels.axpy import axpy as _axpy_pallas
from repro.kernels.covariance import covariance as _cov_pallas
from repro.kernels.decode_attention import (
    decode_attention as _decode_pallas, fits_vmem as _decode_fits,
    mla_decode_attention as _mla_decode_pallas,
)
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.matmul import matmul as _matmul_pallas

IMPLS = ("pallas", "xla", "auto")


def _resolve(impl: str) -> str:
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def axpy(x, y, alpha, *, impl: str = "auto", **kw) -> jnp.ndarray:
    if _resolve(impl) == "pallas":
        return _axpy_pallas(x, y, alpha, **kw)
    return ref.axpy(x, y, alpha)


def matmul(a, b, *, impl: str = "auto", **kw) -> jnp.ndarray:
    if _resolve(impl) == "pallas":
        return _matmul_pallas(a, b, **kw)
    return ref.matmul(a, b)


def atax(a, x, *, impl: str = "auto", **kw) -> jnp.ndarray:
    if _resolve(impl) == "pallas":
        return _atax_pallas(a, x, **kw)
    return ref.atax(a, x)


def covariance(data, *, impl: str = "auto", **kw) -> jnp.ndarray:
    if _resolve(impl) == "pallas":
        return _cov_pallas(data, **kw)
    return ref.covariance(data)


def attention(
    q, k, v, *, causal: bool = True, impl: str = "auto", **kw
) -> jnp.ndarray:
    """Multi-head attention with GQA support: k/v may have fewer heads than q
    (q heads must be a multiple); KV heads are repeated before the kernel."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        if hq % hkv:
            raise ValueError(f"GQA heads {hq} not a multiple of {hkv}")
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if _resolve(impl) == "pallas":
        return _flash_pallas(q, k, v, causal=causal, **kw)
    return ref.attention(q, k, v, causal=causal)


def decode_attention(q, k, v, k_cache, v_cache, pos_b, layer, *,
                     impl: str = "auto", **kw):
    """One decode token per row against layer ``layer`` of the stacked
    ``(L, B, Hkv*d, S)`` caches, writing the token's K/V at ``pos_b`` in
    place (see ``kernels/decode_attention.py``).  A row's slab that does not
    fit the kernel's VMEM budget takes the XLA path, and so does a program
    that may span devices (more than one in view on a TPU): a Mosaic call
    is not partitioned across a mesh."""
    if _decode_kernel(impl) and _decode_fits(k_cache):
        return _decode_pallas(q, k, v, k_cache, v_cache, pos_b, layer, **kw)
    return ref.decode_attention(q, k, v, k_cache, v_cache, pos_b, layer)


def mla_decode_attention(q, kv, cache, pos_b, layer, *, scale: float,
                         v_dim: int, impl: str = "auto", **kw):
    """MLA's absorbed decode against layer ``layer`` of the stacked latent
    cache ``(L, B, r+ρ, S)``, writing this token's column at ``pos_b`` in
    place: the decode kernel's latent mode, on the same terms as
    ``decode_attention`` (one slab to fit, one device in view)."""
    if _decode_kernel(impl) and _decode_fits(cache, slabs=1):
        return _mla_decode_pallas(q, kv, cache, pos_b, layer, scale=scale,
                                  v_dim=v_dim, **kw)
    return ref.mla_decode_attention(q, kv, cache, pos_b, layer, scale=scale,
                                    v_dim=v_dim)


def _decode_kernel(impl: str) -> bool:
    return _resolve(impl) == "pallas" and (
        impl == "pallas" or jax.device_count() == 1)
