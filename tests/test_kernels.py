"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import chunk_len, mla_decode_attention

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n", [64, 100, 1024, 4096, 5000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_axpy(n, dtype):
    x = jnp.asarray(RNG.standard_normal(n), dtype)
    y = jnp.asarray(RNG.standard_normal(n), dtype)
    got = ops.axpy(x, y, 2.5, impl="pallas")
    want = ref.axpy(x, y, 2.5)
    assert got.shape == want.shape and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("mkn", [(128, 128, 128), (256, 384, 128),
                                 (100, 70, 36), (17, 300, 129), (512, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul(mkn, dtype):
    m, k, n = mkn
    a = jnp.asarray(RNG.standard_normal((m, k)), dtype)
    b = jnp.asarray(RNG.standard_normal((k, n)), dtype)
    got = ops.matmul(a, b, impl="pallas")
    want = ref.matmul(a, b)
    assert got.shape == (m, n)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2 if dtype == jnp.bfloat16 else 1e-3,
        atol=5e-1 if dtype == jnp.bfloat16 else 1e-2)


@pytest.mark.parametrize("block_m", [64, 128, 256])
def test_matmul_block_sweep(block_m):
    a = jnp.asarray(RNG.standard_normal((192, 256)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((256, 320)), jnp.float32)
    got = ops.matmul(a, b, impl="pallas", block_m=block_m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.matmul(a, b)),
                               rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("mn", [(256, 128), (100, 64), (512, 256), (33, 100)])
def test_atax(mn):
    m, n = mn
    a = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    got = ops.atax(a, x, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.atax(a, x)),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("mn", [(32, 64), (128, 256), (100, 50), (8, 2)])
def test_covariance(mn):
    m, n = mn
    d = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    got = ops.covariance(d, impl="pallas")
    want = ref.covariance(d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(got).T,
                               rtol=1e-5, atol=1e-5)   # symmetry


@pytest.mark.parametrize(
    "bhsd", [(1, 2, 128, 64), (2, 4, 256, 64), (1, 2, 100, 64),
             (1, 8, 128, 128), (1, 1, 384, 80)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(bhsd, causal):
    b, h, s, d = bhsd
    q = jnp.asarray(RNG.standard_normal((b, h, s, d)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, h, s, d)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, h, s, d)), jnp.float32)
    got = ops.attention(q, k, v, causal=causal, impl="pallas")
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_gqa():
    """KV heads fewer than Q heads (the wrapper's GQA repeat)."""
    q = jnp.asarray(RNG.standard_normal((2, 8, 128, 64)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 2, 128, 64)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 2, 128, 64)), jnp.float32)
    got = ops.attention(q, k, v, causal=True, impl="pallas")
    want = ops.attention(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_blocks_sweep():
    q = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)) * 0.5, jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)) * 0.5, jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), jnp.float32)
    want = ref.attention(q, k, v, causal=True)
    for bq, bkv in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        got = ops.attention(q, k, v, causal=True, impl="pallas",
                            block_q=bq, block_kv=bkv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def _positions(b, s):
    """0, the last cell, past the end (-> the last cell), mid-way; where the
    cache holds several chunks, either side of the first chunk's end and a
    row whose read is one chunk."""
    pos = [0, s - 1, s, s // 3]
    ch = chunk_len(s)
    if ch < s:
        pos += [ch - 1, ch, ch + 1, 7]
    assert len(pos) == b
    return jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("b,hq,hkv,d,s", [(4, 6, 2, 32, 256),
                                          (4, 4, 4, 16, 64),
                                          (4, 8, 1, 64, 200),
                                          (8, 6, 2, 32, 1536)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, hq, hkv, d, s, dtype):
    """Grouped-query decode against a stacked cache: the kernel's output
    matches the oracle, and each row's token lands at its position
    (``_positions``) of the given layer and nowhere else."""
    L, layer = 3, 1
    q = jnp.asarray(RNG.standard_normal((b, hq, d)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, hkv, d)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, hkv, d)), dtype)
    kc = jnp.asarray(RNG.standard_normal((L, b, hkv * d, s)), dtype)
    vc = jnp.asarray(RNG.standard_normal((L, b, hkv * d, s)), dtype)
    pos = _positions(b, s)
    got = ops.decode_attention(q, k, v, kc, vc, pos, jnp.int32(layer),
                               impl="pallas")
    want = ref.decode_attention(q, k, v, kc, vc, pos, jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               **_tol(dtype))
    at = np.minimum(np.asarray(pos), s - 1)
    for cache, new, row in ((kc, got[1], k), (vc, got[2], v)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(
            want[1] if cache is kc else want[2]))
        hit = np.zeros(cache.shape, bool)
        hit[layer, np.arange(b), :, at] = True
        np.testing.assert_array_equal(np.asarray(new)[~hit],
                                      np.asarray(cache)[~hit])
        np.testing.assert_array_equal(np.asarray(new)[layer, np.arange(b), :, at],
                                      np.asarray(row).reshape(b, -1))


@pytest.mark.parametrize("mode", ["gqa", "latent"])
def test_decode_attention_reads_only_the_live_chunks(mode):
    """Every cache cell past each row's copied chunks, and every other
    layer, is NaN.  Short rows follow long ones in the same VMEM slot, so
    stale VMEM is live past their prefix (and the first row's is unwritten,
    NaN in the interpreter): the output is finite and matches the oracle on
    the same cache with those cells zeroed, and the cache is unchanged but
    for each row's written column."""
    L, layer, s, d = 3, 1, 1536, 32
    ch = chunk_len(s)
    pos = np.asarray([3, 1535, 1400, 10, 600, 0], np.int32)
    b = len(pos)
    kvd = 2 * d
    caches = [RNG.standard_normal((L, b, kvd, s)).astype(np.float32)
              for _ in range(2 if mode == "gqa" else 1)]
    dead = np.ones(caches[0].shape, bool)
    for i, n in enumerate(-(-(pos + 1) // ch) * ch):
        dead[layer, i, :, :n] = False
    poisoned = [np.where(dead, np.nan, c).astype(np.float32) for c in caches]
    zeroed = [jnp.asarray(np.where(dead, 0, c)) for c in caches]
    pos_j = jnp.asarray(pos)
    if mode == "gqa":
        q = jnp.asarray(RNG.standard_normal((b, 4, d)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((b, 2, d)), jnp.float32)
        v = jnp.asarray(RNG.standard_normal((b, 2, d)), jnp.float32)
        got = ops.decode_attention(q, k, v, *map(jnp.asarray, poisoned),
                                   pos_j, jnp.int32(layer), impl="pallas")
        want = ref.decode_attention(q, k, v, *zeroed, pos_j,
                                    jnp.int32(layer))
        cols = [k.reshape(b, -1), v.reshape(b, -1)]
    else:
        q = jnp.asarray(RNG.standard_normal((b, 4, kvd)), jnp.float32)
        kv = jnp.asarray(RNG.standard_normal((b, kvd)), jnp.float32)
        got = mla_decode_attention(q, kv, jnp.asarray(poisoned[0]), pos_j,
                                   layer, scale=0.2, v_dim=d)
        want = ref.mla_decode_attention(q, kv, zeroed[0], pos_j, layer,
                                        scale=0.2, v_dim=d)
        cols = [kv]
    o = np.asarray(got[0])
    assert np.isfinite(o).all()
    np.testing.assert_allclose(o, np.asarray(want[0]), rtol=2e-4, atol=2e-4)
    at = np.minimum(pos, s - 1)
    for old, new, col in zip(poisoned, got[1:], cols):
        expect = old.copy()
        expect[layer, np.arange(b), :, at] = np.asarray(col)
        np.testing.assert_array_equal(np.asarray(new), expect)


@given(n=st.integers(1, 2048))
@settings(max_examples=20, deadline=None)
def test_axpy_any_length(n):
    """Property: arbitrary (non-aligned) lengths survive pad/unpad."""
    x = jnp.asarray(np.arange(n, dtype=np.float32))
    y = jnp.ones((n,), jnp.float32)
    got = ops.axpy(x, y, -1.0, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), 1.0 - np.arange(n), rtol=1e-6)


@pytest.mark.parametrize("shape", [(1, 64, 128, 16), (2, 100, 64, 16),
                                   (1, 33, 512, 8)])
@pytest.mark.parametrize("chunk", [16, 64])
def test_ssm_scan(shape, chunk):
    from repro.kernels.ssm_scan import ssm_scan
    B, S, D, N = shape
    # decays in (0, 1) keep the recurrence stable, like exp(dt·A) with A<0
    a = jnp.asarray(RNG.uniform(0.7, 0.999, shape), jnp.float32)
    b = jnp.asarray(RNG.standard_normal(shape) * 0.1, jnp.float32)
    c = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    got = ssm_scan(a, b, c, chunk=chunk, interpret=True)
    want = ref.ssm_scan(a, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssm_scan_matches_mamba_block_recurrence():
    """The kernel computes the same recurrence the Mamba-1 block uses."""
    from repro.kernels.ssm_scan import ssm_scan
    from repro.models.ssm import chunked_linear_recurrence
    B, S, D, N = 1, 48, 32, 8
    a = jnp.asarray(RNG.uniform(0.8, 0.999, (B, S, D, N)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((B, S, D, N)) * 0.1, jnp.float32)
    c = jnp.asarray(RNG.standard_normal((B, S, N)), jnp.float32)
    h, _ = chunked_linear_recurrence(a, b, jnp.zeros((B, D, N)), 16)
    want = jnp.einsum("bsdn,bsn->bsd", h, c)
    got = ssm_scan(a, b, c, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
