"""DeepSeek-V2's block on the serving path, against the plain reference
(``models/reference.py``) at a small size with seeded random weights.

The configuration keeps every mechanism of the published one: a leading
dense layer, MLA with YaRN, MoE layers of raw softmax-top-k gates with a
shared expert, and a device that holds a share of the routed experts.  All
compute is float32, so the program and the reference differ by rounding
and by the order of their sums: the absorbed decode contracts ``W_UK``
into the query, the reference contracts it into the keys.
"""

import collections
import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import models as M
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.decode_attention import fits_vmem, mla_decode_attention
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import reference
from repro.models.layers import yarn_freqs, yarn_range
from repro.serve import ServeConfig, ServeEngine
from repro.serve import engine as serve_engine

#: float32 throughout: a served position's logits (spread about 1) agree
#: with the reference's to 2e-6-5e-6, float32 rounding of sums taken in
#: another order; computed in bfloat16 the same model misses by 4e-2-7e-2
LOGIT_TOL = 2e-4


def _cfg(held=(2, 4)):
    """A small DeepSeek-V2: 1 dense layer + 2 MoE layers of 8 experts, top
    3, one shared; this device holds ``held`` = (first, count)."""
    full = M.get("deepseek-v2-lite-16b")
    return dataclasses.replace(
        full, name="deepseek-small", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=96, vocab_size=256,
        mla=M.MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                        qk_rope_head_dim=16, v_head_dim=16),
        moe=dataclasses.replace(full.moe, n_experts=8, top_k=3,
                                d_ff_expert=32, n_shared=1,
                                experts_held=held),
        param_dtype="float32", compute_dtype="float32")


def _params(cfg, seed=0):
    """Seeded weights with norm scales away from one, so that a norm or a
    scale applied twice or left out shows."""
    p = M.init_params(jax.random.key(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    out = []
    for k, (path, a) in zip(keys, leaves):
        name = getattr(path[-1], "key", "")
        if name in ("ln1", "ln2", "final_norm", "kv_norm"):
            a = 1.0 + 0.3 * jax.random.normal(k, a.shape, a.dtype)
        elif name == "router":       # spread the routing probabilities
            a = 4.0 * a
        out.append(a)
    return jax.tree.unflatten(tree, out)


def test_yarn_at_published_sizes():
    cfg = M.get("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    assert yarn_range(64, cfg.rope_theta, y) == (10, 23)
    # mscale = 0.1 * 0.707 * ln 40 + 1; the softmax scale takes its square
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale == pytest.approx(1.26080, abs=1e-5)
    assert attn.mla_scale(cfg) == pytest.approx(mscale ** 2 / math.sqrt(192))
    assert mscale ** 2 == pytest.approx(1.58963, abs=1e-5)
    got = np.asarray(yarn_freqs(64, cfg.rope_theta, y))
    extra = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / 40, rtol=1e-6)
    np.testing.assert_allclose(
        got, reference.yarn_inv_freq(64, cfg.rope_theta, y), rtol=1e-6)


def test_published_config():
    cfg = M.get("deepseek-v2-lite-16b")
    assert (cfg.first_k_dense, cfg.d_ff, cfg.moe.d_ff_expert) == (1, 10944,
                                                                   1408)
    assert cfg.norm_eps == 1e-6 and cfg.moe.norm_topk_prob is False
    assert M.count_params(cfg) == pytest.approx(15.71e9, rel=5e-4)
    held = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, experts_held=(0, 8)))
    assert M.count_params(held) == pytest.approx(3.111e9, rel=5e-4)


def test_held_expert_shares_add_up_to_the_layer():
    """Each of the 4 shares of 2 experts routes over all 8; their routed
    parts, with the shared expert counted once, give the uncut layer, in
    the reference and in the program's ``moe_block`` alike."""
    cfg = _cfg(held=None)
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["layers"]["moe"])
    x = jax.random.normal(jax.random.key(7), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        routed, shared = reference.moe(x[0], lp, cfg)
        whole, _ = moe_lib.moe_block(x, lp, cfg, no_drop=True)
        parts, program = [], []
        for i in range(4):
            share = dict(lp, experts=jax.tree.map(
                lambda a, i=i: a[2 * i:2 * i + 2], lp["experts"]))
            r, s = reference.moe(x[0], share, cfg, held=(2 * i, 2))
            np.testing.assert_allclose(s, shared, rtol=0, atol=0)
            parts.append(r)
            cfg_i = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, experts_held=(2 * i, 2)))
            program.append(moe_lib.moe_block(x, share, cfg_i,
                                             no_drop=True)[0][0] - shared)
    # every token's 3 experts fall in some share: nothing is lost
    np.testing.assert_allclose(sum(parts) + shared, routed + shared,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(sum(program) + shared, whole[0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(whole[0], routed + shared, rtol=1e-5,
                               atol=1e-5)
    # the gates are the raw probabilities, not renormalised ones
    normed = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, norm_topk_prob=True))
    with jax.default_matmul_precision("highest"):
        renormed = reference.moe(x[0], lp, normed)[0]
    assert np.abs(renormed - routed).max() > 0.1 * np.abs(routed).max()


#: (cache length, row positions): one chunk of 256; the slab as one chunk
#: where 128 does not divide it; three chunks of 512
LATENT_CASES = {"": (256, (0, 255, 100, 256)),
                "-s200": (200, (0, 199, 66, 200)),
                "-s1536": (1536, (511, 512, 513, 0, 1535, 7, 1536))}


@pytest.mark.parametrize("dtype,s,positions", [
    pytest.param(dtype, *case, id=dtype + name)
    for name, case in LATENT_CASES.items()
    for dtype in ("float32", "bfloat16")])
def test_latent_kernel_matches_its_xla_form(dtype, s, positions):
    """Interpret mode against ``kernels/ref.py``: rows at position 0, the
    last cell, mid-way, and parked past the end; a cache of no whole
    number of 128 positions (one chunk, the slab); and in a cache of
    several chunks, rows either side of the first chunk's end and a row
    whose read is one chunk."""
    h, r, rho, L = 4, 32, 16, 3
    b = len(positions)
    dt = jnp.dtype(dtype)
    kq, kk, kc = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, (b, h, r + rho)).astype(dt)
    kv = jax.random.normal(kk, (b, r + rho)).astype(dt)
    cache = jax.random.normal(kc, (L, b, r + rho, s)).astype(dt)
    pos_b = jnp.asarray(positions, jnp.int32)
    got_o, got_c = mla_decode_attention(q, kv, cache, pos_b, 1, scale=0.17,
                                        v_dim=r, interpret=True)
    want_o, want_c = kref.mla_decode_attention(q, kv, cache, pos_b, 1,
                                               scale=0.17, v_dim=r)
    live = np.asarray(pos_b) < s
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(got_c, np.float32),
                                  np.asarray(want_c, np.float32))
    assert fits_vmem(jax.ShapeDtypeStruct((27, 40, 576, 4096), jnp.bfloat16),
                     slabs=1)
    assert not fits_vmem(jax.ShapeDtypeStruct((27, 40, 576, 4096),
                                              jnp.bfloat16))


def test_prefill_logits_match_reference():
    cfg = _cfg()
    params = _params(cfg)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, 21)
    logits, cache = M.prefill(params, cfg, {"tokens": tokens[None]}, 32,
                              M.CallConfig(moe_no_drop=True))
    want = reference.forward(params, cfg, tokens)
    np.testing.assert_allclose(np.asarray(logits)[0, -1],
                               np.asarray(want)[-1], rtol=0, atol=LOGIT_TOL)
    assert cache["latent"].shape == (3, 1, 48, 32)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_generate_many_decodes_as_the_reference(path, monkeypatch):
    """Staggered arrivals into 3 slots, with retired slots between
    requests; every decode step's logits of every live slot against the
    reference's full forward over that request's prompt and served tokens.
    Free slots decode at position 0, and each step's span counts the
    positions its live slots attend and the whole 128-position chunks the
    kernel copies for them (one request crosses a chunk's end)."""
    if path == "pallas":    # the latent kernel, in interpret mode
        monkeypatch.setattr(ops, "_resolve", lambda impl: "pallas")
    cfg = _cfg()
    params = _params(cfg)
    steps, admitted = [], []         # (logits, pos_b) a step; (step, slot, r)
    real_step = serve_engine.decode_step_ragged

    def recording(params, cfg, cache, tokens, pos_b, call=None):
        logits, cache = real_step(params, cfg, cache, tokens, pos_b, call)
        jax.debug.callback(lambda lg, pb: steps.append(
            (np.asarray(lg), np.asarray(pb))), logits, pos_b, ordered=True)
        return logits, cache

    monkeypatch.setattr(serve_engine, "decode_step_ragged", recording)
    spans = []

    def span(name, **meta):
        spans.append((name, meta))
        return contextlib.nullcontext()

    monkeypatch.setattr(serve_engine, "span", span)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    eng = ServeEngine(cfg, params, mesh,
                      ServeConfig(batch=3, max_len=384, prefill_bucket=8))
    rng = np.random.default_rng(4)
    lens, news = [1, 9, 14, 5, 130, 11], [6, 4, 9, 7, 3, 8]
    reqs = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
            for n, m in zip(lens, news)]
    n_calls = [0]
    real_insert = eng._insert

    def insert(cache, tok, pos_b, active, slot, prompt):
        r = lens.index(prompt.size)         # the lengths are distinct
        admitted.append((n_calls[0], slot, r))
        return real_insert(cache, tok, pos_b, active, slot, prompt)

    real_get = eng._get_ragged_step

    def counting():
        fn = real_get()

        def step(*a):
            n_calls[0] += 1
            return fn(*a)
        return step

    monkeypatch.setattr(eng, "_insert", insert)
    monkeypatch.setattr(eng, "_get_ragged_step", counting)
    outs = eng.generate_many(reqs, arrival_steps=[0, 0, 1, 2, 4, 9])
    jax.effects_barrier()
    assert [len(o) for o in outs] == news
    assert len(steps) == n_calls[0]

    checked = 0
    live = collections.defaultdict(dict)        # step -> {slot: position}
    for t0, slot, r in admitted:
        for i in range(reqs[r][1]):
            live[t0 + i][slot] = lens[r] - 1 + i
    decode = [meta for name, meta in spans if name == "serve.decode"]
    assert len(decode) == len(steps)
    for t, (meta, (_, pb)) in enumerate(zip(decode, steps)):
        at = live[t]
        assert all(pb[j] == 0 for j in range(3) if j not in at)
        assert meta["kv_positions"] == sum(p + 1 for p in at.values())
        assert meta["kv_read_positions"] == sum(
            -(-(p + 1) // 128) * 128 for p in at.values())
    assert any(p >= 128 for at in live.values() for p in at.values())
    for t0, slot, r in admitted:
        prompt, m = reqs[r]
        seq = np.concatenate([prompt, outs[r][:-1]])
        want = np.asarray(reference.forward(params, cfg, seq))
        for i in range(m):
            lg, pb = steps[t0 + i]
            assert pb[slot] == prompt.size - 1 + i
            np.testing.assert_allclose(lg[slot, 0],
                                       want[prompt.size - 1 + i], rtol=0,
                                       atol=LOGIT_TOL)
            checked += 1
        # greedy: each served token is the reference's best
        np.testing.assert_array_equal(
            outs[r], want[prompt.size - 1:].argmax(-1))
    assert checked == sum(news)
