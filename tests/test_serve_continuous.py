"""Continuous batching (generate_many) + sub-batch padding (subprocess),
and the decode kernel's path through generate_many (in process)."""

import contextlib
import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

from repro import models as M
from repro.kernels import ops
from repro.serve import ServeConfig, ServeEngine
from repro.serve import engine as serve_engine


def test_subbatch_padding_matches_full_batch(subproc):
    """generate() on b < batch pads to the configured batch and slices:
    real rows' tokens are identical to the same rows in a full batch."""
    subproc("""
import jax, numpy as np
from jax.sharding import Mesh
from repro import models as M
from repro.dist.sharding import param_specs, to_shardings
from repro.serve import ServeConfig, ServeEngine

cfg = M.reduced(M.get("smollm-360m"))
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
params = M.init_params(jax.random.key(0), cfg)
params = jax.device_put(params, to_shardings(param_specs(params, mesh), mesh))
prompts = np.random.default_rng(2).integers(
    0, cfg.vocab_size, (4, 9)).astype(np.int32)
eng = ServeEngine(cfg, params, mesh, ServeConfig(batch=4, max_len=40))
full = eng.generate(prompts, 6)
for b in (1, 2, 3):
    sub = eng.generate(prompts[:b], 6)
    assert sub.shape == (b, 6)
    np.testing.assert_array_equal(sub, full[:b])
assert eng.stats["batch_padded_rows"] == 3 + 2 + 1
try:
    eng.generate(np.concatenate([prompts, prompts]), 6)
    raise SystemExit("expected ValueError for oversized batch")
except ValueError:
    pass
print("OK")
""", devices=8, x64=False, timeout=900)


def test_generate_many_matches_static_and_is_schedule_independent(subproc):
    """Greedy continuous batching == static generate for a full same-length
    batch, and each request's tokens are independent of co-scheduling
    (variable lengths, staggered arrivals, R > batch)."""
    subproc("""
import jax, numpy as np
from jax.sharding import Mesh
from repro import models as M
from repro.dist.sharding import param_specs, to_shardings
from repro.serve import ServeConfig, ServeEngine

cfg = M.reduced(M.get("smollm-360m"))
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
params = M.init_params(jax.random.key(0), cfg)
params = jax.device_put(params, to_shardings(param_specs(params, mesh), mesh))
rng = np.random.default_rng(5)
prompts = rng.integers(0, cfg.vocab_size, (4, 9)).astype(np.int32)

eng = ServeEngine(cfg, params, mesh,
                  ServeConfig(batch=4, max_len=48, prefill_bucket=8))
ref = eng.generate(prompts, 6)
outs = eng.generate_many([(prompts[i], 6) for i in range(4)])
for i in range(4):
    np.testing.assert_array_equal(outs[i], ref[i])

# variable lengths (incl. a single-token prompt: insert with no prefill),
# staggered arrivals, more requests than slots
lens = [1, 9, 7, 12, 6, 9]
reqs = [(rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32), 5)
        for s in lens]
outs2 = eng.generate_many(reqs, arrival_steps=[0, 0, 1, 3, 6, 8])
assert [len(o) for o in outs2] == [5] * 6
assert eng.stats["requests_retired"] >= 10
# schedule independence: each request alone emits the same greedy tokens
for i in (0, 3, 5):
    solo = eng.generate_many([reqs[i]])[0]
    np.testing.assert_array_equal(outs2[i], solo)

# mid-stream insert really interleaves: slots were refilled, not batched
assert eng.stats["prefill_inserts"] >= 4 + 6 + 3
print("OK")
""", devices=8, x64=False, timeout=900)


def test_generate_many_temperature_reproducible(subproc):
    """Temperature sampling through the ragged step: a fixed seed and a
    fixed schedule reproduce exactly; tokens stay in range."""
    subproc("""
import jax, numpy as np
from jax.sharding import Mesh
from repro import models as M
from repro.dist.sharding import param_specs, to_shardings
from repro.serve import ServeConfig, ServeEngine

cfg = M.reduced(M.get("smollm-360m"))
mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
params = M.init_params(jax.random.key(0), cfg)
params = jax.device_put(params, to_shardings(param_specs(params, mesh), mesh))
rng = np.random.default_rng(9)
reqs = [(rng.integers(0, cfg.vocab_size, (s,)).astype(np.int32), 4)
        for s in (4, 7, 6, 9, 5)]
# prefill_bucket > max_len exercises the bucket cap (prefill padded to
# the cache length, never past it)
eng = ServeEngine(cfg, params, mesh,
                  ServeConfig(batch=2, max_len=32, temperature=0.7,
                              prefill_bucket=64))
a = eng.generate_many(reqs, arrival_steps=[0, 0, 2, 4, 4])
b = eng.generate_many(reqs, arrival_steps=[0, 0, 2, 4, 4])
for x, y in zip(a, b):
    np.testing.assert_array_equal(x, y)
    assert (x >= 0).all() and (x < cfg.vocab_size).all()
print("OK")
""", devices=8, x64=False, timeout=900)


def test_generate_many_kernel_path_keeps_greedy_tokens(monkeypatch):
    """The decode kernel (interpret mode) through generate_many on one
    device: with slots retired and refilled, free slots decoding at
    position 0 and a request crossing a 128-position chunk, every request
    emits the greedy tokens it emits alone on the XLA path, and each step's
    span counts the whole chunks its live slots' prefixes take."""
    cfg = dataclasses.replace(M.reduced(M.get("smollm-360m")),
                              param_dtype="float32", compute_dtype="float32")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    params = M.init_params(jax.random.key(0), cfg)
    scfg = ServeConfig(batch=3, max_len=384, prefill_bucket=8)
    rng = np.random.default_rng(11)
    lens, news = [9, 1, 126, 14, 5, 7], [4, 6, 5, 3, 7, 2]
    reqs = [(rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
            for n, m in zip(lens, news)]
    alone = [ServeEngine(cfg, params, mesh, scfg).generate_many([r])[0]
             for r in reqs]

    monkeypatch.setattr(ops, "_resolve", lambda impl: "pallas")
    spans = []

    def span(name, **meta):
        spans.append((name, meta))
        return contextlib.nullcontext()

    monkeypatch.setattr(serve_engine, "span", span)
    eng = ServeEngine(cfg, params, mesh, scfg)
    outs = eng.generate_many(reqs, arrival_steps=[0, 0, 0, 1, 2, 6])
    for got, want in zip(outs, alone):
        np.testing.assert_array_equal(got, want)
    assert eng.stats["requests_retired"] == len(reqs)

    # the schedule: admissions in arrival order into the lowest free slot,
    # a slot free again the step after its last token
    free_at, live = [0] * 3, []                 # live: (first step, r)
    for r, arrive in enumerate([0, 0, 0, 1, 2, 6]):
        j = min(range(3), key=lambda j: (max(free_at[j], arrive), j))
        t0 = max(free_at[j], arrive)
        live.append((t0, r))
        free_at[j] = t0 + news[r]
    read = [m["kv_read_positions"] for n, m in spans if n == "serve.decode"]
    want = []
    for t in range(len(read)):
        pos = [lens[r] - 1 + t - t0 for t0, r in live
               if t0 <= t < t0 + news[r]]
        want.append(sum(-(-(p + 1) // 128) * 128 for p in pos))
    assert read == want
    assert max(want) > 3 * 128       # request 2 reads a second chunk
