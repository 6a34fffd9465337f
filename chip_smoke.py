"""Chip smoke: the offload runtime and smollm-360m serving on a TPU.

    python chip_smoke.py              # one chip: device, offload, kernels, serve
    python chip_smoke.py --chips 4    # a v5e:2x2 host: the cross-chip paths only

The default run drives both main paths of the repository through the entry
points a user calls, on one chip, in five phases:

1. device check: the first JAX device must be a TPU whose ``device_kind``
   has an entry in the peak table (``repro.launch.roofline.PEAKS``);
2. offload: the paper's six jobs through ``Session.submit(...).wait()``,
   cold, warm and resident, under AUTO and the baseline policy, at the
   paper's sizes and at sizes where the device work is real;
3. kernels: every Pallas kernel compiled for the chip (``tpu_custom_call``
   in the program) and compared with ``repro.kernels.ref``;
4. serve: smollm-360m at its published widths, seed-initialised, through
   ``ServeEngine`` as ``repro.launch.serve`` builds it;
5. the last line of standard output: one JSON object naming the device.

``--chips 4`` runs only what exists across chips: multicast against the
P2P chain, tree staging and device-to-device forwarding between leases.

Everything runs in this one process, which holds the chip: it starts no
child process.  Without a TPU it exits non-zero at the device check; it
never falls back to the CPU.

x64 is off.  The serving stack is 32-bit, a TPU has no float64 datapath,
and the paper's jobs compute in their operands' device dtype, so with x64
off they run in float32 against numpy's float64 ``expected``; the
tolerances below say what that costs.  The script refuses to run with
``jax_enable_x64`` on.

Compiled programs go to JAX's persistent cache
(``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` where set,
otherwise ``<checkout>/.jax_cache``.  Each phase prints the seconds it
spent compiling, so a second run shows what the cache saved.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import NoReturn

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# -- tolerances ---------------------------------------------------------------
# Offload jobs: normwise relative error ||got - expected|| / ||expected||
# against numpy's float64 reference, the jobs running in float32 (x64 off).
#: elementwise float32 arithmetic: a couple of float32 ulps (2^-23 each)
TOL_ELEMENTWISE = 1e-6
#: float32 matrix products: XLA's default precision for a float32 dot on a
#: TPU rounds its inputs to bfloat16 (unit roundoff 2^-9); a normwise error
#: of a few such units is expected, 2^-6 is the bound
TOL_MATMUL = 2.0 ** -6
#: 0/1 adjacency and integer distances are exact in bfloat16 and float32
TOL_EXACT = 0.0
#: Monte Carlo: a sample within float32 rounding of the unit circle may fall
#: on the other side; each moves the estimate by 4/N; four are allowed
MC_FLIPS = 4

JOB_TOL = {"axpy": TOL_ELEMENTWISE, "matmul": TOL_MATMUL,
           "atax": TOL_MATMUL, "covariance": TOL_MATMUL, "bfs": TOL_EXACT}

# Kernels: normwise relative error against repro.kernels.ref evaluated at
# the highest matmul precision (an accurate float32 oracle).
#: float32 elementwise kernels (axpy, the ssm recurrence over 256 steps)
KTOL_F32_ELEMENTWISE = 1e-5
#: bfloat16 output rounding (2^-9) and float32 MXU passes over
#: bfloat16-rounded inputs: 2^-6, as for the offload matmul class
KTOL_MATMUL = 2.0 ** -6

# Serving: greedy decoding through two different programs agrees only up to
# rounding.  The logits are a bfloat16 product (8 significant bits), so a
# token whose logit sits within a few bfloat16 ulps of the top can win in
# one program and lose in another; where continuous batching and static
# generate part ways, both tokens must be such near-ties.  Computing in
# float32 at the highest matmul precision, the two must agree exactly.
#: a continuous-batching token's logit, in an independent full forward over
#: its own context, must sit below the top logit by at most this share of
#: the step's largest logit magnitude: four bfloat16 ulps (2^-8 each)
LOGIT_TIE = 2.0 ** -6

#: the job sizes of the offload phase: the ``core/jobs.py`` defaults (the
#: paper's fine-grained regime) and sizes where device work is real (axpy
#: 2^24, matmul 1024^3, PolyBench/C 4.2 LARGE atax and covariance)
OFFLOAD_SIZES = {
    "paper": {"axpy": {}, "montecarlo": {}, "matmul": {}, "atax": {},
              "covariance": {}, "bfs": {}},
    "large": {"axpy": {"N": 1 << 24}, "montecarlo": {"N": 1 << 20},
              "matmul": {"M": 1024, "K": 1024, "N": 1024},
              "atax": {"M": 1900, "N": 2100},
              "covariance": {"M": 1200, "N": 1400},
              "bfs": {"V": 1024}},
}


#: seed of every operand, weight and prompt
SEED = 0

#: the served model, at its published widths
ARCH = "smollm-360m"


@dataclasses.dataclass(frozen=True)
class ServeSizes:
    """The serve phase's traffic (the model itself is never cut)."""

    batch: int = 8              # the fixed decode batch
    prompt_len: int = 128       # the fixed batch's prompt length
    new_tokens: int = 32
    requests: int = 8           # continuous-batching requests
    min_prompt: int = 64        # their prompt lengths, drawn from the seed
    max_prompt: int = 256
    prefill_bucket: int = 64


SERVE = ServeSizes()


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def fail(msg: str) -> NoReturn:
    raise SmokeFailure(msg)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        fail(f"shape {got.shape} != expected {want.shape}")
    if not np.all(np.isfinite(got)):
        fail("non-finite values in the result")
    scale = np.linalg.norm(want)
    diff = np.linalg.norm(got - want)
    return float(diff / scale) if scale else float(diff)


class CompileClock:
    """Seconds of backend compilation (or cache retrieval) and cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self, monitoring) -> None:
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.hits
    print(f"[{name}] start", flush=True)
    yield
    print(f"[{name}] pass wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.seconds - c0:.3f} "
          f"cache_hits={clock.hits - h0}", flush=True)


# -- 1. device ----------------------------------------------------------------


def device_check(chips: int):
    """-> the devices; exits non-zero unless they are known TPUs."""
    import jax
    from repro.launch.roofline import peaks

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform}); this "
                 "smoke runs on the chip only")
    try:
        pk = peaks(dev.device_kind)
    except KeyError as e:
        sys.exit(f"chip_smoke: {e.args[0]}")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPUs, "
                 f"JAX found {len(devices)}")
    if jax.config.jax_enable_x64:
        sys.exit("chip_smoke: runs with x64 off (see its docstring); unset "
                 "JAX_ENABLE_X64")
    print(f"[device] peaks bf16={pk.flops_bf16:.4g} FLOP/s "
          f"hbm={pk.hbm_bw:.4g} B/s ({pk.source})", flush=True)
    return devices


# -- 2. offload ----------------------------------------------------------------


def check_job(job, ops, got, want) -> float:
    """Compare one job result with numpy's expected; -> its error."""
    name = job.spec.name.split("[")[0]
    if name == "montecarlo":
        bound = 4.0 * MC_FLIPS / ops["pts"].shape[0]
        err = abs(float(np.asarray(got)) - float(want))
        if not err <= bound:
            fail(f"{job.spec.name}: estimate {got} vs {want}, |err| "
                 f"{err:.3g} > {bound:.3g}")
        return err
    err = rel_err(got, want)
    if err > JOB_TOL[name]:
        fail(f"{job.spec.name}: normwise error {err:.3g} > {JOB_TOL[name]:.3g}")
    return err


def offload_phase(devices) -> None:
    from repro.api import (
        AUTO, Completion, InfoDist, OffloadPolicy, PAPER_JOBS, Residency,
        Session,
    )

    policies = {
        "auto": AUTO,
        "baseline": OffloadPolicy(info_dist=InfoDist.P2P_CHAIN,
                                  completion=Completion.CENTRAL_COUNTER),
    }
    sess = Session(devices=devices)
    try:
        for size, table in OFFLOAD_SIZES.items():
            for name, kw in table.items():
                job = PAPER_JOBS[name](**kw)
                cold, warm = job.make_instance(0), job.make_instance(1)
                for pname, pol in policies.items():
                    times, errs = [], []
                    for ops, want in (cold, warm):
                        t0 = time.perf_counter()
                        got = sess.submit(job, ops, policy=pol).wait()
                        times.append(time.perf_counter() - t0)
                        errs.append(check_job(job, ops, got, want))
                    sess.stage(job, cold[0], policy=pol)
                    t0 = time.perf_counter()
                    got = sess.submit(job, Residency.RESIDENT,
                                      policy=pol).wait()
                    times.append(time.perf_counter() - t0)
                    errs.append(check_job(job, cold[0], got, cold[1]))
                    print(f"[offload] {size:5s} {job.spec.name:26s} "
                          f"{pname:8s} "
                          + " ".join(f"{lb}_ms={t * 1e3:.3f}"
                                     for lb, t in zip(("cold", "warm",
                                                       "resident"), times))
                          + " err=" + ",".join(f"{e:.3g}" for e in errs),
                          flush=True)
    finally:
        sess.close()


# -- 3. kernels ----------------------------------------------------------------


def kernel_cases():
    """-> [(name, pallas fn, reference fn, args, tolerance)] at the widths
    of ``tests/test_tpu_compile.py``, data drawn on the device."""
    import functools

    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.ssm_scan import ssm_scan

    keys = iter(jax.random.split(jax.random.key(SEED), 16))

    def normal(shape, dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    f32, bf16 = jnp.float32, jnp.bfloat16
    ssm_shape = (1, 256, 8192, 16)
    decay = jax.random.uniform(next(keys), ssm_shape, minval=0.7,
                               maxval=0.999)
    qkv = [normal((1, 15, 2048, 64), bf16, 0.5) for _ in range(3)]
    return [
        ("axpy",
         lambda x, y: ops.axpy(x, y, 2.5, impl="pallas", interpret=False),
         lambda x, y: ref.axpy(x, y, 2.5),
         [normal((1 << 20,), f32), normal((1 << 20,), f32)],
         KTOL_F32_ELEMENTWISE),
        ("matmul",
         functools.partial(ops.matmul, impl="pallas", interpret=False),
         ref.matmul, [normal((2048, 2048), bf16), normal((2048, 2048), bf16)],
         KTOL_MATMUL),
        ("atax",
         functools.partial(ops.atax, impl="pallas", interpret=False),
         ref.atax, [normal((1900, 2100), f32), normal((2100,), f32)],
         KTOL_MATMUL),
        ("covariance",
         functools.partial(ops.covariance, impl="pallas", interpret=False),
         ref.covariance, [normal((1200, 1400), f32)], KTOL_MATMUL),
        ("flash_attention",
         functools.partial(ops.attention, causal=True, impl="pallas",
                           interpret=False),
         functools.partial(ref.attention, causal=True), qkv, KTOL_MATMUL),
        ("ssm_scan",
         functools.partial(ssm_scan, interpret=False), ref.ssm_scan,
         [decay, normal(ssm_shape, f32, 0.1), normal(ssm_shape[:2] + (16,),
                                                     f32)],
         KTOL_F32_ELEMENTWISE),
    ]


def kernel_phase() -> None:
    import jax

    for name, fn, ref_fn, args, tol in kernel_cases():
        compiled = jax.jit(fn).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            fail(f"{name}: the compiled program holds no Pallas kernel "
                 "(tpu_custom_call)")
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        err = rel_err(got, want)
        if err > tol:
            fail(f"kernel {name}: normwise error {err:.3g} > {tol:.3g}")
        print(f"[kernels] {name:16s} tpu_custom_call=yes shape="
              f"{tuple(got.shape)} run_ms={dt * 1e3:.3f} err={err:.3g}",
              flush=True)


# -- 4. serve ------------------------------------------------------------------


def serve_phase(device) -> None:
    import jax
    import jax.numpy as jnp
    from repro.api import ServeConfig, ServeEngine
    from repro.launch.mesh import make_mesh
    from repro.models import forward, get, init_params, prefill

    cfg = get(ARCH)
    print(f"[serve] {cfg.name}: {cfg.n_layers}L d{cfg.d_model} "
          f"{cfg.n_heads}H/{cfg.n_kv_heads}KV ff{cfg.d_ff} "
          f"vocab {cfg.vocab_size}", flush=True)
    mesh = make_mesh((1, 1), ("data", "model"), devices=[device])
    # as repro.launch.serve builds it: host params from the seed, placed
    # by the engine
    params = jax.device_get(init_params(jax.random.key(SEED), cfg))
    scfg = ServeConfig(batch=SERVE.batch,
                       max_len=SERVE.max_prompt + SERVE.new_tokens + 1,
                       prefill_bucket=SERVE.prefill_bucket, seed=SEED)
    engine = ServeEngine(cfg, params, mesh, scfg)
    placed = engine.place_params(params)
    chunked = ServeEngine(cfg, placed, mesh,
                          dataclasses.replace(scfg, decode_mode="chunk"))
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, cfg.vocab_size,
                           (SERVE.batch, SERVE.prompt_len)).astype(np.int32)

    # every logit finite: prefill, then one decode step from its cache
    logits, cache = prefill(placed, cfg, {"tokens": jnp.asarray(prompts)},
                            scfg.max_len)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    step_logits, _ = engine.step_fn(placed, cache, tok)
    for label, lg in (("prefill", logits), ("decode", step_logits)):
        if not bool(jnp.all(jnp.isfinite(lg))):
            fail(f"serve: non-finite {label} logits")
    print(f"[serve] logits finite: prefill {tuple(logits.shape)}, decode "
          f"{tuple(step_logits.shape)}", flush=True)

    t0 = time.perf_counter()
    out_step = engine.generate(prompts, SERVE.new_tokens)
    t_step = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_chunk = chunked.generate(prompts, SERVE.new_tokens)
    t_chunk = time.perf_counter() - t0
    if out_step.shape != (SERVE.batch, SERVE.new_tokens):
        fail(f"serve: generate returned {out_step.shape}")
    if not np.array_equal(out_step, out_chunk):
        rows = np.nonzero((out_step != out_chunk).any(axis=1))[0]
        fail(f"serve: step and chunk decode differ in rows {rows.tolist()}")
    print(f"[serve] fixed batch {SERVE.batch}x{SERVE.prompt_len}: step == "
          f"chunk greedy tokens ({SERVE.new_tokens} each); step_s="
          f"{t_step:.3f} chunk_s={t_chunk:.3f} (compile included)",
          flush=True)

    lens = rng.integers(SERVE.min_prompt, SERVE.max_prompt + 1,
                        size=SERVE.requests)
    reqs = [(rng.integers(0, cfg.vocab_size, (int(s),)).astype(np.int32),
             SERVE.new_tokens) for s in lens]
    # staggered arrivals: later requests are inserted mid-stream
    arrivals = [0, 0] + [2 * i for i in range(1, SERVE.requests - 1)]
    t0 = time.perf_counter()
    outs = engine.generate_many(reqs, arrival_steps=arrivals)
    t_many = time.perf_counter() - t0
    inserts = engine.stats["prefill_inserts"]
    # schedule independence, exact: batch rows are computed independently,
    # so a request decoded alone runs the same programs on the same row
    # values and must emit the same tokens as in the shared batch
    for r, (req, got) in enumerate(zip(reqs, outs)):
        alone = engine.generate_many([req])[0]
        if not np.array_equal(got, alone):
            fail(f"serve: request {r} decoded alone {alone.tolist()} != in "
                 f"the shared batch {got.tolist()}")
    print(f"[serve] continuous {SERVE.requests} requests: each identical to "
          "the same request decoded alone", flush=True)
    # the reference: one full forward over prompt + continuous tokens,
    # padded to one shape (causal, so the padding changes no earlier logit)
    forward_logits = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t})[0])
    identical, worst = 0, 0.0
    for r, ((prompt, m), got) in enumerate(zip(reqs, outs)):
        want = engine.generate(prompt[None, :], m)[0]
        n = prompt.size
        seq = np.zeros((1, scfg.max_len), np.int32)
        seq[0, :n], seq[0, n:n + m] = prompt, got
        ref = np.asarray(forward_logits(placed, seq))[0, n - 1:n - 1 + m]
        top, scale = ref.max(axis=-1), np.abs(ref).max(axis=-1)
        gap = (top - ref[np.arange(m), got]) / scale
        if np.any(gap > LOGIT_TIE):
            j = int(np.argmax(gap > LOGIT_TIE))
            fail(f"serve: request {r} token {j} ({got[j]}) sits {gap[j]:.3g}"
                 f" (share of the largest |logit|) below the reference's "
                 f"argmax ({int(ref[j].argmax())}); bound {LOGIT_TIE:.3g}")
        worst = max(worst, float(gap.max()))
        if np.array_equal(got, want):
            identical += 1
            continue
        # the two agree on tokens[:k], so ref[k] scores both choices there
        k = int(np.argmax(got != want))
        static_gap = (top[k] - ref[k, want[k]]) / scale[k]
        if static_gap > LOGIT_TIE:
            fail(f"serve: request {r} static token {k} ({want[k]}) is "
                 f"{static_gap:.3g} below the reference's top logit")
        print(f"[serve] request {r} (prompt {n}): continuous and static "
              f"part at token {k} ({got[k]} vs {want[k]}), a near-tie: "
              f"{gap[k]:.3g} and {static_gap:.3g} below the top logit",
              flush=True)
    print(f"[serve] continuous {SERVE.requests} requests, prompts "
          f"{sorted(int(s) for s in lens)}: {identical} identical to static "
          f"generate, every token within {worst:.3g} <= {LOGIT_TIE:.3g} of "
          f"the reference's top logit; generate_many_s={t_many:.3f} "
          f"(compile included), inserts={inserts}",
          flush=True)

    # continuous vs static, exact: the same weights computing in float32
    # at the highest matmul precision, where the two programs' rounding
    # differences (float32 ulps) sit far below the gap between the top
    # two logits, so their greedy tokens must agree for every request
    exact = ServeEngine(dataclasses.replace(cfg, compute_dtype="float32"),
                        placed, mesh, scfg)
    with jax.default_matmul_precision("highest"):
        outs32 = exact.generate_many(reqs, arrival_steps=arrivals)
        for r, ((prompt, m), got) in enumerate(zip(reqs, outs32)):
            want = exact.generate(prompt[None, :], m)[0]
            if not np.array_equal(got, want):
                fail(f"serve: float32 request {r} (prompt {prompt.size}) "
                     f"continuous {got.tolist()} != static {want.tolist()}")
    print(f"[serve] float32, highest precision: continuous == static "
          f"greedy tokens for all {SERVE.requests} requests", flush=True)


# -- --chips 4 -----------------------------------------------------------------


def multichip_phase(devices) -> None:
    from repro.api import (
        AUTO, Completion, FabricScheduler, GraphNode, InfoDist,
        OffloadPolicy, Ref, Session, Staging,
    )
    from repro.core import jobs

    n = len(devices)
    want_devs = set(devices)
    baseline = OffloadPolicy(info_dist=InfoDist.P2P_CHAIN,
                             completion=Completion.CENTRAL_COUNTER)
    sess = Session(devices=devices)
    try:
        # multicast vs the P2P chain, a broadcast-class and an Amdahl job
        for job, tol in ((jobs.make_atax(1900, 2100), TOL_MATMUL),
                         (jobs.make_axpy(1 << 24), TOL_ELEMENTWISE)):
            ops, want = job.make_instance(0)
            got = {}
            for pname, pol in (("baseline", baseline), ("multicast", AUTO)):
                got[pname] = sess.submit(job, ops, policy=pol, n=n).wait()
                err = rel_err(got[pname], want)
                if err > tol:
                    fail(f"{job.spec.name} {pname}: error {err:.3g} > {tol}")
                # the same plan, launched through the runtime, shows where
                # its output landed
                h = sess.runtime(pol).offload(job, ops, n=n)
                placed = {s.device for s in h.result.addressable_shards}
                if placed != want_devs:
                    fail(f"{job.spec.name} {pname}: output on {placed}, "
                         f"not on all {n} devices")
                h.wait()
            # the two programs differ only in how job info reaches the chips
            between = rel_err(got["baseline"], got["multicast"])
            if between > tol:
                fail(f"{job.spec.name}: baseline and multicast differ "
                     f"({between:.3g})")
            print(f"[multichip] {job.spec.name} n={n}: baseline vs "
                  f"multicast rel_diff={between:.3g} bit_equal="
                  f"{np.array_equal(got['baseline'], got['multicast'])}; "
                  f"output on {n} devices", flush=True)

        # direct vs tree staging of a replicated 32 MiB operand
        job = jobs.make_covariance(256, 32768)
        data, want = job.make_instance(0)
        ops = {"data": data["data"].astype(np.float32)}
        size = ops["data"].nbytes
        sess.submit(job, ops, policy=OffloadPolicy(staging=Staging.DIRECT),
                    n=n).wait()              # compile + stage job args
        res, h2d = {}, {}
        for mode in (Staging.DIRECT, Staging.TREE):
            before = sess.stats.h2d_bytes
            res[mode] = sess.submit(job, ops, n=n,
                                    policy=OffloadPolicy(staging=mode)).wait()
            h2d[mode] = sess.stats.h2d_bytes - before
        if not np.array_equal(res[Staging.DIRECT], res[Staging.TREE]):
            fail("direct and tree staging gave different results")
        err = rel_err(res[Staging.TREE], want)
        if err > TOL_MATMUL:
            fail(f"covariance 32 MiB: error {err:.3g}")
        if h2d[Staging.DIRECT] != n * size or h2d[Staging.TREE] != size:
            fail(f"h2d bytes direct={h2d[Staging.DIRECT]} "
                 f"tree={h2d[Staging.TREE]}, expected {n}x{size} and {size}")
        print(f"[multichip] staging {size} B replicated: direct h2d="
              f"{h2d[Staging.DIRECT]} ({n}x) tree h2d={h2d[Staging.TREE]} "
              f"(1x), results equal, err={err:.3g}", flush=True)
    finally:
        sess.close()

    # a two-node graph across two 2-chip leases: the producer's result is
    # forwarded device to device, never fetched
    sched = FabricScheduler(devices)
    sa, sb = sched.session("producer", n // 2), sched.session("consumer",
                                                               n // 2)
    try:
        if set(sa.devices) & set(sb.devices):
            fail("the two leases share devices")
        job = jobs.make_axpy(1 << 20)
        ops, _ = job.make_instance(0)
        gh = sched.submit_graph([
            GraphNode(job, ops, name="p", session=sa),
            GraphNode(job, {"x": ops["x"], "y": Ref("p")}, name="c",
                      session=sb)])
        out = gh.wait()["c"]
        want = 2.5 * ops["x"] + (2.5 * ops["x"] + ops["y"])
        err = rel_err(out, want)
        if err > TOL_ELEMENTWISE:
            fail(f"cross-lease graph: error {err:.3g}")
        inter = sa.stats.d2h_bytes + sb.stats.d2h_bytes - out.nbytes
        if inter != 0:
            fail(f"cross-lease graph moved {inter} intermediate d2h bytes")
        print(f"[multichip] graph across leases {sa.lease.clusters} -> "
              f"{sb.lease.clusters}: forwarded {gh.forwarded[(0, 1, 'y')]} "
              f"B d2d, intermediate d2h 0 B, err={err:.3g}", flush=True)
    finally:
        sa.close()
        sb.close()


# -- main ------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip phase on a v5e:2x2 host")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: the repro package is not at {SRC}")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    clock = CompileClock()
    clock.install(jax.monitoring)
    t0 = time.perf_counter()
    devices = device_check(args.chips)
    print(f"[cache] {cache_dir}", flush=True)
    if args.chips == 4:
        with phase("multichip", clock):
            multichip_phase(devices[:4])
    else:
        with phase("offload", clock):
            offload_phase(devices[:1])
        with phase("kernels", clock):
            kernel_phase()
        with phase("serve", clock):
            serve_phase(devices[0])
    print(f"[total] wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.seconds:.3f} cache_hits={clock.hits} "
          f"cache_misses={clock.misses}", flush=True)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
