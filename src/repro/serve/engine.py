"""Batched serving engine: prefill + greedy/temperature decode loop.

``build_serve_step`` produces the jitted one-token decode program (the
dry-run's ``serve_step``) with explicit cache shardings; ``ServeEngine``
drives it host-side with batched requests, async dispatch (multiple
outstanding steps — the paper's multiple-outstanding-jobs pattern, §4.3),
and completion tracking through the CompletionUnit.

Decode fast path (the framework's own offload-overhead fix): the seed
engine's loop was a per-token host round-trip — fetch logits, sample on the
host, ``device_put`` the sampled token back.  That is exactly the phase-A/E
per-job tax the paper kills, so the engine now keeps the token resident:

* ``decode_mode="step"`` (default) — sampling (greedy and temperature, with
  the per-step ``fold_in``) runs *inside* the jitted step; the token and the
  PRNG key never leave the device between steps.  Zero host->device
  transfers per decoded token.
* ``decode_mode="chunk"`` — a ``jax.lax.scan`` over ``decode_chunk`` steps
  amortizes dispatch to **one** XLA launch per chunk; the CompletionUnit
  accounts one job per chunk (the paper's job granularity knob).  A
  trailing remainder shorter than the chunk runs through the single-step
  program, so only two programs are ever compiled.
* ``decode_mode="host"`` — the seed's host-round-trip loop, kept as the
  measurable "before" for ``benchmarks/offload_wallclock.py``.

Continuous batching (``generate_many``): the static engine pays one full
fixed-shape batch per ``generate`` call — a half-empty batch decodes at
full-batch cost, and a queued request waits for the whole previous batch
to finish.  ``generate_many`` instead runs a slot scheduler over the fixed
decode batch: variable-length prompts are admitted into free slots as they
arrive (a bucketed prefill of ``prompt[:-1]`` is scattered into the slot's
cache rows; the last prompt token becomes the slot's pending decode
token), every step advances *all* occupied slots through one
``decode_step_ragged`` dispatch (per-slot cache positions, so slots at
different generation depths share the program), finished slots retire via
the done-mask and immediately refill from the queue.  The decode batch
stays full under streaming traffic — the offload-stream idea applied to
serving.

``ServeEngine.stats`` counts per-token host->device transfers and XLA
dispatches so tests and benchmarks can assert the fast-path properties.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import broadcast as bc
from repro.core.completion import CompletionUnit
from repro.core.fabric import (
    ClusterLease, FabricScheduler, LeaseUnavailable, Tenant,
)
from repro.core.policy import Staging, TenantKind, coerce_enum
from repro.dist.sharding import batch_specs, cache_specs, param_specs, to_shardings
from repro.kernels.decode_attention import positions_read
from repro.models.config import ModelConfig
from repro.models.model import (
    CallConfig, decode_step, decode_step_ragged, init_cache, init_params,
    prefill,
)
from repro.tracing import span

Pytree = Any


class _ByteCounter:
    """Duck-typed stats sink for :mod:`repro.core.broadcast` byte counters."""

    def __init__(self):
        self.h2d_bytes = 0
        self.d2d_bytes = 0


def _serve_shardings(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int):
    """-> (param specs, cache specs, token NamedSharding)."""
    cache_shapes = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    cspecs = cache_specs(cache_shapes, mesh)
    key_spec = jax.eval_shape(lambda: jax.random.key(0))
    pshapes = jax.eval_shape(
        lambda k: init_params(k, cfg),
        jax.ShapeDtypeStruct(key_spec.shape, key_spec.dtype))
    pspecs = param_specs(pshapes, mesh)
    tok_sharding = NamedSharding(
        mesh, batch_specs(
            {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}, mesh
        )["tokens"])
    return pspecs, cspecs, tok_sharding


def build_serve_step(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int,
                     call: CallConfig = CallConfig(moe_no_drop=True),
                     shardings=None):
    """-> (jitted decode step, cache shardings).  tokens: (B, 1) -> logits."""
    pspecs, cspecs, tok_sharding = (
        shardings or _serve_shardings(cfg, mesh, batch, max_len))

    def serve_step(params, cache, tokens):
        return decode_step(params, cfg, cache, tokens, call)

    jitted = jax.jit(
        serve_step,
        in_shardings=(
            to_shardings(pspecs, mesh),
            to_shardings(cspecs, mesh),
            tok_sharding,
        ),
        out_shardings=(
            NamedSharding(mesh, P()),
            to_shardings(cspecs, mesh),
        ),
        donate_argnums=(1,),
    )
    return jitted, cspecs, pspecs


def _sampler(temperature: float):
    """(logits (B, V), key) -> (B,) int32, traced inside the jitted step."""
    def sample(logits, key):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)
    return sample


def _decode_sample_body(cfg: ModelConfig, temperature: float,
                        call: CallConfig):
    """The one decode+sample step both resident builders share: decode a
    (B, 1) token, fold the key with the step index, sample the next token.
    Sharing this body is what keeps the single-step and chunk programs on
    the identical key trajectory."""
    sample = _sampler(temperature)

    def body(params, cache, tok, key, i):
        logits, cache = decode_step(params, cfg, cache, tok, call)
        lg = logits[:, 0] if logits.ndim == 3 else logits
        key = jax.random.fold_in(key, i)
        return sample(lg, key), key, cache

    return body


def build_sampling_step(cfg: ModelConfig, mesh: Mesh, batch: int,
                        max_len: int, temperature: float,
                        call: CallConfig = CallConfig(moe_no_drop=True),
                        shardings=None):
    """Device-resident decode+sample: one jitted program per token.

    (params, cache, tok (B,1), key, idx) ->
        (next tok (B,1), key', idx+1, cache').
    The per-step ``fold_in(key, idx)`` happens inside the program; nothing
    crosses the host boundary between steps.
    """
    pspecs, cspecs, tok_sharding = (
        shardings or _serve_shardings(cfg, mesh, batch, max_len))
    body = _decode_sample_body(cfg, temperature, call)
    repl = NamedSharding(mesh, P())

    def serve_sample_step(params, cache, tok, key, idx):
        nxt, key, cache = body(params, cache, tok, key, idx)
        return nxt[:, None], key, idx + 1, cache

    jitted = jax.jit(
        serve_sample_step,
        in_shardings=(
            to_shardings(pspecs, mesh), to_shardings(cspecs, mesh),
            tok_sharding, repl, repl,
        ),
        out_shardings=(tok_sharding, repl, repl, to_shardings(cspecs, mesh)),
        donate_argnums=(1,),
    )
    return jitted, cspecs, tok_sharding


def build_decode_chunk(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int,
                       temperature: float, chunk: int,
                       call: CallConfig = CallConfig(moe_no_drop=True),
                       shardings=None):
    """``lax.scan`` multi-token decode: ``chunk`` tokens per XLA dispatch.

    (params, cache, tok (B,1), key, idx0) ->
        (toks (B, chunk), tok', key', idx0+chunk, cache').
    Step i of the scan folds the key with ``idx0 + i`` — identical key
    trajectory to the single-step program (both run the shared
    ``_decode_sample_body``), so mixing chunked and single-step dispatch
    (e.g. for a remainder) is sampling-equivalent.
    """
    pspecs, cspecs, tok_sharding = (
        shardings or _serve_shardings(cfg, mesh, batch, max_len))
    step_body = _decode_sample_body(cfg, temperature, call)
    repl = NamedSharding(mesh, P())

    def serve_decode_chunk(params, cache, tok, key, idx0):
        def body(carry, i):
            tok, key, cache = carry
            nxt, key, cache = step_body(params, cache, tok, key, i)
            return (nxt[:, None], key, cache), nxt

        (tok, key, cache), toks = jax.lax.scan(
            body, (tok, key, cache), idx0 + jnp.arange(chunk))
        return toks.T, tok, key, idx0 + chunk, cache    # toks: (B, chunk)

    jitted = jax.jit(
        serve_decode_chunk,
        in_shardings=(
            to_shardings(pspecs, mesh), to_shardings(cspecs, mesh),
            tok_sharding, repl, repl,
        ),
        out_shardings=(repl, tok_sharding, repl, repl,
                       to_shardings(cspecs, mesh)),
        donate_argnums=(1,),
    )
    return jitted, cspecs, tok_sharding


def build_ragged_step(cfg: ModelConfig, mesh: Mesh, batch: int, max_len: int,
                      temperature: float,
                      call: CallConfig = CallConfig(moe_no_drop=True),
                      shardings=None):
    """Continuous-batching decode step: per-slot positions + done-mask.

    (params, cache, tok (B,1), pos_b (B,), active (B,), key, idx) ->
        (next tok (B,1), pos_b', key', idx+1, cache').
    Each occupied slot writes/attends its own cache position (see
    ``decode_step_ragged``); free slots (``active == 0``) hold their
    position but decode at position 0, so the kernel reads one chunk of
    their rows and their writes land in cell 0, which the next
    prefill-insert overwrites.
    """
    pspecs, cspecs, tok_sharding = (
        shardings or _serve_shardings(cfg, mesh, batch, max_len))
    sample = _sampler(temperature)
    repl = NamedSharding(mesh, P())

    def serve_decode_step(params, cache, tok, pos_b, active, key, idx):
        logits, cache = decode_step_ragged(
            params, cfg, cache, tok, jnp.where(active > 0, pos_b, 0), call)
        lg = logits[:, 0] if logits.ndim == 3 else logits
        key = jax.random.fold_in(key, idx)
        nxt = sample(lg, key)
        pos_b = pos_b + active.astype(pos_b.dtype)
        return nxt[:, None], pos_b, key, idx + 1, cache

    jitted = jax.jit(
        serve_decode_step,
        in_shardings=(
            to_shardings(pspecs, mesh), to_shardings(cspecs, mesh),
            tok_sharding, repl, repl, repl, repl,
        ),
        out_shardings=(tok_sharding, repl, repl, repl,
                       to_shardings(cspecs, mesh)),
        donate_argnums=(1,),
    )
    return jitted, cspecs, tok_sharding


@dataclasses.dataclass
class ServeConfig:
    batch: int = 8
    max_len: int = 256
    temperature: float = 0.0         # 0 = greedy
    seed: int = 0
    decode_mode: str = "step"        # "step" | "chunk" | "host" (legacy)
    decode_chunk: int = 8            # tokens per dispatch in "chunk" mode
    prefill_bucket: int = 16         # generate_many pads prefills to this
                                     # granularity (bounds compile count)
    staging: Staging = Staging.DIRECT  # replicated-placement strategy for
                                     # weight placement and prefill inserts:
                                     # DIRECT | TREE | TREE_RESHARD
                                     # (repro.core.policy.Staging; the
                                     # serialized host_fanout baseline is an
                                     # offload-runtime measurement device,
                                     # not a serving mode).  Raw strings are
                                     # accepted with a DeprecationWarning.

    def __post_init__(self):
        self.staging = coerce_enum(Staging, self.staging, "staging",
                                   warn_legacy=True)
        if self.staging is Staging.HOST_FANOUT:
            valid = tuple(m.value for m in Staging if m is not Staging.HOST_FANOUT)
            raise ValueError(f"staging {self.staging.value!r} not in {valid}")


class ServeEngine:
    """Static-batch decode engine with per-slot generation state.

    ``params`` may be device-resident (already placed on the mesh) or a
    host pytree; in the latter case call :meth:`place_params` before
    generating — it places the weights under ``scfg.staging`` (the tree
    modes send every replicated leaf over the host link once) and
    records the link bytes in ``stats``.  Skipping it still works (jit
    re-places host params per dispatch) but bypasses the configured
    staging strategy and its byte accounting.
    """

    def __init__(self, cfg: ModelConfig, params: Pytree, mesh: Mesh,
                 scfg: ServeConfig, call: CallConfig = CallConfig(moe_no_drop=True),
                 cluster_ids: Optional[Sequence[int]] = None):
        self.cfg, self.scfg, self.call = cfg, scfg, call
        self.mesh = mesh
        self.params = params
        # the engine's fabric window (global cluster ids, one per mesh
        # device): a lease-holding engine derives its weight-placement
        # fan-out tree from the real placement, so cross-quadrant edges
        # are what the lease actually pays
        self.cluster_ids = (None if cluster_ids is None
                            else tuple(int(c) for c in cluster_ids))
        # one _serve_shardings resolution shared by every program builder
        self._shardings = _serve_shardings(cfg, mesh, scfg.batch, scfg.max_len)
        self._tok_sharding = self._shardings[2]
        self.step_fn, self.cspecs, _ = build_serve_step(
            cfg, mesh, scfg.batch, scfg.max_len, call,
            shardings=self._shardings)
        self.unit = CompletionUnit(n_units=8)
        self._jobid = 0
        self._sampled_step = None      # built lazily per decode mode
        self._chunk_fn = None
        self._first_fn = None
        self._ragged_step = None       # continuous-batching programs
        self._insert_fn = None
        self._prefill_fn = None
        self._stager: Optional[bc.TreeStager] = None   # hierarchical staging
        self.stats = {"h2d_token_puts": 0, "xla_dispatches": 0,
                      "tokens_emitted": 0, "prefill_inserts": 0,
                      "requests_retired": 0, "batch_padded_rows": 0,
                      "h2d_bytes": 0, "d2d_bytes": 0}

    # -- program cache -----------------------------------------------------------

    def _get_sampled_step(self):
        if self._sampled_step is None:
            self._sampled_step, _, _ = build_sampling_step(
                self.cfg, self.mesh, self.scfg.batch, self.scfg.max_len,
                self.scfg.temperature, self.call, shardings=self._shardings)
        return self._sampled_step

    def _get_chunk_fn(self):
        if self._chunk_fn is None:
            self._chunk_fn, _, _ = build_decode_chunk(
                self.cfg, self.mesh, self.scfg.batch, self.scfg.max_len,
                self.scfg.temperature, self.scfg.decode_chunk, self.call,
                shardings=self._shardings)
        return self._chunk_fn

    def _get_ragged_step(self):
        if self._ragged_step is None:
            self._ragged_step, _, _ = build_ragged_step(
                self.cfg, self.mesh, self.scfg.batch, self.scfg.max_len,
                self.scfg.temperature, self.call, shardings=self._shardings)
        return self._ragged_step

    def _get_insert_fn(self):
        if self._insert_fn is None:
            cshard = to_shardings(self.cspecs, self.mesh)

            def serve_insert(cache, rows, slot):
                # every (L, 1, ..., S) leaf of a prefill's cache into the
                # slot's row: K and V, or MLA's latent cache
                return dict(cache, **{
                    name: jax.lax.dynamic_update_slice(
                        cache[name], r.astype(cache[name].dtype),
                        (0, slot) + (0,) * (r.ndim - 2))
                    for name, r in rows.items()})

            self._insert_fn = jax.jit(serve_insert, out_shardings=cshard,
                                      donate_argnums=(0,))
        return self._insert_fn

    def _get_prefill_fn(self):
        # jit caches one program per prefill bucket length
        if self._prefill_fn is None:
            def serve_prefill(p, toks):
                return prefill(p, self.cfg, {"tokens": toks},
                               self.scfg.max_len, self.call)

            self._prefill_fn = jax.jit(serve_prefill)
        return self._prefill_fn

    # -- hierarchical staging (weight placement + prefill inserts) ----------------

    def _get_stager(self) -> bc.TreeStager:
        if self._stager is None:
            self._stager = bc.TreeStager(list(self.mesh.devices.flat),
                                         cluster_ids=self.cluster_ids)
        return self._stager

    def _put_replicated(self, arr: np.ndarray):
        """Replicated placement under ``scfg.staging``, link bytes counted."""
        sharding = NamedSharding(self.mesh, P())
        if self.scfg.staging in bc.TREE_MODES:
            counted = _ByteCounter()
            out = self._get_stager().put_replicated(
                arr, sharding, reshard=self.scfg.staging == "tree_reshard",
                stats=counted)
            self.stats["h2d_bytes"] += counted.h2d_bytes
            self.stats["d2d_bytes"] += counted.d2d_bytes
            return out
        self.stats["h2d_bytes"] += bc.placement_bytes(arr, sharding)
        return jax.device_put(arr, sharding)

    def place_params(self, host_params: Pytree) -> Pytree:
        """Place host-side parameters onto the mesh and adopt them.

        Under ``staging="tree"`` every fully replicated leaf (1-D scales,
        biases, anything the sharding rules could not split) crosses the
        host link once and fans out device-to-device along the broadcast
        tree; sharded leaves take the direct path.  ``stats["h2d_bytes"]``
        / ``stats["d2d_bytes"]`` record the logical link traffic either
        way, so tests can assert the O(n) -> O(1) weight-placement claim.
        """
        shardings = to_shardings(self._shardings[0], self.mesh)
        counted = _ByteCounter()
        if self.scfg.staging in bc.TREE_MODES:
            placed = bc.place_pytree(
                host_params, shardings, self._get_stager(),
                reshard=self.scfg.staging == "tree_reshard", stats=counted)
        else:
            def put(leaf, sh):
                counted.h2d_bytes += bc.placement_bytes(np.asarray(leaf), sh)
                return jax.device_put(leaf, sh)
            placed = jax.tree_util.tree_map(put, host_params, shardings)
        self.stats["h2d_bytes"] += counted.h2d_bytes
        self.stats["d2d_bytes"] += counted.d2d_bytes
        self.params = placed
        return placed

    # -- generation ---------------------------------------------------------------

    def generate(self, prompts: np.ndarray, n_new: int,
                 extra_inputs: Optional[Dict[str, np.ndarray]] = None
                 ) -> np.ndarray:
        """prompts: (b, S_prompt) int32 -> (b, n_new) generated ids.

        ``b`` may be any size up to the configured batch: a sub-batch is
        padded to ``scfg.batch`` (repeating the last prompt row, so every
        padded row is a valid token sequence) and the output sliced back —
        the fixed-shape programs never see a new batch size, so no
        recompile.  Batch rows are computed independently, so padding does
        not change the real rows' tokens.
        """
        b = prompts.shape[0]
        if b > self.scfg.batch:
            raise ValueError(
                f"batch {b} exceeds configured batch {self.scfg.batch}")
        if b < self.scfg.batch:
            pad = self.scfg.batch - b
            self.stats["batch_padded_rows"] += pad
            prompts = np.concatenate(
                [prompts, np.broadcast_to(
                    prompts[-1:], (pad,) + prompts.shape[1:])], axis=0)
            if extra_inputs:
                extra_inputs = {
                    k: np.concatenate(
                        [v, np.broadcast_to(
                            np.asarray(v)[-1:], (pad,) + np.asarray(v).shape[1:])],
                        axis=0)
                    for k, v in extra_inputs.items()}
        batch = {"tokens": jnp.asarray(prompts)}
        if extra_inputs:
            batch.update({k: jnp.asarray(v) for k, v in extra_inputs.items()})
        logits, cache = prefill(
            self.params, self.cfg, batch, self.scfg.max_len, self.call)
        # prefill leaves cache layout to XLA; reshard once to the decode
        # step's cache sharding (phase-E staging, in offload terms)
        cache = jax.device_put(cache, to_shardings(self.cspecs, self.mesh))
        key = jax.random.key(self.scfg.seed)
        mode = self.scfg.decode_mode
        if mode not in ("host", "step", "chunk"):
            raise ValueError(f"decode_mode {mode!r} not in host/step/chunk")
        if mode == "host":
            out = self._generate_host_loop(logits, cache, key, n_new)
        else:
            out = self._generate_resident(logits, cache, key, n_new)
        return out[:b]

    def _generate_resident(self, logits, cache, key, n_new: int) -> np.ndarray:
        """Device-resident decode: the token never visits the host."""
        if self._first_fn is None:
            sample = _sampler(self.scfg.temperature)

            def serve_first_token(lg, k):
                return sample(lg, k)[:, None]

            self._first_fn = jax.jit(serve_first_token,
                                     out_shardings=self._tok_sharding)
        tok = self._first_fn(logits[:, -1], key)
        # the prefill-token sample is a real XLA launch emitting token 0
        # (host mode samples it eagerly inside its first loop iteration)
        self.stats["xla_dispatches"] += 1
        self.stats["tokens_emitted"] += 1
        idx = jnp.int32(0)         # fold index, carried on device thereafter
        toks = [tok]
        steps = n_new - 1
        done = 0
        use_chunk = (self.scfg.decode_mode == "chunk"
                     and self.scfg.decode_chunk > 1)
        if use_chunk:
            chunk_fn = self._get_chunk_fn()
            c = self.scfg.decode_chunk
            while steps - done >= c:
                job = self._dispatch_begin()
                ys, tok, key, idx, cache = chunk_fn(
                    self.params, cache, tok, key, idx)
                self._dispatch_end(job, tokens=c)
                toks.append(ys)
                done += c
        if done < steps:
            step_fn = self._get_sampled_step()
            while done < steps:
                job = self._dispatch_begin()
                tok, key, idx, cache = step_fn(
                    self.params, cache, tok, key, idx)
                self._dispatch_end(job, tokens=1)
                toks.append(tok)
                done += 1
        out = np.concatenate([np.asarray(t) for t in toks], axis=1)
        assert out.shape[1] == n_new, (out.shape, n_new)
        return out

    def _generate_host_loop(self, logits, cache, key, n_new: int) -> np.ndarray:
        """The seed path: host-side sampling + per-step token device_put."""
        sample = _sampler(self.scfg.temperature)
        out = []
        tok = sample(logits[:, -1], key)
        for i in range(n_new):
            out.append(tok)
            job = self._dispatch_begin()
            tok_dev = jax.device_put(tok[:, None], self._tok_sharding)
            self.stats["h2d_token_puts"] += 1
            logits, cache = self.step_fn(self.params, cache, tok_dev)
            key = jax.random.fold_in(key, i)
            tok = sample(logits[:, 0] if logits.ndim == 3 else logits, key)
            self._dispatch_end(job, tokens=1)
        return np.stack([np.asarray(t) for t in out], axis=1)

    # -- continuous batching -------------------------------------------------------

    def generate_many(self, requests: Sequence[Tuple[np.ndarray, int]],
                      arrival_steps: Optional[Sequence[int]] = None
                      ) -> List[np.ndarray]:
        """Continuous batching over ``requests`` = [(prompt, n_new), ...].

        Prompts are variable-length 1-D int32 arrays.  Requests are
        admitted into free slots of the fixed decode batch in arrival
        order; each decode step advances every occupied slot through one
        ``decode_step_ragged`` dispatch; a slot that has emitted its
        ``n_new`` tokens retires and refills from the queue.  Returns the
        (n_new_r,) generated ids per request, in request order.

        ``arrival_steps`` (optional, same length) gives each request the
        earliest decode step at which it may be admitted — an arrival
        trace for throughput benchmarks; steps where the batch is entirely
        idle are skipped, not decoded.

        Greedy outputs are schedule-independent: batch rows are computed
        independently, so a request's tokens do not depend on which other
        requests it shares the batch with (temperature sampling shares one
        key trajectory across the batch and is reproducible per schedule,
        not per request).
        """
        if self.cfg.family in ("ssm", "hybrid") or self.cfg.frontend:
            raise NotImplementedError(
                "continuous batching requires an attention family, GQA or "
                "MLA (ragged per-slot cache positions; modality-prefix "
                "frontends would shift every slot's positions)")
        scfg = self.scfg
        reqs = [(np.asarray(p, np.int32).ravel(), int(m))
                for p, m in requests]
        R = len(reqs)
        arrivals = ([0] * R if arrival_steps is None
                    else [int(a) for a in arrival_steps])
        if len(arrivals) != R:
            raise ValueError(
                f"{len(arrivals)} arrival steps for {R} requests")
        for prompt, m in reqs:
            if prompt.size < 1:
                raise ValueError("empty prompt")
            if m < 1:
                raise ValueError(f"n_new must be >= 1, got {m}")
            if prompt.size - 1 + m > scfg.max_len:
                raise ValueError(
                    f"prompt ({prompt.size}) + n_new ({m}) exceeds "
                    f"max_len {scfg.max_len}")

        step_fn = self._get_ragged_step()
        B = scfg.batch
        cache = jax.device_put(init_cache(self.cfg, B, scfg.max_len),
                               to_shardings(self.cspecs, self.mesh))
        tok = jax.device_put(jnp.zeros((B, 1), jnp.int32),
                             self._tok_sharding)
        pos_b = jnp.zeros((B,), jnp.int32)
        active = jnp.zeros((B,), jnp.int32)
        key = jax.random.key(scfg.seed)
        idx = jnp.zeros((), jnp.int32)

        slots: List[Optional[Dict[str, int]]] = [None] * B
        free = list(range(B))
        order = sorted(range(R), key=lambda r: (arrivals[r], r))
        queue: collections.deque = collections.deque()
        step_log: List[Tuple[Any, List[Tuple[int, int]]]] = []
        t = 0
        pi = 0
        while pi < R or queue or any(s is not None for s in slots):
            while pi < R and arrivals[order[pi]] <= t:
                queue.append(order[pi])
                pi += 1
            # prefill-insert: refill free slots from the queue
            while queue and free:
                r = queue.popleft()
                j = free.pop(0)
                with span("serve.admit", slot=j):
                    cache, tok, pos_b, active = self._insert(
                        cache, tok, pos_b, active, j, reqs[r][0])
                slots[j] = {"req": r, "remaining": reqs[r][1],
                            "pos": reqs[r][0].size - 1}
            if all(s is None for s in slots):
                t = arrivals[order[pi]]     # batch idle: skip to next arrival
                continue
            # one resident decode step advances every occupied slot; each
            # attends its position + 1 cached positions, and the kernel
            # copies them in whole chunks (the host knows the positions, so
            # the span's counts cost no sync)
            live = [(j, s["req"]) for j, s in enumerate(slots)
                    if s is not None]
            at = [slots[j]["pos"] for j, _ in live]
            job = self._dispatch_begin()
            with span("serve.decode", kv_positions=sum(at) + len(at),
                      kv_read_positions=positions_read(at, scfg.max_len)):
                tok, pos_b, key, idx, cache = step_fn(
                    self.params, cache, tok, pos_b, active, key, idx)
            self._dispatch_end(job, tokens=len(live))
            step_log.append((tok, live))
            with span("serve.retire"):
                for j, s in enumerate(slots):
                    if s is None:
                        continue
                    s["remaining"] -= 1
                    s["pos"] += 1
                    if s["remaining"] == 0:     # done-mask: retire the slot
                        slots[j] = None
                        free.append(j)
                        free.sort()
                        active = active.at[j].set(0)
                        self.stats["requests_retired"] += 1
            t += 1

        # tokens stayed device-resident throughout; one drain at the end
        with span("serve.drain"):
            fetched = jax.device_get([tk for tk, _ in step_log])
        results: List[List[int]] = [[] for _ in range(R)]
        for tk_host, (_, live) in zip(fetched, step_log):
            for j, r in live:
                results[r].append(tk_host[j, 0])
        return [np.asarray(seq, np.int32) for seq in results]

    def _insert(self, cache, tok, pos_b, active, slot: int,
                prompt: np.ndarray):
        """Admit ``prompt`` into ``slot``: bucketed prefill of
        ``prompt[:-1]`` scattered into the slot's cache rows; the last
        prompt token becomes the slot's pending decode token at position
        ``len(prompt) - 1``."""
        s = int(prompt.size)
        if s > 1:
            bucket = max(1, self.scfg.prefill_bucket)
            # bucketed up, but never past the cache length
            sb = min(-(-(s - 1) // bucket) * bucket, self.scfg.max_len)
            padded = np.zeros((1, sb), np.int32)
            padded[0, :s - 1] = prompt[:-1]
            # the bucketed prompt is replicated input to the prefill
            # program; tree staging sends it over the host link once
            _, pcache = self._get_prefill_fn()(self.params,
                                               self._put_replicated(padded))
            rows = {name: a for name, a in pcache.items() if name != "pos"}
            cache = self._get_insert_fn()(cache, rows, np.int32(slot))
        tok = tok.at[slot, 0].set(int(prompt[-1]))
        self.stats["h2d_token_puts"] += 1   # the pending prompt token
        pos_b = pos_b.at[slot].set(s - 1)
        active = active.at[slot].set(1)
        self.stats["prefill_inserts"] += 1
        return cache, tok, pos_b, active

    # -- completion accounting (one offloaded job per dispatch) -------------------

    def _dispatch_begin(self) -> int:
        job = self._jobid
        self._jobid += 1
        self.unit.program(1, job)
        return job

    def _dispatch_end(self, job: int, tokens: int) -> None:
        self.unit.arrive(job, 1)   # the step's fused arrival reduction
        self.unit.collect(job)
        self.stats["xla_dispatches"] += 1
        self.stats["tokens_emitted"] += tokens


class ServeTenant:
    """A lease-holding serve tenant: elastic grow/shrink between bursts.

    The pre-scheduler engine owned its mesh for the process lifetime —
    idle decode capacity was dead capacity.  A ``ServeTenant`` instead
    holds a *floor* lease on the :class:`~repro.core.fabric.
    FabricScheduler` and, per decode burst (one ``generate`` /
    ``generate_many`` call), grows toward its preferred ``burst`` size
    using whatever clusters are free, shrinking back to the floor when
    the burst completes — bursty offload tenants get the head-room
    between bursts, exactly the serve/offload fabric split of the PR-5
    scheduler.

    One :class:`ServeEngine` is kept per distinct lease window (the
    scheduler's in-place resizing makes the windows recur), so weight
    placement and compiled programs are warm across burst cycles at the
    cost of one engine per window actually seen.
    """

    def __init__(self, scheduler: FabricScheduler, cfg: ModelConfig,
                 host_params: Pytree, scfg: ServeConfig, *,
                 tenant: str = "serve",
                 floor: int = 1,
                 burst: Optional[int] = None,
                 call: CallConfig = CallConfig(moe_no_drop=True)):
        if floor < 1:
            raise ValueError(f"floor must be >= 1, got {floor}")
        self.scheduler = scheduler
        self.cfg, self.scfg, self.call = cfg, scfg, call
        self.host_params = host_params
        self.floor = floor
        self.burst = scheduler.num_clusters if burst is None else burst
        if self.burst < floor:
            raise ValueError(
                f"burst size {self.burst} below the floor {floor}")
        self.lease: ClusterLease = scheduler.request(
            Tenant(tenant, kind=TenantKind.SERVE), n=floor)
        # the scheduler's overload ladder shrinks elastic serve leases
        # (back to, then below, this floor) before revoking anything
        scheduler.register_elastic(self.lease, floor)
        self._engines: Dict[Tuple[int, ...], ServeEngine] = {}

    def _engine(self) -> ServeEngine:
        key = self.lease.clusters
        eng = self._engines.get(key)
        if eng is None:
            devs = self.lease.devices
            mesh = Mesh(np.asarray(devs).reshape(len(devs), 1),
                        ("data", "model"))
            eng = ServeEngine(self.cfg, self.host_params, mesh, self.scfg,
                              self.call, cluster_ids=key)
            eng.place_params(self.host_params)
            self._engines[key] = eng
        return eng

    def _sync(self) -> None:
        # a fabric failover (FabricScheduler.fail_clusters) replaces the
        # lease object in place — same id, healthy window — leaving this
        # tenant's reference stale; refresh it before keying any
        # scheduler call (or engine cache) on the window
        cur = self.scheduler.current_lease(self.lease)
        if cur is not None and cur is not self.lease:
            self.lease = cur
        # overload pressure may have shrunk the floor itself (graceful
        # degradation); adopt the scheduler's view so _grow/_shrink
        # target the degraded floor instead of fighting the ladder
        floor = self.scheduler.elastic_floor(self.lease)
        if floor is not None and floor != self.floor:
            self.floor = floor

    def _grow(self) -> None:
        self._sync()
        # the global free count is an upper bound; the free space may be
        # fragmented into windows smaller than it, so walk the target
        # down until a contiguous grow (or relocation) fits — a burst
        # takes the largest window available, never fails the generate
        headroom = len(self.scheduler.free_clusters())
        target = max(self.floor, min(self.burst, self.lease.n + headroom))
        while target > self.lease.n:
            try:
                self.lease = self.scheduler.resize(self.lease, target)
                return
            except LeaseUnavailable:
                target -= 1

    def _shrink(self) -> None:
        self._sync()
        if self.lease.n > self.floor:
            self.lease = self.scheduler.resize(self.lease, self.floor)
        elif self.lease.n < self.floor:
            # a failover or the overload ladder left the lease under the
            # floor; growing back is best-effort while pressure persists
            try:
                self.lease = self.scheduler.resize(self.lease, self.floor)
            except LeaseUnavailable:
                pass

    def generate(self, prompts: np.ndarray, n_new: int,
                 extra_inputs: Optional[Dict[str, np.ndarray]] = None
                 ) -> np.ndarray:
        """One decode burst: grow the lease, generate, shrink back."""
        self._grow()
        try:
            return self._engine().generate(prompts, n_new, extra_inputs)
        finally:
            self._shrink()

    def generate_many(self, requests: Sequence[Tuple[np.ndarray, int]],
                      arrival_steps: Optional[Sequence[int]] = None
                      ) -> List[np.ndarray]:
        """One continuous-batching burst under the elastic lease."""
        self._grow()
        try:
            return self._engine().generate_many(requests, arrival_steps)
        finally:
            self._shrink()

    @property
    def windows(self) -> Tuple[Tuple[int, ...], ...]:
        """Every lease window this tenant has served a burst on (each
        backs one warm engine), smallest first."""
        return tuple(sorted(self._engines, key=len))

    @property
    def peak_burst(self) -> int:
        """The widest burst window served so far (clusters)."""
        return max((len(w) for w in self._engines), default=self.lease.n)

    @property
    def stats(self) -> Dict[str, int]:
        """Engine counters summed across every lease window served."""
        agg: Dict[str, int] = {}
        for eng in self._engines.values():
            for k, v in eng.stats.items():
                agg[k] = agg.get(k, 0) + v
        return agg

    def close(self) -> None:
        """Release the floor lease (the tenant leaves the fabric)."""
        self._sync()
        self.scheduler.unregister_elastic(self.lease)
        if self.lease.active:
            self.lease.release()
