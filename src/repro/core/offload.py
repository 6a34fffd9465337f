"""The offload runtime — the paper's host-centric execution model on a JAX
device mesh.

``OffloadRuntime`` is the dispatch layer of this framework: it carries a job
(the paper's six kernels, or a training/serving step) onto a set of
accelerator "clusters" (devices of a 1-D mesh), reproducing the paper's two
implementations:

* ``baseline``  — job information is materialized on cluster 0 only and
  distributed hop-by-hop through a chain of ``collective-permute``s (the
  sequential P2P writes of §4.1, phases C/D), and completion is synchronized
  through the central-counter chain (§5.5 H).  The lowered HLO contains an
  O(n)-deep chain of collectives — the paper's O(n) offload critical path,
  structurally visible in ``compiled.as_text()``.
* ``multicast`` — job information is replicated (a single logical broadcast,
  XLA lowers it to an O(log n) tree), phases C/D vanish, and completion is a
  single fused ``psum`` (the job completion unit).  This is the paper's
  co-designed fast path and the default for every training/serving step in
  this framework.

Cluster selection uses the paper's address-mask multicast encoding (§4.2):
``select=MulticastRequest(...)`` picks any power-of-two subcube of clusters,
exactly like fig. 5; arbitrary sets fall back to a minimal multi-request
cover.  The selected clusters become a sub-mesh.

Completion is tracked host-side by the :class:`~repro.core.completion.
CompletionUnit` (fig. 6 semantics, multiple outstanding jobs by job ID), fed
by the device-side arrivals count that every offloaded program returns.

Dispatch fast path
------------------

The paper's thesis applies to this framework's *own* host-side critical
path: re-resolving the sub-mesh, re-deriving shardings, and re-``device_put``
-ing identical operands on every ``offload()`` is exactly the per-job
overhead §4 sets out to kill.  The runtime therefore caches a
:class:`DispatchPlan` per (job, cluster selection, operand shapes/dtypes):

* **plan reuse** — the resolved sub-mesh, the ``NamedSharding`` for every
  operand and for the job args, and the compiled program are computed once
  and reused; a warm dispatch performs zero sharding/compile work.
* **resident operands** — ``offload(job, "resident", ...)`` reuses the
  operand buffers staged by the previous dispatch (or by an explicit
  ``plan.stage(operands)``), skipping phase-E ``device_put`` entirely.
  ``plan.invalidate()`` drops residency explicitly; staging fresh operands
  through a normal ``offload(job, {...})`` call refreshes it implicitly.
* **job-args cache** — job args are tiny but re-uploaded on every seed-style
  dispatch; the plan keeps the last staged value and skips the upload when
  the host value is unchanged (exact ``array_equal`` check).
* **buffer donation** — ``OffloadConfig.donate_operands=True`` donates the
  operand buffers to XLA (phase-E buffers can back phase-G outputs).  A
  donated dispatch consumes the resident buffers; the plan keeps the host
  references and transparently re-stages on the next dispatch, so donation
  never corrupts reuse (it only trades residency for memory).
* **one-fetch completion** — ``JobHandle.wait()`` fetches result and
  arrivals in a single ``device_get`` and drains completion-unit causes
  out of order, so outstanding handles (up to the runtime's ``n_units``
  completion-unit copies, §4.3) can be waited on in any order.

Fused dispatch batching
-----------------------

The fast path shrinks the per-job overhead; it cannot remove the floor of
one host dispatch per job.  ``offload_fused(job, [ops_0, ..., ops_B-1])``
removes it by fusing B independent instances of the same job into **one**
XLA launch: operands and job args gain a leading batch axis, the kernel is
``vmap``-ed over it inside the sharded program, and any cross-cluster
reduction happens once on the batched array — so the HLO collective count
is independent of B while the fixed host dispatch cost is amortized to
~1/B per job.  This is the software analogue of the paper's O(1) multicast
wakeup (one doorbell wakes n clusters; here one dispatch launches B jobs),
applied to this framework's own host critical path.  Fused plans support
the same residency/donation semantics as single-job plans, and
``lowered_text(job, n, fuse=B)`` exposes the batched program's HLO for the
B-independence assertions.

Streaming
---------

:class:`repro.core.stream.OffloadStream` builds on two hooks here: slot
staging (``DispatchPlan.stage(operands, slot=k)`` uploads into a numbered
buffer slot without touching residency, so job k+1's phase-E transfer can
proceed while job k computes out of the other slot) and ``_launch`` (the
dispatch tail shared by ``offload``/``offload_fused``/the stream).

Hierarchical broadcast staging
------------------------------

Replicated operands used to be the last O(n) segment of the dispatch path:
``device_put`` against a replicated sharding moves the array over the host
link once *per cluster*.  ``DispatchPlan.stage(..., via="tree")`` instead
derives a quadrant-aware fan-out tree from the cluster selection
(:mod:`repro.core.broadcast`) and stages the operand with **one** host
upload to the tree root plus device-to-device copies along the tree — the
paper's multicast algebra lowered to a phase-E data path.  ``via=
"host_fanout"`` keeps the explicit O(n) sequential-upload baseline
measurable, and ``OffloadConfig.staging`` sets the per-runtime default.
``stats.h2d_bytes`` / ``stats.d2d_bytes`` account the logical link bytes so
the O(n) -> O(1) host-link claim is asserted by tests, not just timed; the
staging-cost model in :mod:`repro.core.simulator` (``staging_model`` /
``model_error``) closes the loop against the paper's §6 analytical
treatment.

``DispatchPlan.stats`` / ``OffloadRuntime.stats`` count device_puts, plan
hits/misses, resident hits, and staging bytes — the hooks the fast-path
tests and ``benchmarks/offload_wallclock.py`` assert against.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import sanitizer as _san
from repro.compat import shard_map
from repro.core import broadcast as bc
from repro.core import multicast as mc
from repro.core.completion import (
    CompletionUnit,
    central_counter_arrivals,
    completion_unit_arrivals,
)
from repro.core.faults import CompletionTimeout, FaultInjector
from repro.core.jobs import PaperJob, stack_instances
from repro.core.policy import (
    Completion, InfoDist, Residency, Staging, coerce_enum, warn_legacy,
)
from repro.tracing import span

AXIS = "clusters"

#: legacy sentinel accepted by ``offload(job, "resident", ...)`` — the
#: typed spelling is ``repro.core.policy.Residency.RESIDENT``
RESIDENT = "resident"


def _is_resident(operands: Any, legacy_surface: str) -> bool:
    """True when ``operands`` selects resident redispatch.

    Accepts the typed :class:`Residency` enum silently and the legacy
    ``"resident"`` string with a :class:`DeprecationWarning`; any other
    string (or ``Residency.FRESH``, which names no buffers) is an error.
    """
    if isinstance(operands, Residency):
        if operands is not Residency.RESIDENT:
            raise ValueError(
                f"{operands!r} is not a dispatchable operand mode; pass "
                "an operand dict or Residency.RESIDENT")
        return True
    if isinstance(operands, str):
        if operands != RESIDENT:
            raise ValueError(f"unknown operands mode {operands!r}")
        warn_legacy(f"{legacy_surface}(job, 'resident')",
                    f"{legacy_surface}(job, Residency.RESIDENT)")
        return True
    return False


#: valid phase-E staging strategies for replicated operands (see
#: ``DispatchPlan.stage``; the canonical set lives in ``repro.core.
#: broadcast``):
#:   "direct"       one ``device_put`` against the replicated sharding — the
#:                  substrate's native path (O(n) logical host-link bytes)
#:   "host_fanout"  explicit sequential per-device uploads, one outstanding
#:                  transfer at a time — the measurable O(n) host-link
#:                  baseline, mirroring the paper's serialized P2P writes
#:                  (CVA6's limited outstanding-write budget, §4.2)
#:   "tree"         hierarchical broadcast staging: ONE host upload to the
#:                  fan-out tree root, then device-to-device copies along
#:                  the quadrant-aware tree (``repro.core.broadcast``) —
#:                  O(1) host-link bytes
#:   "tree_reshard" tree semantics through the replicated-resharding fast
#:                  path (root upload + one resharding ``device_put``)
STAGING_MODES = bc.STAGING_MODES


@dataclasses.dataclass(frozen=True)
class OffloadConfig:
    """First-class framework feature: how jobs are dispatched (§4.2/§4.3).

    Every mode field is validated on construction (a typo like
    ``info_dist="mulicast"`` raises instead of silently misconfiguring
    the run) and coerced to its :mod:`repro.core.policy` enum; raw
    strings still work but raise :class:`DeprecationWarning` — the typed
    session surface (``repro.api.OffloadPolicy``) is the replacement.
    """

    info_dist: InfoDist = InfoDist.MULTICAST
    completion: Completion = Completion.UNIT
    donate_operands: bool = False
    staging: Staging = Staging.DIRECT  # default phase-E mode, see STAGING_MODES

    def __post_init__(self):
        coerce = object.__setattr__
        coerce(self, "info_dist",
               coerce_enum(InfoDist, self.info_dist, "info_dist",
                           warn_legacy=True))
        coerce(self, "completion",
               coerce_enum(Completion, self.completion, "completion",
                           warn_legacy=True))
        coerce(self, "staging",
               coerce_enum(Staging, self.staging, "staging",
                           warn_legacy=True))

    @staticmethod
    def baseline() -> "OffloadConfig":
        return OffloadConfig(info_dist=InfoDist.P2P_CHAIN,
                             completion=Completion.CENTRAL_COUNTER)

    @staticmethod
    def extended() -> "OffloadConfig":
        return OffloadConfig(info_dist=InfoDist.MULTICAST,
                             completion=Completion.UNIT)


@dataclasses.dataclass
class PlanStats:
    """Host-side dispatch-overhead counters (per plan / per runtime)."""

    device_puts: int = 0          # operand/arg buffers uploaded
    resident_hits: int = 0        # operands reused without any upload
    args_hits: int = 0            # job-args upload skipped (unchanged value)
    dispatches: int = 0           # XLA launches through this plan
    donation_restages: int = 0    # re-uploads forced by a donated dispatch
    fused_jobs: int = 0           # logical jobs carried by fused dispatches
    h2d_bytes: int = 0            # logical host-link bytes (see broadcast.py)
    d2d_bytes: int = 0            # logical device-to-device fan-out bytes
    tree_stages: int = 0          # operand/arg stagings routed via the tree
    d2h_bytes: int = 0            # result payload fetched to host by wait()
    forwards: int = 0             # operands forwarded from producer results
    forward_bytes: int = 0        # logical d2d bytes of those forwards
    renames: int = 0              # rename copies breaking WAR/WAW hazards

    def accumulate(self, other: "PlanStats") -> "PlanStats":
        """Add ``other``'s counters into this instance (returns self) —
        the one aggregation used by every stats rollup surface."""
        for f in dataclasses.fields(PlanStats):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))
        return self


class DonatedOperandError(RuntimeError):
    """A device buffer was reused after a donating dispatch consumed it.

    Donation (``OffloadConfig.donate_operands``) hands the operand
    buffers to XLA, which deletes them on launch.  Reusing one —
    re-staging it, forwarding it to a dependent job, or fetching a
    result whose buffer a later donating consumer swallowed — used to
    surface as an opaque substrate error deep inside ``device_put`` /
    ``device_get``.  This typed error names the operand and the remedy
    instead (restage from host, or let the graph dispatcher *rename* —
    copy — the buffer before the donating consumer).
    """

    #: stable diagnostic code (``repro.analysis.diagnostics.CODES``)
    code = "OFL003"

    def __init__(self, what: str):
        from repro.analysis.diagnostics import use_after_donate
        self.diagnostic = use_after_donate(what)
        super().__init__(
            f"{what} was deleted by a donating dispatch; restage it from "
            "the host copy (plan.resident_operands restores resident "
            "buffers automatically) or disable donate_operands for "
            "buffers that must stay readable")


def _check_live(value: Any, what: str) -> Any:
    """Raise the typed donation error for a deleted jax buffer."""
    if getattr(value, "is_deleted", None) is not None and value.is_deleted():
        raise DonatedOperandError(what)
    return value


def _nbytes_of(data: Any) -> int:
    """Host bytes of a fetched result (arrays or pytrees of them)."""
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(data))


@dataclasses.dataclass
class JobHandle:
    """An in-flight offloaded job (async dispatch = multiple outstanding)."""

    job_id: int
    result: Any                      # jax arrays (async until blocked on)
    arrivals: Any                    # device-side arrivals count
    n_clusters: int
    runtime: "OffloadRuntime"
    cluster_ids: Tuple[int, ...] = ()
    plan: Optional["DispatchPlan"] = None
    _data: Any = None
    _done: bool = False
    _retired: bool = False
    _fault: Optional[CompletionTimeout] = None

    def _complete(self, arrivals: int) -> None:
        """Feed the completion unit, resolving any injected fault."""
        inj = self.runtime.fault_injector
        lost = (inj.lost_arrivals(self.runtime, self.job_id)
                if inj is not None else 0)
        if lost:
            self.runtime.unit.arrive(self.job_id, arrivals - lost)
            missing = self.runtime.unit.cancel(self.job_id)
            self.result = self.arrivals = None
            self._fault = CompletionTimeout(self.job_id, missing,
                                            self.cluster_ids)
            raise self._fault
        self.runtime.unit.arrive(self.job_id, arrivals)
        self.runtime.unit.collect(self.job_id)
        self._retired = True

    def retire(self) -> None:
        """Collect *completion only*, leaving the result on the fabric.

        Fetches the arrivals scalar (a host-side doorbell read, not the
        result payload), feeds the completion unit, and frees this job's
        unit copy — ``stats.d2h_bytes`` does not grow.  The graph
        dispatcher retires intermediate nodes this way: their results are
        forwarded device-to-device to consumers and never fetched.
        Idempotent; ``wait()`` after ``retire()`` fetches only the data.
        """
        if self._fault is not None:
            raise self._fault
        if self._retired or self._done:
            return
        with span("offload.fetch"):
            arrivals = jax.device_get(self.arrivals)
        self._complete(int(arrivals))
        self.arrivals = None

    def wait(self) -> Any:
        """Block until complete; feeds the completion unit and returns data.

        One blocking ``device_get`` fetches result and arrivals together,
        and completion causes are drained out of order through
        :meth:`CompletionUnit.collect` — handles may be waited on in any
        order relative to dispatch (the number of *outstanding* jobs is
        bounded by the runtime's ``n_units``, as in the paper's fig. 6).
        Idempotent: a second call returns the cached result without
        touching the device or the completion unit again.  The result
        payload's bytes are counted in the plan's ``stats.d2h_bytes`` —
        the counter proving graph intermediates never take this path.

        Under fault injection, a dispatch whose arrivals were dropped
        raises :class:`~repro.core.faults.CompletionTimeout` instead:
        the partial arrivals are fed to the unit first (so
        ``outstanding()`` shows the missing count — the actionable
        signal) and the stuck register is cancelled so the unit is
        immediately reusable for the resubmit.
        """
        if self._fault is not None:
            raise self._fault
        if self._done:
            return self._data
        _check_live(self.result, f"job {self.job_id}'s result buffer")
        s = _san.active()
        if s is not None:
            s.read(self.result, f"wait() on job {self.job_id}")
        with span("offload.fetch"):
            data, arrivals = jax.device_get(
                (self.result, None if self._retired else self.arrivals))
        if arrivals is not None:
            self._complete(int(arrivals))
        if self.plan is not None:
            self.plan.stats.d2h_bytes += _nbytes_of(data)
        self._data, self._done = data, True
        self.result = self.arrivals = None   # drop device refs
        return data


@dataclasses.dataclass
class FusedHandle(JobHandle):
    """Handle for B jobs fused into one launch; ``wait()`` returns the
    stacked (B, ...) output, ``wait_each()`` the per-job results."""

    batch: int = 1

    def wait_each(self) -> list:
        data = self.wait()
        return [np.asarray(data[i]) for i in range(self.batch)]


class DispatchPlan:
    """Cached dispatch state for one (job, cluster selection, operand shapes).

    Holds everything ``offload()`` would otherwise recompute per job: the
    sub-mesh, per-operand ``NamedSharding``s, the compiled program, the last
    staged job-args value, and (optionally) *resident* operand buffers that
    repeated dispatch reuses without any host->device transfer.

    ``fuse=B`` makes this a *fused* plan: operand shapes in ``op_meta``
    carry a leading batch axis of length B, shard axes shift right by one,
    and the compiled program vmaps the kernel over the batch — one launch
    for B jobs.
    """

    def __init__(self, runtime: "OffloadRuntime", job: PaperJob,
                 devices: Sequence[jax.Device], cluster_ids: Sequence[int],
                 op_meta: Tuple[Tuple[str, Tuple[int, ...], str], ...],
                 args_shape: Tuple[int, ...],
                 fuse: Optional[int] = None):
        self.runtime = runtime
        self.job = job
        self.cluster_ids = tuple(cluster_ids)
        self.n_clusters = len(cluster_ids)
        self.mesh = Mesh(np.asarray(devices), (AXIS,))
        self.op_meta = op_meta
        self.args_shape = tuple(args_shape)
        self.fuse = fuse
        self.stats = PlanStats()

        cfg = runtime.config
        if cfg.info_dist == "multicast":
            self.args_sharding = NamedSharding(self.mesh, P())
        else:
            self.args_sharding = NamedSharding(self.mesh, P(AXIS))
        lead = 0 if fuse is None else 1   # fused shapes: (B,) + per-job shape
        self.op_shardings: Dict[str, NamedSharding] = {}
        for name, shape, _ in op_meta:
            axis = job.shard_axes[name]
            spec = (P() if axis is None
                    else P(*([None] * (axis + lead) + [AXIS])))
            if axis is not None and shape[axis + lead] % self.n_clusters:
                raise ValueError(
                    f"operand {name} axis {axis} ({shape[axis + lead]}) "
                    f"not divisible by {self.n_clusters} clusters"
                )
            self.op_shardings[name] = NamedSharding(self.mesh, spec)

        self.fn = runtime._build(
            job, self.mesh, self.n_clusters,
            tuple(name for name, _, _ in op_meta), self.args_shape,
            fuse=fuse)

        self._resident: Dict[str, Any] = {}       # name -> device buffer
        self._resident_src: Dict[str, np.ndarray] = {}  # name -> host array
        self._slots: Dict[int, Dict[str, Any]] = {}  # stream staging slots
        self._args_val: Optional[np.ndarray] = None
        self._args_dev: Any = None
        self._devices = list(devices)
        self._stager: Optional[bc.TreeStager] = None   # built lazily
        self._staged_via: str = runtime.config.staging  # residency's mode

    # -- staging ---------------------------------------------------------------

    @property
    def has_resident(self) -> bool:
        return len(self._resident) == len(self.op_meta) > 0 or not self.op_meta

    def _resolve_via(self, via: Optional[Union[str, Staging]]) -> Staging:
        if via is None:
            return self.runtime.config.staging
        if isinstance(via, Staging):
            return via
        return coerce_enum(Staging, via, "via", warn_legacy=True)

    def _tree_stager(self) -> bc.TreeStager:
        if self._stager is None:
            # one tree per plan: the quadrant-aware fan-out derived from the
            # cluster selection, shared by every staging (and every job of a
            # fused batch — the stacked operands ride one tree)
            self._stager = bc.TreeStager(self._devices, self.cluster_ids)
        return self._stager

    def _put(self, arr: np.ndarray, sharding: NamedSharding, via: str) -> Any:
        """One operand/args upload under a staging strategy, bytes counted.

        Sharded arrays cross the host link once regardless of mode (each
        device receives only its shard); the strategies differ only for
        replicated arrays — the O(n) host-link offenders.
        """
        n = self.n_clusters
        if not bc.is_replicated(sharding):
            self.stats.h2d_bytes += arr.nbytes
            return jax.device_put(arr, sharding)
        if via in bc.TREE_MODES:
            self.stats.tree_stages += 1
            return self._tree_stager().put_replicated(
                arr, sharding, reshard=(via == "tree_reshard"),
                stats=self.stats)
        if via == "host_fanout":
            # the measurable O(n) baseline: one host->device transfer per
            # cluster, one outstanding at a time (the serialized host-link
            # writes of §4.1 — CVA6's outstanding-transaction budget)
            bufs = []
            for d in self._devices:
                b = jax.device_put(arr, d)
                b.block_until_ready()
                bufs.append(b)
            self.stats.h2d_bytes += arr.nbytes * n
            return jax.make_array_from_single_device_arrays(
                tuple(arr.shape), sharding, bufs)
        self.stats.h2d_bytes += arr.nbytes * n
        return jax.device_put(arr, sharding)

    def stage(self, operands: Dict[str, np.ndarray], *,
              _caller_owned: bool = True,
              slot: Optional[int] = None,
              via: Optional[Union[str, Staging]] = None) -> Dict[str, Any]:
        """Phase-E upload of ``operands``.

        With ``slot=None`` (default) the buffers become *resident* — the
        warm ``offload(job, "resident")`` path reuses them.  With a slot
        number they land in that numbered staging slot instead, leaving
        residency untouched: the double-buffering hook
        :class:`~repro.core.stream.OffloadStream` uses to overlap job k+1's
        upload with job k's compute.

        ``via`` picks the staging strategy for replicated operands (see
        ``STAGING_MODES``), defaulting to ``OffloadConfig.staging``.  With
        ``"tree"``, each replicated operand crosses the host link exactly
        once (to the fan-out tree root) and reaches the remaining clusters
        through device-to-device copies — ``stats.h2d_bytes`` grows by
        size, not n·size.
        """
        with span("offload.stage"):
            via = self._resolve_via(via)
            names = tuple(sorted(operands))
            if names != tuple(name for name, _, _ in self.op_meta):
                raise ValueError(
                    f"operand names {names} do not match plan {self.op_meta}")
            staged = {}
            donating = self.runtime.config.donate_operands
            for name, shape, dtype in self.op_meta:
                arr = np.asarray(_check_live(operands[name],
                                             f"staged operand {name!r}"))
                if tuple(arr.shape) != shape:
                    raise ValueError(
                        f"operand {name} shape {arr.shape} != planned {shape}")
                if str(arr.dtype) != dtype:
                    raise ValueError(
                        f"operand {name} dtype {arr.dtype} != planned "
                        f"{dtype} (a dtype change needs a new plan, not a "
                        "silent retrace)")
                staged[name] = self._put(arr, self.op_shardings[name], via)
                self.stats.device_puts += 1
                if slot is None:
                    # donation restages from these refs later — snapshot
                    # caller arrays so in-place mutation cannot skew the redo
                    # (restages from our own snapshots skip the copy).  One
                    # snapshot per operand at the tree root only: the
                    # per-device fan-out copies live on the devices, never
                    # on the host.
                    self._resident_src[name] = (
                        arr.copy() if donating and _caller_owned else arr)
            if slot is None:
                self._resident = staged
                self._staged_via = via
            else:
                # slot buffers are single-use: each stream submit stages fresh
                # operands, so a donated dispatch consuming them needs no redo
                self._slots[slot] = staged
            s = _san.active()
            if s is not None:
                for name, buf in staged.items():
                    s.track(buf, f"staged operand {name!r}")
            return staged

    def forward(self, name: str, value: Any, *,
                rename: bool = False) -> Tuple[Any, int]:
        """Stage operand ``name`` from a *device-resident* producer result.

        The device-to-device leg of dependent dispatch: ``value`` (a jax
        array, possibly still in flight — async dispatch chains it) is
        resharded to this plan's operand sharding without ever visiting
        the host.  Replicated consumer operands fan out along the PR-3
        broadcast tree (root hop from the producer, then the levelled
        d2d copies); sharding-identical forwards alias the producer's
        buffer outright (zero copies) unless ``rename`` or a donating
        config forces a fresh buffer — the WAR/WAW rename that keeps the
        producer's result alive for its remaining readers.

        Returns ``(staged, nbytes)`` where ``nbytes`` is the logical d2d
        byte count of this edge (also accumulated into
        ``stats.forward_bytes``; ``stats.h2d_bytes``/``d2h_bytes`` do
        not move — that is the point).
        """
        names = tuple(n for n, _, _ in self.op_meta)
        if name not in names:
            raise ValueError(f"operand {name!r} not in plan {names}")
        _check_live(value, f"forwarded operand {name!r}")
        s = _san.active()
        if s is not None:
            s.read(value, f"forward of operand {name!r}")
        shape, dtype = next((s, d) for n, s, d in self.op_meta if n == name)
        if tuple(value.shape) != shape or str(value.dtype) != dtype:
            raise ValueError(
                f"forwarded operand {name!r} is {value.shape}/{value.dtype},"
                f" plan expects {shape}/{dtype}")
        sharding = self.op_shardings[name]
        must_rename = rename or self.runtime.config.donate_operands
        moved = 0
        src_sharding = getattr(value, "sharding", None)
        if (src_sharding is not None
                and src_sharding.is_equivalent_to(sharding, value.ndim)):
            # same placement: alias (free) or rename-copy (per-device
            # local, so the logical link bytes stay zero — no edge of
            # the fabric is crossed)
            if must_rename:
                staged = jnp.copy(value)
                self.stats.renames += 1
            else:
                staged = value
        elif bc.is_replicated(sharding):
            staged = self._tree_stager().forward_replicated(
                value, sharding, stats=self.stats)
            moved = value.nbytes * self.n_clusters
        else:
            # sharded consumer: each shard crosses the fabric once
            staged = jax.device_put(value, sharding)
            moved = value.nbytes
            self.stats.forward_bytes += moved
        self.stats.forwards += 1
        if s is not None and staged is not value:
            s.track(staged, f"forwarded operand {name!r}")
        return staged, moved

    def stage_renamed(self, operands: Dict[str, Any], *,
                      via: Optional[Union[str, Staging]] = None
                      ) -> Tuple[Dict[str, Any], Dict[str, int]]:
        """Graph-node staging: host arrays *and* forwarded device arrays.

        Every buffer is fresh (renamed) — residency and stream slots are
        never overwritten, so a graph node whose operands collide with a
        resident buffer or an earlier node's staging proceeds instead of
        stalling (the WAW side of the scoreboard's renaming).  Host
        arrays take the ordinary :meth:`_put` path under ``via``;
        device-resident values take :meth:`forward`.  Returns
        ``(staged, forwarded_bytes_per_operand)``.
        """
        with span("offload.stage"):
            via = self._resolve_via(via)
            names = tuple(sorted(operands))
            if names != tuple(name for name, _, _ in self.op_meta):
                raise ValueError(
                    f"operand names {names} do not match plan {self.op_meta}")
            staged: Dict[str, Any] = {}
            fwd_bytes: Dict[str, int] = {}
            for name, shape, dtype in self.op_meta:
                value = operands[name]
                if isinstance(value, jax.Array):
                    staged[name], fwd_bytes[name] = self.forward(name, value)
                else:
                    arr = np.asarray(value)
                    if tuple(arr.shape) != shape:
                        raise ValueError(
                            f"operand {name} shape {arr.shape} != planned "
                            f"{shape}")
                    if str(arr.dtype) != dtype:
                        raise ValueError(
                            f"operand {name} dtype {arr.dtype} != planned "
                            f"{dtype}")
                    staged[name] = self._put(arr, self.op_shardings[name], via)
                    self.stats.device_puts += 1
                    s = _san.active()
                    if s is not None:
                        s.track(staged[name], f"renamed operand {name!r}")
            return staged, fwd_bytes

    def invalidate(self, names: Optional[Sequence[str]] = None) -> None:
        """Drop resident operand buffers (all, or a named subset)."""
        s = _san.active()
        if s is not None:
            dropped = (self._resident.items() if names is None else
                       ((n, self._resident[n]) for n in names
                        if n in self._resident))
            for name, buf in dropped:
                s.revoke(buf, f"resident operand {name!r}")
        if names is None:
            self._resident.clear()
            self._resident_src.clear()
            self._slots.clear()
        else:
            for name in names:
                self._resident.pop(name, None)
                self._resident_src.pop(name, None)

    def resident_operands(self) -> Dict[str, Any]:
        """The resident device buffers, re-staging any consumed by donation."""
        if not self._resident and self._resident_src:
            # a donated dispatch consumed the buffers; restore from host
            # refs through the same staging strategy they arrived by — a
            # tree-staged operand re-crosses the host link once (root
            # upload), not once per device
            self.stage(dict(self._resident_src), _caller_owned=False,
                       via=self._staged_via)
            self.stats.donation_restages += len(self.op_meta)
        if len(self._resident) != len(self.op_meta):
            raise RuntimeError(
                "no resident operands staged for this plan — dispatch once "
                "with real operands (or call plan.stage) before "
                "offload(job, 'resident', ...)")
        self.stats.resident_hits += len(self.op_meta)
        s = _san.active()
        if s is not None:
            for name, buf in self._resident.items():
                s.read(buf, f"resident operand {name!r}")
        return dict(self._resident)

    def stage_args(self, job_args: np.ndarray, *,
                   via: Optional[Union[str, Staging]] = None) -> Any:
        """Upload job args, skipping the transfer when the value is unchanged.

        Replicated job args (multicast mode) honour the ``via`` staging
        strategy too — they are the paper's actual multicast payload (the
        phase-A job information), so ``"tree"`` sends them over the host
        link once.  Baseline (p2p_chain) args are materialized on cluster 0
        and tiled, an O(n)-byte host transfer by construction.
        """
        with span("offload.stage"):
            if (self._args_dev is not None and self._args_val is not None
                    and np.array_equal(self._args_val, job_args)):
                self.stats.args_hits += 1
                return self._args_dev
            if self.runtime.config.info_dist == "multicast":
                host = job_args
            else:
                tiled = np.zeros((self.n_clusters,) + job_args.shape,
                                 job_args.dtype)
                tiled[0] = job_args
                host = tiled
            self._args_dev = self._put(np.asarray(host), self.args_sharding,
                                       self._resolve_via(via))
            self.stats.device_puts += 1
            self._args_val = job_args.copy()
            return self._args_dev

    def _after_dispatch(self, consumed_resident: bool = True) -> None:
        self.stats.dispatches += 1
        self.stats.fused_jobs += self.fuse if self.fuse else 1
        if self.runtime.config.donate_operands and consumed_resident:
            # donated buffers are dead; keep host refs so reuse self-heals
            self._resident.clear()


def _chain_distribute(args: jnp.ndarray, n: int) -> jnp.ndarray:
    """Baseline phases C/D: args hop cluster-0 -> 1 -> ... -> n-1.

    Builds n-1 dependent collective-permutes (the O(n) critical path).
    """
    if n == 1:
        return args
    idx = jax.lax.axis_index(AXIS)
    have = args
    perm = [(i, i + 1) for i in range(n - 1)]
    for k in range(n - 1):
        received = jax.lax.ppermute(have, AXIS, perm)
        have = jnp.where(idx <= k, have, received)
    return have


class OffloadRuntime:
    """Host-centric offload of jobs onto a 1-D cluster mesh."""

    def __init__(
        self,
        devices: Optional[Sequence[jax.Device]] = None,
        config: OffloadConfig = OffloadConfig.extended(),
        n_units: int = 4,
        cluster_ids: Optional[Sequence[int]] = None,
        fault_injector: Optional[FaultInjector] = None,
    ):
        self.all_devices = list(devices if devices is not None else jax.devices())
        # the fabric window this runtime owns: global cluster ids, one per
        # device.  A whole-mesh runtime is the identity window; a runtime
        # backing a ClusterLease carries the lease's ids so dispatch plans
        # are keyed by placement and staging trees stay quadrant-aware
        # relative to the real fabric position.
        ids = (range(len(self.all_devices)) if cluster_ids is None
               else cluster_ids)
        self.cluster_ids = tuple(int(c) for c in ids)
        if len(self.cluster_ids) != len(self.all_devices):
            raise ValueError(
                f"{len(self.cluster_ids)} cluster ids for "
                f"{len(self.all_devices)} devices")
        if len(set(self.cluster_ids)) != len(self.cluster_ids):
            raise ValueError(f"duplicate cluster ids in {self.cluster_ids}")
        self.config = config
        self.fault_injector = fault_injector
        self.unit = CompletionUnit(n_units=n_units)
        self._job_counter = 0
        self._compiled: Dict[Tuple, Any] = {}
        self._hlo_text: Dict[Tuple, str] = {}   # lowered_text cache
        self._plans: Dict[Tuple, DispatchPlan] = {}
        self._retired_stats = PlanStats()   # counts from replaced plans
        self.plan_hits = 0
        self.plan_misses = 0

    @property
    def stats(self) -> PlanStats:
        """Running dispatch-overhead totals across all plans (monotonic —
        replaced plans' counts are retained)."""
        agg = dataclasses.replace(self._retired_stats)
        for p in self._plans.values():
            agg.accumulate(p.stats)
        return agg

    # -- cluster selection (paper §4.2 semantics) ---------------------------------

    def select_clusters(
        self,
        n: Optional[int] = None,
        request: Optional[mc.MulticastRequest] = None,
        clusters: Optional[Sequence[int]] = None,
    ) -> Tuple[Sequence[jax.Device], Sequence[int]]:
        """Resolve a cluster selection to a device subset.

        Exactly one of ``n`` (first n clusters), ``request`` (an address-mask
        multicast request, fig. 5) or ``clusters`` (an explicit set, greedily
        covered by subcube requests) must be given.  All three are
        *window-relative*: they select within the runtime's fabric window
        (``cluster_ids``), and the returned ids are the selected clusters'
        **global** fabric ids — a lease-backed runtime keys its plans and
        derives its staging trees from the real placement.  For a
        whole-mesh runtime the window is the identity and nothing changes.
        """
        if sum(x is not None for x in (n, request, clusters)) != 1:
            raise ValueError("give exactly one of n / request / clusters")
        if request is not None:
            ids = mc.decode_cluster_selection(request, len(self.all_devices))
        elif clusters is not None:
            reqs = mc.encode_cluster_selection_multi(clusters, len(self.all_devices))
            ids = sorted(
                {c for r in reqs for c in mc.decode_cluster_selection(r, len(self.all_devices))}
            )
            assert set(ids) == set(clusters)
        else:
            if not (1 <= n <= len(self.all_devices)):
                raise ValueError(f"n={n} outside [1, {len(self.all_devices)}]")
            ids = list(range(n))
        return ([self.all_devices[i] for i in ids],
                [self.cluster_ids[i] for i in ids])

    # -- planning -------------------------------------------------------------------

    def plan(
        self,
        job: PaperJob,
        operands: Optional[Dict[str, np.ndarray]] = None,
        n: Optional[int] = None,
        request: Optional[mc.MulticastRequest] = None,
        clusters: Optional[Sequence[int]] = None,
        args_shape: Tuple[int, ...] = (8,),
        fuse: Optional[int] = None,
    ) -> DispatchPlan:
        """Resolve (and cache) the dispatch plan for a job/selection pair.

        With ``operands`` given, their shapes/dtypes seed (or validate) the
        plan; staging is separate (``plan.stage`` / a dict ``offload``).
        Without operands, the plan must already exist (from a prior dispatch
        or ``plan()`` call) and is returned as-is.  ``fuse=B`` resolves the
        fused-batch plan (operand shapes carry the leading B axis).
        """
        devices, ids = self.select_clusters(
            n=n if (request is None and clusters is None) else None,
            request=request, clusters=clusters,
        )
        key = (job.spec.name, tuple(ids), tuple(args_shape), fuse)
        if operands is None:
            plan = self._plans.get(key)
            if plan is None:
                raise KeyError(
                    f"no dispatch plan for {key}; pass operands once first")
            self.plan_hits += 1
            return plan

        op_meta = tuple(
            (name, tuple(np.asarray(operands[name]).shape),
             str(np.asarray(operands[name]).dtype))
            for name in sorted(operands)
        )
        plan = self._plans.get(key)
        if plan is not None and plan.op_meta == op_meta:
            self.plan_hits += 1
            return plan
        self.plan_misses += 1
        new_plan = DispatchPlan(self, job, devices, ids, op_meta,
                                tuple(args_shape), fuse=fuse)
        if plan is not None:   # replaced: keep its counts (after the build
            # succeeded, so a failing build leaves the old plan untouched)
            self._retired_stats.accumulate(plan.stats)
        self._plans[key] = new_plan
        return new_plan

    # -- dispatch -------------------------------------------------------------------

    def offload(
        self,
        job: PaperJob,
        operands: Union[Dict[str, np.ndarray], str, Residency],
        job_args: Optional[np.ndarray] = None,
        n: Optional[int] = None,
        request: Optional[mc.MulticastRequest] = None,
        clusters: Optional[Sequence[int]] = None,
    ) -> JobHandle:
        """Phase A..I, as one jitted program on the selected sub-mesh.

        ``operands`` is either the host operand dict (phase-E staged on this
        call, and left resident on the plan) or the string ``"resident"`` to
        reuse the buffers staged by the previous dispatch of the same plan —
        the zero-``device_put`` warm path.
        """
        if job_args is None:
            job_args = np.ones((8,), dtype=np.float64)
        job_args = np.asarray(job_args, dtype=np.float64)

        resident = _is_resident(operands, "offload")
        plan = self.plan(
            job, operands=None if resident else operands,
            n=n, request=request, clusters=clusters,
            args_shape=job_args.shape,
        )

        # Phase A / job-info placement (multicast replicates, baseline
        # materializes on cluster 0) — skipped when the value is unchanged.
        args_dev = plan.stage_args(job_args)

        # Phase E staging: resident mode reuses the prior buffers outright.
        if resident:
            op_dev = plan.resident_operands()
        else:
            op_dev = plan.stage(operands)
        return self._launch(plan, args_dev, op_dev)

    def offload_fused(
        self,
        job: PaperJob,
        instances: Union[Sequence[Dict[str, np.ndarray]], str, Residency],
        job_args: Optional[np.ndarray] = None,
        n: Optional[int] = None,
        request: Optional[mc.MulticastRequest] = None,
        clusters: Optional[Sequence[int]] = None,
        batch: Optional[int] = None,
    ) -> FusedHandle:
        """Deprecated direct entry point — fuse B instances into one launch.

        The session API subsumes this: ``Session.submit(job, instances,
        policy=OffloadPolicy(fuse=B))`` (or ``policy=AUTO`` to let the
        planner pick B).  Kept as a warning shim over the same
        implementation.
        """
        warn_legacy("direct OffloadRuntime.offload_fused()",
                    "Session.submit(job, instances, policy=...)")
        return self._offload_fused(job, instances, job_args=job_args, n=n,
                                   request=request, clusters=clusters,
                                   batch=batch)

    def _offload_fused(
        self,
        job: PaperJob,
        instances: Union[Sequence[Dict[str, np.ndarray]], str, Residency],
        job_args: Optional[np.ndarray] = None,
        n: Optional[int] = None,
        request: Optional[mc.MulticastRequest] = None,
        clusters: Optional[Sequence[int]] = None,
        batch: Optional[int] = None,
        staging: Optional[Staging] = None,
    ) -> FusedHandle:
        """Fuse B instances of ``job`` into one XLA launch.

        ``instances`` is a sequence of B operand dicts (stacked host-side
        along a new leading batch axis and phase-E staged as one transfer
        per operand) or ``Residency.RESIDENT`` to redispatch the
        previously staged batch (``batch=B`` then selects the fused plan).
        ``job_args`` may be one (A,) vector shared by all jobs or a (B, A)
        array of per-job args.  ``staging`` picks the phase-E strategy for
        the stacked replicated operands (default: the runtime config's).
        Returns a :class:`FusedHandle` whose ``wait()`` yields the stacked
        (B, ...) results.

        The host pays ~1/B of the per-job dispatch cost while the lowered
        program's collective count stays independent of B (asserted by
        tests over ``lowered_text(job, n, fuse=B)``).
        """
        resident = _is_resident(instances, "offload_fused")
        if resident:
            if batch is None:
                raise ValueError("resident fused dispatch needs batch=B")
            B = batch
        else:
            B = len(instances)
            if B < 1:
                raise ValueError("offload_fused needs at least one instance")
            if batch is not None and batch != B:
                raise ValueError(f"batch={batch} != len(instances)={B}")

        if job_args is None:
            job_args = np.ones((8,), dtype=np.float64)
        job_args = np.asarray(job_args, dtype=np.float64)
        if job_args.ndim == 1:
            job_args = np.broadcast_to(job_args, (B,) + job_args.shape).copy()
        if job_args.shape[0] != B:
            raise ValueError(
                f"job_args leading axis {job_args.shape[0]} != batch {B}")

        stacked = None if resident else stack_instances(instances)
        plan = self.plan(
            job, operands=stacked,
            n=n, request=request, clusters=clusters,
            args_shape=job_args.shape, fuse=B,
        )
        args_dev = plan.stage_args(job_args, via=staging)
        # the stacked dict is ours (fresh arrays from stack_instances), so
        # donation needs no defensive snapshot of it
        op_dev = (plan.resident_operands() if resident
                  else plan.stage(stacked, _caller_owned=False, via=staging))
        handle = self._launch(plan, args_dev, op_dev)
        return FusedHandle(handle.job_id, handle.result, handle.arrivals,
                           plan.n_clusters, self, plan.cluster_ids, plan,
                           batch=B)

    def _launch(self, plan: DispatchPlan, args_dev: Any,
                op_dev: Dict[str, Any],
                consumed_resident: bool = True) -> JobHandle:
        """The dispatch tail shared by offload/offload_fused/OffloadStream:
        program a completion unit, launch the compiled program (async),
        return the in-flight handle."""
        job_id = self._job_counter
        self._job_counter += 1
        with span("offload.launch", job=job_id):
            self.unit.program(plan.n_clusters, job_id)
            if self.fault_injector is not None:
                # fault-injection hook: resolves this dispatch's scheduled
                # effect (dropped arrivals / virtual delay) deterministically
                self.fault_injector.on_dispatch(self, job_id, plan.cluster_ids,
                                                plan.job.spec)
            s = _san.active()
            if s is not None:
                # op_dev may alias plan._resident, which a donating
                # _after_dispatch clears — snapshot the buffers first
                op_bufs = [(name, op_dev[name]) for name, _, _ in plan.op_meta]
                for name, buf in op_bufs:
                    s.read(buf, f"launch {job_id} operand {name!r}")
            result, arrivals = plan.fn(
                args_dev, *(op_dev[name] for name, _, _ in plan.op_meta))
            plan._after_dispatch(consumed_resident=consumed_resident)
            if s is not None:
                if self.config.donate_operands:
                    for name, buf in op_bufs:
                        s.donate(buf, f"operand {name!r}")
                s.track(result, f"job {job_id}'s result buffer")
            return JobHandle(job_id, result, arrivals, plan.n_clusters, self,
                             plan.cluster_ids, plan)

    def run(self, job: PaperJob, seed: int = 0, **sel) -> Tuple[Any, Any]:
        """Convenience: build an instance, offload it, return (got, expected)."""
        operands, expected = job.make_instance(seed)
        handle = self.offload(job, operands, **sel)
        return handle.wait(), expected

    # -- program construction ---------------------------------------------------------

    def _build(self, job, mesh, n, op_names, args_shape, fuse=None):
        key = (job.spec.name, self.config, n, op_names, args_shape,
               tuple(d.id for d in mesh.devices.flat), fuse)
        if key in self._compiled:
            return self._compiled[key]

        cfg = self.config
        shard_axes = job.shard_axes
        out_axis = job.out_axis
        reduce = job.reduce
        compute = job.compute
        lead = 0 if fuse is None else 1

        in_specs = [P(AXIS) if cfg.info_dist == "p2p_chain" else P()]
        for name in op_names:
            ax = shard_axes[name]
            in_specs.append(
                P() if ax is None else P(*([None] * (ax + lead) + [AXIS])))
        out_specs = (
            P() if out_axis is None
            else P(*([None] * (out_axis + lead) + [AXIS])),
            P(),
        )

        def program(args, *ops):
            # Phases B/C/D: job-information distribution.
            if cfg.info_dist == "p2p_chain":
                local_args = _chain_distribute(args[0], n)
            else:
                local_args = args
            # The job-info scale rides through the computation so the
            # distribution chain is live in the HLO (and so a wrong
            # distribution corrupts the result -> tested).

            # Phase F: the kernel, on this cluster's shard.
            if fuse is None:
                out = compute(*ops)
                out = out * local_args[0].astype(out.dtype)
            else:
                # B fused jobs: vmap the kernel over the leading batch axis;
                # each job keeps its own args scale.  The cross-cluster
                # reduction below acts on the batched array, so the
                # collective count stays independent of B.
                def one_job(job_ops, scale):
                    out = compute(*job_ops)
                    return out * scale.astype(out.dtype)
                out = jax.vmap(one_job)(ops, local_args[:, 0])
            if out_axis is None and reduce == "sum":
                out = jax.lax.psum(out, AXIS)
            elif out_axis is None and reduce == "mean":
                out = jax.lax.pmean(out, AXIS)

            # Phase H: completion notification (one per launch, fused or not).
            done = jnp.float32(1.0)
            if cfg.completion == "unit":
                arrivals = completion_unit_arrivals(done, AXIS)
            else:
                arrivals = central_counter_arrivals(done, AXIS, n)
            return out, arrivals

        # the trace names the device operations by their program:
        # jit_offload_axpy, jit_offload_axpy_fused
        program.__name__ = "offload_" + job.spec.name.split("[")[0] + (
            "" if fuse is None else "_fused")
        donate = tuple(range(1, 1 + len(op_names))) if cfg.donate_operands else ()
        fn = jax.jit(
            shard_map(
                program, mesh=mesh, in_specs=tuple(in_specs),
                out_specs=out_specs,
            ),
            donate_argnums=donate,
        )
        self._compiled[key] = fn
        return fn

    # -- introspection -------------------------------------------------------------

    def lowered_text(self, job: PaperJob, n: int, seed: int = 0,
                     fuse: Optional[int] = None) -> str:
        """Compiled HLO of the offloaded program — used by tests/benchmarks to
        assert the collective structure (chain depth vs broadcast tree).

        The text is cached per (job, n, config, fuse, device set): repeated
        structure assertions read the cache instead of paying a fresh
        lower+compile each call.  ``fuse=B`` lowers the fused-batch program.
        """
        devices, _ = self.select_clusters(n=n)
        key = (job.spec.name, self.config, n, fuse,
               tuple(d.id for d in devices))
        cached = self._hlo_text.get(key)
        if cached is not None:
            return cached
        operands, _ = job.make_instance(seed)
        mesh = Mesh(np.asarray(devices), (AXIS,))
        fn = self._build(job, mesh, n, tuple(sorted(operands)), (8,) if
                         fuse is None else (fuse, 8), fuse=fuse)
        ftype = jax.dtypes.canonicalize_dtype(jnp.float64)  # x64 or f32
        lead = () if fuse is None else (fuse,)
        args_shape = lead + (8,)
        if self.config.info_dist == "p2p_chain":
            args_shape = (n,) + args_shape
        sds = [jax.ShapeDtypeStruct(args_shape, ftype)]
        for name in sorted(operands):
            arr = np.asarray(operands[name])
            sds.append(jax.ShapeDtypeStruct(lead + arr.shape, ftype))
        text = fn.lower(*sds).compile().as_text()
        self._hlo_text[key] = text
        return text


def count_collectives(hlo: str) -> Dict[str, int]:
    """Occurrences of each collective op kind in an HLO dump."""
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    counts = {}
    for k in kinds:
        counts[k] = sum(
            1 for line in hlo.splitlines()
            if f" {k}" in line or line.lstrip().startswith(f"{k}")
            if "start" not in line.split("=")[0]
        )
    return counts
