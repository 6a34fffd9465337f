"""Roofline analysis from compiled dry-run artifacts.

Three terms per (architecture × shape × mesh) cell, all in seconds-per-step
on the TARGET hardware, whose peaks come from :data:`PEAKS` (the dry run
targets ``TARGET_KIND``, a TPU v5e):

    compute    = HLO_FLOPs_per_device   / peak bf16 FLOP/s
    memory     = HLO_bytes_per_device   / peak HBM B/s
    collective = collective_bytes_per_device / peak ICI B/s per link

Accounting notes (verified against a hand-checked matmul in
tests/test_roofline.py):

* XLA:CPU's ``compiled.cost_analysis()`` reports **per-device** (post-SPMD-
  partitioning) flops / bytes, so no division by chip count is applied.
* ``bytes accessed`` counts every operator's reads+writes, an upper bound on
  unique HBM traffic (fusion reduces real traffic) — conservative for a
  memory-bound verdict.
* collective bytes are parsed from the partitioned HLO: for every
  all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
  the *operand* sizes are summed (two-pass parse resolves operand shapes);
  ``*-done`` halves of async pairs are skipped so nothing is double-counted.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float           # FLOP/s
    hbm_bw: float               # B/s
    ici_bw_per_link: float      # B/s
    source: str


#: peak table keyed by ``jax.Device.device_kind``; a kind that is not here
#: has no peaks, and :func:`peaks` refuses it rather than guess
PEAKS: Dict[str, ChipPeaks] = {
    # 1,600 Gbit/s of chip-to-chip interconnect over 4 ICI links = 50 GB/s
    # per link
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw_per_link=50e9,
        source='Google Cloud documentation, "TPU v5e" (per-chip '
               'specifications)'),
}

#: the chip the dry-run roofline models
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None


_TARGET = peaks(TARGET_KIND)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"^([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+([\w\-]+)")


def _shape_bytes(shape_str: str) -> int:
    """bytes of 'bf16[128,512]{1,0}' — 0 for tuples/tokens/opaque."""
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Two-pass parse: symbol table of result shapes, then operand sums."""
    shapes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, shape, _op = m.groups()
            shapes[name] = shape

    counts = {k: 0 for k in COLLECTIVE_OPS}
    nbytes = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, result_shape, op = m.groups()
        kind = next((k for k in COLLECTIVE_OPS if op.startswith(k)), None)
        if kind is None or op.startswith(kind + "-done"):
            continue
        counts[kind] += 1
        # operand list: the (...) right after the op name
        rest = line[line.index(op) + len(op):]
        args = rest[rest.index("(") + 1: _match_paren(rest)] if "(" in rest else ""
        total = 0
        for a in args.split(","):
            a = a.strip().lstrip("%")
            # strip inline shapes like 'bf16[8,128]{1,0} %param.1'
            if " " in a:
                a = a.split()[-1].lstrip("%")
            if a in shapes:
                total += _shape_bytes(shapes[a])
        if total == 0:
            total = _shape_bytes(result_shape)   # fallback: result size
        nbytes[kind] += total
    return CollectiveStats(counts, nbytes)


def _match_paren(s: str) -> int:
    depth = 0
    for i, ch in enumerate(s):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(s)


@dataclasses.dataclass
class Roofline:
    flops: float                     # per device (trip-count corrected)
    bytes_accessed: float            # per device (fusion-boundary traffic)
    collective_bytes: float          # per device (trip-count corrected)
    collectives: Dict[str, int]
    model_flops: float = 0.0         # 6·N·D (active N for MoE), global
    chips: int = 1
    raw_flops: float = 0.0           # XLA cost_analysis (loop bodies ×1)
    raw_bytes: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.flops / _TARGET.flops_bf16

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / _TARGET.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / _TARGET.ici_bw_per_link

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound(self) -> float:
        """Roofline step time (s): max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO flops × chips): how much compiled compute is
        'useful' (catches remat/redundancy waste)."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Useful-FLOPs utilization at the roofline bound: the score.
        = (MODEL_FLOPS / chips / peak) / max-term."""
        if self.bound == 0:
            return 0.0
        t_useful = self.model_flops / self.chips / _TARGET.flops_bf16
        return t_useful / self.bound

    def to_dict(self) -> Dict:
        return {
            "raw_xla_flops_per_device": self.raw_flops,
            "raw_xla_bytes_per_device": self.raw_bytes,
            "flops_per_device": self.flops,
            "bytes_per_device": self.bytes_accessed,
            "collective_bytes_per_device": self.collective_bytes,
            "collective_counts": self.collectives,
            "model_flops": self.model_flops,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }


def analyze(compiled, model_flops: float, chips: int) -> Roofline:
    """Primary numbers come from the trip-count-corrected HLO walk
    (:mod:`repro.launch.hlo_cost`): XLA's own ``cost_analysis()`` counts
    while-loop bodies once (verified in tests/test_hlo_cost.py), which
    under-counts every scan-shaped program here by the trip count.  The raw
    XLA numbers are preserved in ``raw_*`` for comparison."""
    from repro.launch.hlo_cost import analyze_hlo_text

    cost = compiled.cost_analysis()
    if isinstance(cost, list):       # older API returned [dict]
        cost = cost[0]
    corrected = analyze_hlo_text(compiled.as_text())
    return Roofline(
        flops=corrected.flops,
        bytes_accessed=corrected.bytes,
        collective_bytes=corrected.collective_bytes,
        collectives={k: int(v) for k, v in corrected.collective_counts.items()},
        model_flops=model_flops,
        chips=chips,
        raw_flops=float(cost.get("flops", 0.0)),
        raw_bytes=float(cost.get("bytes accessed", 0.0)),
    )


def model_flops_train(n_params_active: int, tokens: int) -> float:
    """6·N·D for a training step (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * tokens


def model_flops_forward(n_params_active: int, tokens: int) -> float:
    return 2.0 * n_params_active * tokens
