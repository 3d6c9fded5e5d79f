"""Roofline terms from compiled dry-run artifacts (TPU v5e targets).

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

``cost_analysis`` of the SPMD-partitioned module reports per-device FLOPs and
bytes. Collective bytes are not in cost_analysis — we parse the optimized
HLO text and sum operand sizes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (per-device shapes, so the
term is already per-chip).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

__all__ = [
    "HW",
    "RooflineTerms",
    "collective_bytes_from_hlo",
    "roofline_terms",
    "model_flops",
]


@dataclasses.dataclass(frozen=True)
class HW:
    """TPU v5e-class hardware constants (per chip)."""

    peak_flops: float = 197e12    # bf16 FLOP/s
    hbm_bw: float = 819e9         # B/s
    link_bw: float = 50e9         # B/s per ICI link


V5E = HW()

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# result shape after '=', e.g.  %ag = bf16[16,512]{1,0} all-gather(%x), ...
_RESULT_RE = re.compile(r"=\s+(?:\()?\s*(pred|[usfb]\w{1,4})\[([0-9,]*)\]")
_GROUPS_ARRAY_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_ARRAY_RE.search(line)
    if m:
        return max(1, int(m.group(2)))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(1, len(m.group(1).split(",")))
    return 1


def _ring_bytes(kind: str, result_bytes: float, k: int) -> float:
    """Per-device link traffic under ring algorithms (documented choice):
    all-reduce 2(K-1)/K·R; all-gather (K-1)/K·R (R = gathered result);
    reduce-scatter (K-1)·R (operand is K×result); all-to-all (K-1)/K·R;
    collective-permute R."""
    if kind == "collective-permute":
        return result_bytes  # no group semantics; one hop of R bytes
    if k <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (k - 1) / k * result_bytes
    if kind == "all-gather":
        return (k - 1) / k * result_bytes
    if kind == "reduce-scatter":
        return float(k - 1) * result_bytes
    return (k - 1) / k * result_bytes  # all-to-all


def collective_bytes_from_hlo(hlo_text: str, multiplier: float = 1.0) -> Dict[str, float]:
    """Per-collective-kind link bytes (per device) from optimized HLO.

    Parses result shapes + replica_groups per collective line and applies
    ring-traffic formulas. ``multiplier`` scales everything (used when a
    parsed module is one scan-body iteration executed N times).
    """
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            if f" {kind}(" not in stripped and f" {kind}-start(" not in stripped:
                continue
            m = _RESULT_RE.search(stripped)
            if not m:
                break
            rbytes = _shape_bytes(m.group(1), m.group(2))
            k = _group_size(stripped)
            out[kind] += _ring_bytes(kind, rbytes, k) * multiplier
            counts[kind] += 1
            break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["ops"] = float(sum(counts.values()))
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float               # per-device HLO FLOPs
    bytes_accessed: float      # per-device HLO bytes
    collective_bytes: float    # per-device collective operand bytes
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_total: float   # 6·N·D (global, useful work)
    useful_ratio: float        # model_flops / (flops × chips)

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(
    cost: Dict[str, Any],
    collective_bytes: float,
    n_chips: int,
    model_flops_total: float,
    hw: HW = V5E,
) -> RooflineTerms:
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    compute_s = flops / hw.peak_flops
    memory_s = bytes_accessed / hw.hbm_bw
    collective_s = collective_bytes / hw.link_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops_total / (flops * n_chips) if flops > 0 else 0.0
    return RooflineTerms(
        flops=flops,
        bytes_accessed=bytes_accessed,
        collective_bytes=collective_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops_total=model_flops_total,
        useful_ratio=useful,
    )


def count_params(params_shapes, axes) -> Dict[str, float]:
    """(total, active) parameter counts. Expert weights count active as
    top_k/num_experts of their size — set by the caller via axes marking."""
    import jax

    is_axes_leaf = lambda a: isinstance(a, tuple) and all(isinstance(s, str) for s in a)
    p_leaves = jax.tree_util.tree_leaves(params_shapes)
    a_leaves = jax.tree_util.tree_leaves(axes, is_leaf=is_axes_leaf)
    total = 0
    expert = 0
    for p, a in zip(p_leaves, a_leaves):
        n = 1
        for d in p.shape:
            n *= d
        total += n
        if "experts" in a:
            expert += n
    return {"total": float(total), "expert": float(expert)}


def model_flops(
    cfg,
    params_shapes,
    axes,
    shape_kind: str,
    tokens: int,
) -> float:
    """Useful-work FLOPs: 6·N_active·D for training, 2·N_active·D for
    inference (prefill per token; decode per generated token)."""
    counts = count_params(params_shapes, axes)
    n_active = counts["total"] - counts["expert"]
    if cfg.num_experts > 0 and counts["expert"] > 0:
        n_active += counts["expert"] * cfg.top_k / cfg.num_experts
    factor = 6.0 if shape_kind == "train" else 2.0
    return factor * n_active * tokens
