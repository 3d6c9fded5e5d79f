"""Tracked production-preset drift metrics (ROADMAP drift-tracking item).

``production_metrics()`` distills the benchmark suite's production rows into
a small deterministic JSON-able dict:

* quality — final loss of ``adamw32`` vs ``production4bit`` (SR seed 0, so
  the kernel-routed SR body runs with real quantization noise) on the shared
  bench LM, and their gap.  Fully deterministic on a fixed platform: data,
  init and SR stream are all seeded.
* memory — optimizer-state bytes on the GPT-2-Medium-shaped tree
  (``eval_shape`` only, no allocation) and the production/fp32 ratio.
  Structural, so it must reproduce exactly anywhere.
* stacked — the fused stacked-leaf update on an L=24 transformer-block
  stack: the Pallas launch count (structural; gated EXACTLY at its baseline
  of 1 — the single-launch invariant) and the step wall-clock,
  gated within a ±25% relative band of the baseline: wide enough for CI
  machine noise, tight enough that a silent 2x slowdown (or the ~20%
  regression that once landed unnoticed) fails the job instead of merging.
* comms — the quantized-gradient-communication quality row: production4bit
  trained with the int4 gradient-collective wire format vs the fp32
  collective (same SR seed), plus the structural bytes-on-the-wire figures
  for the GPT-2-M gradient tree.  The loss gap is gated like quality; the
  wire bytes are exact and the compression ratio must stay >= 4x (the
  acceptance floor for int4 transport).
* serving — the throughput engine vs the legacy per-token host-sync loop
  on the same workload (``benchmarks/serving.py``), plus the structural
  bf16/q4 weight-byte figures for the GPT-2-M tree.  Absolute tok/s/slot
  is recorded for the trajectory only (CI machines vary); the engine/legacy
  speedup must hold the >= 3x floor and the q4 weight-compression ratio
  the >= 3.5x floor.  Weight bytes are exact.

* shampoo — the 4-bit Shampoo quality gap vs the fp32 Shampoo oracle on the
  same bench LM (gated like quality), plus the structural
  Kronecker-factor bytes on the GPT-2-M tree and their compression ratio,
  floored at >= 4x (the ISSUE 10 acceptance criterion).

``compare()`` checks a freshly computed dict against the tracked baseline
(``benchmarks/results/baseline.json``) within tolerances; the CI job
(``scripts_check_drift.py``) fails on violations, catching quality/memory
regressions of the production preset over time.
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import stacked_leaf_update_stats, train_small_lm
from benchmarks.tables import _gpt2m_like_params
from repro.comms import CommsConfig, wire_report
from repro.core.optimizers import make_optimizer, state_nbytes

DEFAULT_STEPS = 80
SR_SEED = 0

# |gap drift| tolerance in nats: generous enough for BLAS/platform jitter on
# an 80-step micro-LM, tight enough to catch a real quality regression of the
# 4-bit body (which shows up as multiples of this on divergence).
LOSS_GAP_TOL = 0.08
# memory ratio is structural; anything beyond fp rounding is a layout change
MEMORY_RATIO_TOL = 1e-3
# stacked us_per_step band: relative drift vs baseline before failing.
STEP_TIME_REL_TOL = 0.25
# int4 transport must keep at least this much compression on the wire.
COMMS_MIN_RATIO = 4.0
# chunked-decode engine must stay at least this much faster than the legacy
# per-token host-sync loop (measured ~12x on CPU; 3x is the acceptance floor).
SERVING_MIN_SPEEDUP = 3.0
# q4 serving weights must keep at least this much compression vs bf16.
SERVING_MIN_Q4_RATIO = 3.5
# 4-bit Kronecker factors must cut preconditioner bytes at least this much
# vs the fp32 Shampoo oracle (ISSUE 10 acceptance floor; structural).
SHAMPOO_MIN_FACTOR_RATIO = 4.0


def production_metrics(steps: int = DEFAULT_STEPS) -> Dict:
    """Compute the tracked quality/memory numbers (deterministic per platform)."""
    r32 = train_small_lm(make_optimizer("adamw32", 3e-3), steps=steps)
    rprod = train_small_lm(
        make_optimizer("production4bit", 3e-3), steps=steps, sr_seed=SR_SEED
    )

    params_s = _gpt2m_like_params()
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params_s)
    )

    def state_bytes(name):
        opt = make_optimizer(name, 3e-3)
        state_s = jax.eval_shape(
            lambda: opt.init(
                jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), params_s
                )
            )
        )
        return state_nbytes(state_s)

    b32 = state_bytes("adamw32")
    bprod = state_bytes("production4bit")
    stacked = stacked_leaf_update_stats()

    # Quantized gradient communication: same production preset, same SR
    # seed, only the gradient-collective wire format changes (fp32 -> int4
    # block-quantized transport).  The single-process harness applies
    # exactly the quantization numerics a mesh run pays on the wire.
    int4 = CommsConfig(mode="int4")
    rint4 = train_small_lm(
        make_optimizer("production4bit", 3e-3), steps=steps, sr_seed=SR_SEED,
        comms=int4,
    )
    wire = wire_report(params_s, int4)

    # Serving: chunked-decode engine vs the legacy host-sync loop, plus the
    # structural bf16/q4 weight bytes on the same GPT-2-M tree.
    from benchmarks.serving import serving_stats

    serving = serving_stats()

    # 4-bit Shampoo: quality gap vs the fp32 Shampoo oracle on the bench LM
    # (deterministic: seeded data/init, round-to-nearest factors), plus the
    # structural preconditioner-byte ratio on the GPT-2-M tree — the four
    # Kronecker-factor trees (stats_l/stats_r/precond_l/precond_r) only.
    rsh32 = train_small_lm(make_optimizer("shampoo32", 3e-3), steps=steps)
    rsh4 = train_small_lm(make_optimizer("shampoo4bit", 3e-3), steps=steps)

    def factor_bytes(name):
        opt = make_optimizer(name, 3e-3)
        state_s = jax.eval_shape(
            lambda: opt.init(
                jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), params_s
                )
            )
        )
        return sum(
            state_nbytes(state_s[f])
            for f in ("stats_l", "stats_r", "precond_l", "precond_r")
        )

    fb32 = factor_bytes("shampoo32")
    fb4 = factor_bytes("shampoo4bit")
    shampoo = {
        "shampoo32_loss": round(rsh32["loss_final"], 6),
        "shampoo4bit_loss": round(rsh4["loss_final"], 6),
        "gap": round(rsh4["loss_final"] - rsh32["loss_final"], 6),
        "shampoo4bit_unstable": bool(rsh4["unstable"]),
        "fp32_factor_bytes": int(fb32),
        "q4_factor_bytes": int(fb4),
        "factor_ratio": round(fb32 / fb4, 6),
    }
    return {
        "meta": {"steps": steps, "sr_seed": SR_SEED, "lr": 3e-3},
        "quality": {
            "adamw32_loss": round(r32["loss_final"], 6),
            "production4bit_loss": round(rprod["loss_final"], 6),
            "gap": round(rprod["loss_final"] - r32["loss_final"], 6),
            "production4bit_unstable": bool(rprod["unstable"]),
        },
        "memory": {
            "n_params": n_params,
            "adamw32_state_bytes": int(b32),
            "production4bit_state_bytes": int(bprod),
            "ratio": round(bprod / b32, 6),
        },
        "stacked": {
            "L": stacked["L"],
            "R": stacked["R"],
            "C": stacked["C"],
            "launch_count": stacked["launch_count"],
            "us_per_step": round(stacked["us_per_step"], 1),
        },
        "comms": {
            "mode": int4.name,
            "int4_loss": round(rint4["loss_final"], 6),
            "gap_vs_fp32_comm": round(
                rint4["loss_final"] - rprod["loss_final"], 6
            ),
            "int4_unstable": bool(rint4["unstable"]),
            "wire_bytes": wire["total_wire_bytes"],
            "fp32_wire_bytes": wire["total_fp32_bytes"],
            "ratio_vs_fp32": wire["ratio_vs_fp32"],
        },
        "serving": serving,
        "shampoo": shampoo,
    }


def compare(
    current: Dict,
    baseline: Dict,
    *,
    loss_gap_tol: float = LOSS_GAP_TOL,
    memory_ratio_tol: float = MEMORY_RATIO_TOL,
    step_time_rel_tol: float = STEP_TIME_REL_TOL,
) -> List[str]:
    """Return human-readable violations of ``current`` vs ``baseline``."""
    violations = []
    if current["meta"]["steps"] != baseline["meta"]["steps"]:
        violations.append(
            f"meta.steps mismatch: current {current['meta']['steps']} vs "
            f"baseline {baseline['meta']['steps']} — regenerate with matching "
            "--steps or --update the baseline"
        )
        return violations

    if current["quality"]["production4bit_unstable"]:
        violations.append("production4bit run went unstable (nonfinite/blowup)")

    gap_cur = current["quality"]["gap"]
    gap_base = baseline["quality"]["gap"]
    if abs(gap_cur - gap_base) > loss_gap_tol:
        violations.append(
            "quality gap (production4bit - adamw32 final loss) drifted: "
            f"{gap_cur:+.4f} vs baseline {gap_base:+.4f} "
            f"(tol {loss_gap_tol})"
        )

    for key in ("adamw32_state_bytes", "production4bit_state_bytes", "n_params"):
        if current["memory"][key] != baseline["memory"][key]:
            violations.append(
                f"memory.{key} changed: {current['memory'][key]} vs "
                f"baseline {baseline['memory'][key]} — state layout drift"
            )
    if abs(current["memory"]["ratio"] - baseline["memory"]["ratio"]) > memory_ratio_tol:
        violations.append(
            f"memory ratio drifted: {current['memory']['ratio']:.6f} vs "
            f"baseline {baseline['memory']['ratio']:.6f}"
        )

    # The single-launch invariant: launch count is structural and gated
    # exactly; us_per_step is gated within a relative band (a ~20% L=24
    # slowdown once merged silently when the figure was trajectory-only).
    # A baseline without the section is tolerated (pre-gate baselines), but
    # once the baseline records it, a current run missing it means the gate
    # silently stopped executing — that is itself a violation.
    base_st = baseline.get("stacked")
    cur_st = current.get("stacked")
    if base_st and not cur_st:
        violations.append(
            "stacked metrics missing from the current run — the launch-count "
            "gate did not execute (baseline still records it)"
        )
    elif base_st and cur_st:
        for key in ("L", "R", "C", "launch_count"):
            if cur_st[key] != base_st[key]:
                violations.append(
                    f"stacked.{key} changed: {cur_st[key]} vs baseline "
                    f"{base_st[key]} — the fused stacked-leaf path regressed "
                    "(single launch)"
                )
        base_us = base_st.get("us_per_step")
        cur_us = cur_st.get("us_per_step")
        if base_us and cur_us:
            rel = (cur_us - base_us) / base_us
            if abs(rel) > step_time_rel_tol:
                violations.append(
                    f"stacked.us_per_step drifted {rel:+.0%}: {cur_us:.1f} vs "
                    f"baseline {base_us:.1f} (band ±{step_time_rel_tol:.0%}) — "
                    "regenerate the baseline with --update if intentional"
                )

    # Quantized gradient communication: the int4-transport quality gap is
    # gated like the optimizer quality gap; wire bytes are structural
    # (exact), and the compression ratio must hold the >= 4x floor.
    base_cm = baseline.get("comms")
    cur_cm = current.get("comms")
    if base_cm and not cur_cm:
        violations.append(
            "comms metrics missing from the current run — the quantized "
            "grad-comm gate did not execute (baseline still records it)"
        )
    elif base_cm and cur_cm:
        if cur_cm["int4_unstable"]:
            violations.append(
                "production4bit + int4 grad-comm run went unstable"
            )
        if abs(cur_cm["gap_vs_fp32_comm"] - base_cm["gap_vs_fp32_comm"]) > loss_gap_tol:
            violations.append(
                "comms quality gap (int4 vs fp32 gradient collective) "
                f"drifted: {cur_cm['gap_vs_fp32_comm']:+.4f} vs baseline "
                f"{base_cm['gap_vs_fp32_comm']:+.4f} (tol {loss_gap_tol})"
            )
        for key in ("wire_bytes", "fp32_wire_bytes"):
            if cur_cm[key] != base_cm[key]:
                violations.append(
                    f"comms.{key} changed: {cur_cm[key]} vs baseline "
                    f"{base_cm[key]} — wire format drift"
                )
        if cur_cm["ratio_vs_fp32"] < COMMS_MIN_RATIO:
            violations.append(
                f"comms compression ratio {cur_cm['ratio_vs_fp32']:.2f}x fell "
                f"below the {COMMS_MIN_RATIO:.0f}x floor for int4 transport"
            )

    # Serving: absolute tok/s/slot is trajectory-only (machine-dependent);
    # the engine/legacy speedup and the q4 weight ratio are floored, and the
    # structural weight bytes are exact.
    base_sv = baseline.get("serving")
    cur_sv = current.get("serving")
    if base_sv and not cur_sv:
        violations.append(
            "serving metrics missing from the current run — the serving "
            "throughput gate did not execute (baseline still records it)"
        )
    elif base_sv and cur_sv:
        if cur_sv["speedup_vs_host_sync_loop"] < SERVING_MIN_SPEEDUP:
            violations.append(
                "serving engine speedup over the per-token host-sync loop "
                f"fell to {cur_sv['speedup_vs_host_sync_loop']:.2f}x, below "
                f"the {SERVING_MIN_SPEEDUP:.0f}x floor — chunked decode "
                "regressed (extra syncs or lost scan fusion)"
            )
        for key in ("bf16_weight_bytes", "q4_weight_bytes"):
            if cur_sv[key] != base_sv[key]:
                violations.append(
                    f"serving.{key} changed: {cur_sv[key]} vs baseline "
                    f"{base_sv[key]} — serving weight-format drift"
                )
        if cur_sv["q4_ratio_vs_bf16"] < SERVING_MIN_Q4_RATIO:
            violations.append(
                f"serving q4 weight compression {cur_sv['q4_ratio_vs_bf16']:.2f}x "
                f"fell below the {SERVING_MIN_Q4_RATIO:.1f}x floor vs bf16"
            )

    # 4-bit Shampoo: the quality gap vs the fp32 oracle is gated like the
    # production quality gap; factor bytes are structural (exact) and the
    # compression ratio must hold the >= 4x acceptance floor.
    base_sh = baseline.get("shampoo")
    cur_sh = current.get("shampoo")
    if base_sh and not cur_sh:
        violations.append(
            "shampoo metrics missing from the current run — the 4-bit "
            "Shampoo gate did not execute (baseline still records it)"
        )
    elif base_sh and cur_sh:
        if cur_sh["shampoo4bit_unstable"]:
            violations.append("shampoo4bit run went unstable (nonfinite/blowup)")
        if abs(cur_sh["gap"] - base_sh["gap"]) > loss_gap_tol:
            violations.append(
                "shampoo quality gap (shampoo4bit - shampoo32 final loss) "
                f"drifted: {cur_sh['gap']:+.4f} vs baseline "
                f"{base_sh['gap']:+.4f} (tol {loss_gap_tol})"
            )
        for key in ("fp32_factor_bytes", "q4_factor_bytes"):
            if cur_sh[key] != base_sh[key]:
                violations.append(
                    f"shampoo.{key} changed: {cur_sh[key]} vs baseline "
                    f"{base_sh[key]} — Kronecker-factor layout drift"
                )
        if cur_sh["factor_ratio"] < SHAMPOO_MIN_FACTOR_RATIO:
            violations.append(
                f"shampoo factor compression {cur_sh['factor_ratio']:.2f}x "
                f"fell below the {SHAMPOO_MIN_FACTOR_RATIO:.0f}x floor for "
                "4-bit Kronecker factors"
            )
    return violations
