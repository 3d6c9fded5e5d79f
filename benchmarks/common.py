"""Shared benchmark harness: a small LM trained on the synthetic pipeline.

Every table benchmark trains the same ~6M-param transformer under identical
hyperparameters and varies only the optimizer/quantizer — the paper's
protocol ("out-of-box transfer from full-precision optimizer to low-bit
optimizer without extra hyperparameter tuning").
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import LayerSpec, ModelConfig, init_model, loss_fn
from repro.train.train_loop import build_train_step, make_train_state

# d_ff is a multiple of 256 so the mlp w1/w3 leaves (2, 128, 512) satisfy the
# fused-kernel layout contract — the tab2 fused rows and the production preset
# actually exercise the Pallas route instead of silently falling back.
BENCH_CFG = ModelConfig(
    name="bench-lm",
    num_layers=2,
    d_model=128,
    num_heads=4,
    num_kv_heads=2,
    head_dim=32,
    d_ff=512,
    vocab_size=512,
    blocks=(LayerSpec("dense", 0),) * 2,
    remat=False,
)

DATA_CFG = DataConfig(vocab_size=512, seq_len=64, global_batch=16, seed=0)


def train_small_lm(optimizer, steps: int = 150, cfg: ModelConfig = BENCH_CFG,
                   seed: int = 0, sr_seed: int = None,
                   comms=None) -> Dict[str, float]:
    """Train the benchmark LM; returns summary metrics.

    ``sr_seed`` threads a stochastic-rounding PRNG key through the train
    step (needed for SR optimizers to actually round stochastically).
    ``comms`` (a ``repro.comms.CommsConfig``) selects the gradient-collective
    wire format; on this single-process harness quantized modes apply
    exactly the transport-quantization numerics a mesh run pays."""
    params, _ = init_model(jax.random.PRNGKey(seed), cfg)
    p0 = jax.tree_util.tree_map(lambda x: np.asarray(x), params)
    key = jax.random.PRNGKey(sr_seed) if sr_seed is not None else None
    state = make_train_state(params, optimizer, key=key)
    step_fn = jax.jit(build_train_step(cfg, optimizer, comms=comms))
    data = SyntheticLM(DATA_CFG)

    losses: List[float] = []
    t0 = time.perf_counter()
    for t in range(steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(t).items()}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    wall = time.perf_counter() - t0

    max_delta = max(
        float(np.max(np.abs(np.asarray(b).astype(np.float32) - a.astype(np.float32))))
        for a, b in zip(
            jax.tree_util.tree_leaves(p0), jax.tree_util.tree_leaves(state.params)
        )
    )
    window = max(1, len(losses) // 10)
    return {
        "loss_first": float(np.mean(losses[:window])),
        "loss_final": float(np.mean(losses[-window:])),
        "unstable": float(not np.isfinite(losses).all() or max_delta > 50.0),
        "max_param_delta": max_delta,
        "us_per_step": wall / steps * 1e6,
    }


def emit(rows: List[Tuple[str, float, str]]):
    """Print the required ``name,us_per_call,derived`` CSV rows."""
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def stacked_leaf_update_stats(
    L: int = 24, R: int = 128, C: int = 512, steps: int = 20
) -> Dict[str, float]:
    """Fused stacked-leaf step metrics for an (L, R, C) transformer-block
    stack — e.g. L=24 is a 24-layer stack of d_model=128 / d_ff=512 blocks.

    Returns the Pallas launch count (structural: traced under the interpret
    kernel backend, so it is the same figure a TPU run would launch) and the
    wall-clock of the jitted leaf update on the default backend (``ref`` on
    CPU — same trace shape, honest step timing).  The launch count is the
    drift-gated number: it must stay 1 (the single-launch
    invariant); wall-clock is recorded for the trajectory but not gated
    (CI machines are too noisy for exact step-time equality).
    """
    from repro.core.optimizers.adamw import M_4BIT, V_4BIT
    from repro.core.quantizer import quantize
    from repro.kernels import ops as kernel_ops

    rng = np.random.default_rng(0)
    m_cfg = dataclasses.replace(M_4BIT, stochastic_rounding=True)
    v_cfg = dataclasses.replace(V_4BIT, stochastic_rounding=True)
    p = jnp.asarray(rng.normal(size=(L, R, C)).astype(np.float32) * 0.1)
    g = jnp.asarray(rng.normal(size=(L, R, C)).astype(np.float32) * 0.01)
    m_q = quantize(
        jnp.asarray(rng.normal(size=(L, R, C)).astype(np.float32) * 0.01), m_cfg
    )
    v_q = quantize(
        jnp.abs(jnp.asarray(rng.normal(size=(L, R, C)).astype(np.float32)))
        * 1e-3
        + 1e-10,
        v_cfg,
    )
    lr, bc1, bc2 = jnp.float32(3e-3), jnp.float32(0.1), jnp.float32(0.001)

    def step(p, g, m_q, v_q, key):
        return kernel_ops.fused_adamw4_leaf(
            p, g, m_q, v_q, lr, 0.9, 0.999, 1e-8, 0.01, bc1, bc2, key=key
        )

    key = jax.random.PRNGKey(0)

    # Launch count: trace with the kernel routed (interpret backend) — the
    # number of pallas_call equations is what a compiled TPU step launches.
    saved = os.environ.get("REPRO_KERNEL_BACKEND")
    os.environ["REPRO_KERNEL_BACKEND"] = "interpret"
    try:
        jaxpr = jax.make_jaxpr(step)(p, g, m_q, v_q, key)
        launches = kernel_ops.count_pallas_calls(jaxpr)
    finally:
        if saved is None:
            del os.environ["REPRO_KERNEL_BACKEND"]
        else:
            os.environ["REPRO_KERNEL_BACKEND"] = saved

    fn = jax.jit(step)
    jax.block_until_ready(fn(p, g, m_q, v_q, key))  # compile
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(p, g, m_q, v_q, key)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    return {
        "L": L,
        "R": R,
        "C": C,
        "launch_count": int(launches),
        "us_per_step": wall / steps * 1e6,
    }
