"""One benchmark per paper table/figure (Tab. 1, 2, 4, 5, 6; Fig. 3, 4/Thm 1).

All train the same small LM under identical hyperparameters, varying only the
optimizer/quantizer — the paper's ablation protocol at CPU scale.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    BENCH_CFG,
    emit,
    stacked_leaf_update_stats,
    train_small_lm,
)
from repro.core.optimizers import (
    QuantPolicy,
    make_optimizer,
    quantized_adamw,
    state_nbytes,
)
from repro.core.optimizers.adamw import M_4BIT
from repro.core.quantizer import QuantConfig, dequantize, quantize
from repro.models import init_model

LR = 3e-3


def _v_cfg(norm: str, mapping: str, block: int = 128) -> QuantConfig:
    return QuantConfig(
        bits=4, normalization=norm, block_size=block, mapping=mapping, signed=False
    )


def tab1_second_moment_ablation() -> List[Tuple[str, float, str]]:
    """Tab. 1: second-moment quantization schemes; first moment fixed B128/DE."""
    m_pol = QuantPolicy(config=M_4BIT, threshold=0)
    grid = [
        ("B2048/DE", _v_cfg("blockwise", "de", 2048), False),
        ("B128/DE", _v_cfg("blockwise", "de", 128), False),
        ("B2048/DE-0", _v_cfg("blockwise", "de0", 2048), False),
        ("B128/DE-0", _v_cfg("blockwise", "de0", 128), False),
        ("Rank-1/DE-0", _v_cfg("rank1", "de0"), False),
        ("Rank-1/Linear", _v_cfg("rank1", "linear"), False),
        ("Rank-1/Linear+Factor", _v_cfg("rank1", "linear"), True),
    ]
    rows = []
    for name, v_cfg, factored in grid:
        opt = quantized_adamw(
            LR,
            m_policy=m_pol,
            v_policy=QuantPolicy(config=v_cfg, threshold=0, factor_2d=factored),
            name=name,
        )
        r = train_small_lm(opt, steps=60)
        rows.append((
            f"tab1/{name}",
            r["us_per_step"],
            f"final_loss={r['loss_final']:.4f} unstable={int(r['unstable'])} "
            f"max_dw={r['max_param_delta']:.2f}",
        ))
    return rows


def tab2_optimizer_comparison() -> List[Tuple[str, float, str]]:
    """Tab. 2: full-precision vs memory-efficient optimizers (the production
    partition preset rides along as the quality row for fp32-embeddings +
    4-bit-SR-body training).

    The fused rows exercise the Pallas kernel route (Tab. 4's "fused"
    operator): ``4bit-AdamW-fused`` routes eligible leaves round-to-nearest,
    ``production4bit-SR`` (kernel on by default) with in-kernel stochastic
    requantization."""
    opts = [
        ("32bit-AdamW", make_optimizer("adamw32", LR), None),
        ("Adafactor", make_optimizer("adafactor", LR, b1=0.9), None),
        ("Adafactor-b1=0", make_optimizer("adafactor", LR, b1=0.0), None),
        ("SM3", make_optimizer("sm3", LR), None),
        ("8bit-AdamW", make_optimizer("adamw8bit", LR, exclude_embeddings=True), None),
        ("4bit-AdamW", make_optimizer("adamw4bit", LR), None),
        ("4bit-AdamW-fused", make_optimizer("adamw4bit", LR, use_kernel=True), None),
        ("4bit-AdamW-fused-SR",
         make_optimizer("adamw4bit", LR, stochastic_rounding=True, use_kernel=True),
         0),
        ("4bit-Factor", make_optimizer("factor4bit", LR), None),
        ("production4bit-SR", make_optimizer("production4bit", LR), 0),
        ("32bit-Shampoo", make_optimizer("shampoo32", LR), None),
        ("4bit-Shampoo", make_optimizer("shampoo4bit", LR), None),
    ]
    rows = []
    base = None
    for name, opt, sr_seed in opts:
        r = train_small_lm(opt, steps=80, sr_seed=sr_seed)
        if name == "32bit-AdamW":
            base = r["loss_final"]
        gap = r["loss_final"] - (base if base is not None else 0.0)
        rows.append((
            f"tab2/{name}",
            r["us_per_step"],
            f"final_loss={r['loss_final']:.4f} gap_vs_fp32={gap:+.4f}",
        ))
    return rows


def _gpt2m_like_params():
    """GPT-2-Medium-shaped parameter tree (~350M params) for memory tables.

    Shapes only (ShapeDtypeStruct init through eval_shape) — no allocation.
    """
    import dataclasses

    from repro.models import LayerSpec, ModelConfig

    cfg = ModelConfig(
        name="gpt2m-like", num_layers=24, d_model=1024, num_heads=16,
        num_kv_heads=16, head_dim=64, d_ff=4096, vocab_size=50257,
        blocks=(LayerSpec("dense", 0),) * 24, gated_mlp=False,
    )
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg)[0])
    return params


def tab4_memory() -> List[Tuple[str, float, str]]:
    """Tab. 4: optimizer-state memory on a GPT-2-Medium-sized model."""
    params_s = _gpt2m_like_params()
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params_s)
    )
    opts = [
        ("32bit-AdamW", make_optimizer("adamw32", LR)),
        ("8bit-AdamW", make_optimizer("adamw8bit", LR)),
        ("4bit-AdamW", make_optimizer("adamw4bit", LR)),
        ("4bit-Factor", make_optimizer("factor4bit", LR)),
        ("production4bit", make_optimizer("production4bit", LR)),
        ("Adafactor-b1=0", make_optimizer("adafactor", LR, b1=0.0)),
        ("SM3", make_optimizer("sm3", LR)),
        ("32bit-Shampoo", make_optimizer("shampoo32", LR)),
        ("4bit-Shampoo", make_optimizer("shampoo4bit", LR)),
    ]
    rows = []
    base = None
    for name, opt in opts:
        state_s = jax.eval_shape(lambda o=opt: o.init(
            jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), params_s)
        ))
        nbytes = state_nbytes(state_s)
        if name == "32bit-AdamW":
            base = nbytes
        saved = (base - nbytes) / base * 100 if base else 0.0
        rows.append((
            f"tab4/{name}",
            0.0,
            f"state_bytes={nbytes} bytes_per_param={nbytes/n_params:.3f} "
            f"saved_vs_fp32={saved:.1f}%",
        ))
    return rows


def tab5_largest_trainable() -> List[Tuple[str, float, str]]:
    """Tab. 5: largest trainable model under a fixed memory budget.

    Per-param training cost: params fp32 + grads fp32 + states; 80 GB budget
    (matching the paper's A100 setting) and a 30% activation reserve."""
    budget = 80e9 * 0.7
    per_param = {
        "32bit-AdamW": 4 + 4 + 8.0,
        "8bit-AdamW": 4 + 4 + 2.0,
        "4bit-AdamW": 4 + 4 + 1.0 + 0.09,  # + scale overhead
        "4bit-Factor": 4 + 4 + 0.5 + 0.05,
    }
    rows = []
    for name, ppb in per_param.items():
        largest = budget / ppb / 1e9
        rows.append((f"tab5/{name}", 0.0, f"largest_trainable={largest:.2f}B_params"))
    return rows


def tab6_moment_ablation() -> List[Tuple[str, float, str]]:
    """Tab. 6: which moment is compressed."""
    m128 = QuantPolicy(config=M_4BIT, threshold=0)
    m2048 = QuantPolicy(
        config=QuantConfig(bits=4, normalization="blockwise", block_size=2048,
                           mapping="de", signed=True),
        threshold=0,
    )
    v_r1lin = QuantPolicy(config=_v_cfg("rank1", "linear"), threshold=0)
    grid = [
        ("none", QuantPolicy(), QuantPolicy(), False),
        ("m:B2048/DE", m2048, QuantPolicy(), False),
        ("m:B128/DE", m128, QuantPolicy(), False),
        ("m:B128/DE+v:Rank1/Lin", m128, v_r1lin, False),
        ("m:B128/DE+v:factored", m128,
         QuantPolicy(config=_v_cfg("rank1", "linear"), threshold=0, factor_2d=True),
         True),
    ]
    rows = []
    for name, m_pol, v_pol, _ in grid:
        opt = quantized_adamw(LR, m_policy=m_pol, v_policy=v_pol, name=name)
        r = train_small_lm(opt, steps=80)
        rows.append((
            f"tab6/{name}", r["us_per_step"],
            f"final_loss={r['loss_final']:.4f}",
        ))
    return rows


def fig3_zero_point() -> List[Tuple[str, float, str]]:
    """Fig. 3: histogram of h(v)=1/(sqrt(v)+1e-6) under quantizers."""
    rng = np.random.default_rng(0)
    # realistic second moment: row-structured lognormal (App. B patterns)
    rowscale = 10.0 ** rng.uniform(-6, -2, size=(256, 1))
    v = jnp.asarray(
        (rng.lognormal(0, 1.0, size=(256, 1024)) * rowscale).astype(np.float32)
    )
    h = lambda t: 1.0 / (jnp.sqrt(t) + 1e-6)
    rows = []
    for name, cfg in [
        ("B128/DE", _v_cfg("blockwise", "de")),
        ("B128/DE-0", _v_cfg("blockwise", "de0")),
        ("Rank-1/Linear", _v_cfg("rank1", "linear")),
    ]:
        vq = dequantize(quantize(v, cfg))
        collapsed = float(jnp.mean(vq == 0.0))
        err = jnp.abs(jnp.log10(h(vq)) - jnp.log10(h(v)))
        rows.append((
            f"fig3/{name}", 0.0,
            f"frac_zero={collapsed:.4f} h_log10_err_mean={float(jnp.mean(err)):.4f} "
            f"h_log10_err_p99={float(jnp.percentile(err, 99)):.4f}",
        ))
    return rows


def thm1_sgdm_convergence() -> List[Tuple[str, float, str]]:
    """Theorem 1: compressed SGDM on a convex quadratic converges to a noise
    ball whose radius grows with quantization variance."""
    rng = np.random.default_rng(1)
    dim = 8192
    target = jnp.asarray(rng.normal(size=(1, dim)).astype(np.float32))
    params = {"w": jnp.zeros((1, dim))}

    def run(opt, key=None, steps=150):
        state = opt.init(params)
        p = params
        upd = jax.jit(opt.update)
        for t in range(steps):
            g = {"w": (p["w"] - target) + 0.01 * jnp.asarray(
                np.random.default_rng(t).normal(size=(1, dim)).astype(np.float32))}
            k = jax.random.fold_in(key, t) if key is not None else None
            p, state = (upd(g, state, p, key=k) if k is not None else upd(g, state, p))
        return float(jnp.mean((p["w"] - target) ** 2))

    e32 = run(make_optimizer("sgdm", 5e-2))
    e4 = run(make_optimizer("sgdm4bit", 5e-2), key=jax.random.PRNGKey(0))
    return [
        ("thm1/sgdm32", 0.0, f"final_mse={e32:.6f}"),
        ("thm1/sgdm4bit_sr", 0.0,
         f"final_mse={e4:.6f} ratio_vs_fp32={e4/max(e32,1e-12):.2f}"),
    ]


def stacked_fused_steptime() -> List[Tuple[str, float, str]]:
    """Stacked-leaf fused update: an L=24 transformer-block stack must run as
    ONE Pallas launch (the ROADMAP "fuse the stacked-leaf loop"
    item) — the row records the launch count and the SR step wall-clock."""
    s = stacked_leaf_update_stats()
    return [(
        f"stacked/L{s['L']}x{s['R']}x{s['C']}-fused-SR",
        s["us_per_step"],
        f"pallas_launches={s['launch_count']} (single launch; "
        f"was {s['L']} per-slice launches)",
    )]


def grad_comm_wire() -> List[Tuple[str, float, str]]:
    """Gradient-collective bytes on the wire per train step (``repro.comms``)
    for the GPT-2-M gradient tree — structural, computed from shapes alone.

    fp32 is the baseline collective; bf16 halves it; int8/int4 move
    block-quantized codes + fp32 absmax scales (B128), with sub-threshold
    leaves (biases, norms) kept fp32 (App. D.1 policy)."""
    from repro.comms import mode_totals

    params_s = _gpt2m_like_params()
    rows = []
    for r in mode_totals(params_s):
        rows.append((
            f"comms/{r['mode']}",
            0.0,
            f"wire_bytes={r['total_wire_bytes']} "
            f"ratio_vs_fp32={r['ratio_vs_fp32']:.2f} "
            f"quantized_leaves={r['quantized_leaves']}/{r['n_leaves']}",
        ))
    return rows


def serving_throughput() -> List[Tuple[str, float, str]]:
    """Serving engine tokens/sec/slot vs the legacy per-token host-sync loop,
    plus the structural q4 weight-byte row (``benchmarks/serving.py``)."""
    from benchmarks.serving import serving_throughput as rows

    return rows()


ALL_TABLES = [
    tab1_second_moment_ablation,
    tab2_optimizer_comparison,
    tab4_memory,
    tab5_largest_trainable,
    tab6_moment_ablation,
    fig3_zero_point,
    thm1_sgdm_convergence,
    stacked_fused_steptime,
    grad_comm_wire,
    serving_throughput,
]
