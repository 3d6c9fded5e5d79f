"""Pallas kernel validation: interpret=True vs the pure-jnp ref.py oracle.

Sweeps shapes/dtypes per the deliverable spec; codes must match bit-for-bit,
floats allclose.  The stochastic-rounding path is held to the same standard:
the in-kernel Threefry noise is counter-based, so SR codes from the kernel
must match the SR oracle bit-for-bit given the same per-slice key — plus
statistical checks (unbiasedness, bounded rounding error) and equivalence
against the unfused ``compressed()`` SR path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mappings import mapping_table
from repro.core.optimizers import adamw4bit, make_optimizer
from repro.core.optimizers.adamw import M_4BIT, V_4BIT
from repro.core.optimizers.transform import FusedAdamWRoute
from repro.core.quantizer import QuantizedTensor, quantize
from repro.kernels import ref, sr
from repro.kernels.adamw4bit import fused_adamw4
from repro.kernels.quant4 import dequantize_blockwise_4bit, quantize_blockwise_4bit

jax.config.update("jax_platform_name", "cpu")

M_TABLE = mapping_table("de", 4, signed=True)
V_TABLE = mapping_table("linear", 4, signed=False)


def _rand(shape, seed=0, scale=1.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(dtype) * scale)


# ---------------------------------------------------------------------------
# quantize / dequantize kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(128, 512), (256, 256), (8, 1024), (128, 768)])
@pytest.mark.parametrize("scale", [1e-4, 1.0, 1e3])
def test_quant_kernel_matches_ref(shape, scale):
    x = _rand(shape, seed=shape[0] + shape[1], scale=scale)
    pk, sk = quantize_blockwise_4bit(x, M_TABLE, interpret=True)
    pr, sr = ref.quant_blockwise(x, M_TABLE)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    # round trip through the dequant kernel
    xk = dequantize_blockwise_4bit(pk, sk, M_TABLE, interpret=True)
    xr = ref.dequant_blockwise(pr, sr, M_TABLE)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xr), rtol=1e-6)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_quant_kernel_dtypes(dtype):
    x = _rand((128, 512), seed=7).astype(dtype)
    pk, sk = quantize_blockwise_4bit(x, M_TABLE, interpret=True)
    pr, sr = ref.quant_blockwise(x.astype(jnp.float32), M_TABLE)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))


# ---------------------------------------------------------------------------
# fused AdamW kernel
# ---------------------------------------------------------------------------


def _mk_states(shape, seed):
    """Realistic packed m/v states built through the public quantizer."""
    from repro.core.optimizers.adamw import M_4BIT, V_4BIT

    m0 = _rand(shape, seed=seed, scale=0.01)
    v0 = jnp.abs(_rand(shape, seed=seed + 1, scale=0.001)) + 1e-10
    m_q = quantize(m0, M_4BIT)
    v_q = quantize(v0, V_4BIT)
    R, C = shape
    return m_q.codes, m_q.scales[0].reshape(R, C // 128), v_q.codes, v_q.scales


@pytest.mark.parametrize(
    "shape", [(128, 512), (256, 1024), (64, 256), (128, 768)]
)
def test_fused_adamw4_matches_ref(shape):
    R, C = shape
    w = _rand(shape, seed=1)
    g = _rand(shape, seed=2, scale=0.1)
    m_packed, m_scale, v_packed, (v_r, v_c) = _mk_states(shape, seed=3)

    hp = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    lr = jnp.float32(1e-3)
    bc1, bc2 = jnp.float32(0.1), jnp.float32(0.001)

    # oracle
    w_r, mp_r, ms_r, vp_r, vr_r, vc_r = ref.fused_adamw4_reference(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, M_TABLE, V_TABLE,
        lr, hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"], bc1, bc2,
    )
    # kernel (interpret mode executes the kernel body on CPU)
    w_k, mp_k, ms_k, vp_k = fused_adamw4(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, vr_r, vc_r,
        M_TABLE, V_TABLE, lr, bc1, bc2, interpret=True, tile_r=pick_r(R), **hp,
    )
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), rtol=2e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(mp_k), np.asarray(mp_r))
    np.testing.assert_allclose(np.asarray(ms_k), np.asarray(ms_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(vp_k), np.asarray(vp_r))


def test_fused_adamw4_bf16_params():
    shape = (128, 512)
    w = _rand(shape, seed=11).astype(jnp.bfloat16)
    g = _rand(shape, seed=12, scale=0.1)
    m_packed, m_scale, v_packed, (v_r, v_c) = _mk_states(shape, seed=13)
    lr, bc1, bc2 = jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.001)
    hp = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    w_r, *_ = ref.fused_adamw4_reference(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, M_TABLE, V_TABLE,
        lr, hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"], bc1, bc2,
    )
    vr_n = jnp.max(
        hp["b2"] * ref.dequant_rank1(v_packed, v_r, v_c, V_TABLE)
        + (1 - hp["b2"]) * g * g,
        axis=1,
    )
    vc_n = jnp.max(
        hp["b2"] * ref.dequant_rank1(v_packed, v_r, v_c, V_TABLE)
        + (1 - hp["b2"]) * g * g,
        axis=0,
    )
    w_k, *_ = fused_adamw4(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, vr_n, vc_n,
        M_TABLE, V_TABLE, lr, bc1, bc2, interpret=True, **hp,
    )
    assert w_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(w_k, np.float32), np.asarray(w_r, np.float32), rtol=2e-2
    )


# ---------------------------------------------------------------------------
# end-to-end: optimizer with use_kernel routes through the fused path
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# stochastic rounding: in-kernel threefry noise
# ---------------------------------------------------------------------------


def test_threefry_matches_jax_prng():
    """The jnp-expressed Threefry-2x32 (usable inside Pallas) must be the real
    thing: bit-identical to JAX's own implementation."""
    from jax.extend import random as jex_random

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(0, 2**32, size=(2,), dtype=np.uint32))
    c = jnp.asarray(rng.integers(0, 2**32, size=(256,), dtype=np.uint32))
    expect = jex_random.threefry_2x32(k, c)  # counts split into (c0, c1) halves
    x0, x1 = sr.threefry2x32(k[0], k[1], c[:128], c[128:])
    np.testing.assert_array_equal(
        np.asarray(expect), np.asarray(jnp.concatenate([x0, x1]))
    )


def _sr_kernel_and_ref(shape, seed_words, base_seed=3):
    R, C = shape
    w = _rand(shape, seed=base_seed)
    g = _rand(shape, seed=base_seed + 1, scale=0.1)
    m_packed, m_scale, v_packed, (v_r, v_c) = _mk_states(shape, seed=base_seed + 2)
    hp = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    lr, bc1, bc2 = jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.001)
    seed = jnp.asarray(seed_words, jnp.uint32)

    out_ref = ref.fused_adamw4_sr_reference(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, M_TABLE, V_TABLE,
        lr, hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"], bc1, bc2, seed,
    )
    w_r, mp_r, ms_r, vp_r, vr_r, vc_r = out_ref
    out_k = fused_adamw4(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, vr_r, vc_r,
        M_TABLE, V_TABLE, lr, bc1, bc2, seed,
        interpret=True, use_sr=True, tile_r=pick_r(R), **hp,
    )
    return out_ref, out_k


def pick_r(R):
    return 128 if R % 128 == 0 else 64


@pytest.mark.parametrize("shape", [(128, 512), (64, 256), (128, 768)])
def test_fused_adamw4_sr_kernel_matches_sr_reference(shape):
    """Counter-based noise => the SR kernel is bit-reproducible by the oracle:
    packed codes identical, floats allclose — not just statistically close."""
    (w_r, mp_r, ms_r, vp_r, _, _), (w_k, mp_k, ms_k, vp_k) = _sr_kernel_and_ref(
        shape, [123, 456]
    )
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_r), rtol=2e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(mp_k), np.asarray(mp_r))
    np.testing.assert_allclose(np.asarray(ms_k), np.asarray(ms_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(vp_k), np.asarray(vp_r))


@pytest.mark.parametrize("use_sr", [False, True], ids=["rtn", "sr"])
def test_fused_adamw4_3d_grid_matches_per_slice_launches(use_sr):
    """Kernel-level single-launch contract: one (L, R, C) call with (L, R)
    row stats and (L, 2) seed rows is bit-identical to L separate 2-d
    launches — the outer grid dim only selects the slice's stats/seed row,
    and the SR counter stays slice-local."""
    L, R, C = 3, 64, 512
    w = _rand((L, R, C), seed=81)
    g = _rand((L, R, C), seed=82, scale=0.1)
    m0 = _rand((L, R, C), seed=83, scale=0.01)
    v0 = jnp.abs(_rand((L, R, C), seed=84, scale=0.001)) + 1e-10
    m_q, v_q = quantize(m0, M_4BIT), quantize(v0, V_4BIT)
    m_packed = m_q.codes.reshape(L, R, C // 2)
    m_scale = m_q.scales[0].reshape(L, R, C // 128)
    v_packed = v_q.codes.reshape(L, R, C // 2)
    from repro.kernels.ops import _rank1_slice_stats
    v_r, v_c = _rank1_slice_stats(v_q.scales, (L, R, C))  # (L, R), (C,)

    hp = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    lr, bc1, bc2 = jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.001)
    v_old = jnp.stack(
        [ref.dequant_rank1(v_packed[l], v_r[l], v_c, V_TABLE) for l in range(L)]
    )
    v_new = hp["b2"] * v_old + (1 - hp["b2"]) * g * g
    v_r_new = jnp.max(v_new, axis=2)                      # (L, R)
    v_c_new = jnp.max(v_new, axis=(0, 1))                 # (C,)
    seeds = jnp.asarray([[3 * l + 1, 5 * l + 2] for l in range(L)], jnp.uint32)

    fused = fused_adamw4(
        w, g, m_packed, m_scale, v_packed, v_r, v_c, v_r_new, v_c_new,
        M_TABLE, V_TABLE, lr, bc1, bc2, seeds if use_sr else None,
        interpret=True, use_sr=use_sr, **hp,
    )
    for l in range(L):
        per_slice = fused_adamw4(
            w[l], g[l], m_packed[l], m_scale[l], v_packed[l],
            v_r[l], v_c, v_r_new[l], v_c_new,
            M_TABLE, V_TABLE, lr, bc1, bc2, seeds[l] if use_sr else None,
            interpret=True, use_sr=use_sr, **hp,
        )
        for a, b in zip(fused, per_slice):
            np.testing.assert_array_equal(np.asarray(a[l]), np.asarray(b))


def test_sr_kernel_tiling_invariant():
    """The noise is keyed on global element indices, so retiling the kernel
    must not change a single code (results independent of rows per tile)."""
    shape = (128, 512)
    w = _rand(shape, seed=31)
    g = _rand(shape, seed=32, scale=0.1)
    m_packed, m_scale, v_packed, (v_r, v_c) = _mk_states(shape, seed=33)
    hp = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    lr, bc1, bc2 = jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.001)
    v_old = ref.dequant_rank1(v_packed, v_r, v_c, V_TABLE)
    v_new = hp["b2"] * v_old + (1 - hp["b2"]) * g * g
    vr_n, vc_n = jnp.max(v_new, axis=1), jnp.max(v_new, axis=0)
    seed = jnp.asarray([7, 9], jnp.uint32)
    outs = [
        fused_adamw4(
            w, g, m_packed, m_scale, v_packed, v_r, v_c, vr_n, vc_n,
            M_TABLE, V_TABLE, lr, bc1, bc2, seed,
            interpret=True, use_sr=True, tile_r=tr, **hp,
        )
        for tr in (128, 64, 32, 8)
    ]
    for other in outs[1:]:
        for a, b in zip(outs[0], other):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sr_kernel_unbiased_with_bounded_error():
    """Statistics of the in-kernel SR requantization of m: averaging the
    dequantized first moment over many keys converges to the exact update
    (unbiasedness), and every single draw stays within its bracketing table
    interval (bounded rounding error — the 'variance bound' of SR noise)."""
    shape = (8, 256)
    n_keys = 64
    g = _rand(shape, seed=41, scale=0.1)
    m_packed, m_scale, v_packed, (v_r, v_c) = _mk_states(shape, seed=42)
    hp = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
    lr, bc1, bc2 = jnp.float32(1e-3), jnp.float32(0.1), jnp.float32(0.001)
    w = _rand(shape, seed=40)

    # exact (rounding-free) updated first moment
    m_exact = hp["b1"] * ref.dequant_blockwise(m_packed, m_scale, M_TABLE) + (
        1 - hp["b1"]
    ) * np.asarray(g)
    v_old = ref.dequant_rank1(v_packed, v_r, v_c, V_TABLE)
    v_new = hp["b2"] * v_old + (1 - hp["b2"]) * g * g
    vr_n, vc_n = jnp.max(v_new, axis=1), jnp.max(v_new, axis=0)

    deq = []
    table_np = np.asarray(M_TABLE)
    for i in range(n_keys):
        k0, k1 = sr.key_words(jax.random.PRNGKey(i))
        _, mp, ms, _ = fused_adamw4(
            w, g, m_packed, m_scale, v_packed, v_r, v_c, vr_n, vc_n,
            M_TABLE, V_TABLE, lr, bc1, bc2, jnp.stack([k0, k1]),
            interpret=True, use_sr=True, **hp,
        )
        deq.append(np.asarray(ref.dequant_blockwise(mp, ms, M_TABLE)))
        # bounded error: each draw is one of the two bracketing points, so the
        # normalized distance to the exact value never exceeds the bracket
        scale_pe = np.repeat(np.asarray(ms), 128, axis=1)
        n_exact = np.clip(m_exact / scale_pe, table_np[0], table_np[-1])
        n_drawn = deq[-1] / scale_pe
        spans = np.diff(table_np).max()
        assert np.max(np.abs(n_drawn - n_exact)) <= spans + 1e-6

    single_dev = float(np.mean([np.abs(d - m_exact).mean() for d in deq]))
    mean_bias = float(np.abs(np.mean(deq, axis=0) - m_exact).mean())
    assert single_dev > 0
    # unbiased => the 64-key average shrinks the deviation ~8x; 0.3 is slack
    assert mean_bias < 0.3 * single_dev, (mean_bias, single_dev)


def test_optimizer_kernel_sr_statistically_equivalent_to_unfused(monkeypatch):
    """The fused SR route and the unfused compressed() SR path draw different
    PRNG streams but must agree in distribution: averaged over many base
    keys, the 2-step parameter trajectories coincide far more tightly than
    any single run scatters."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
    params = {"w": _rand((32, 512), seed=50, scale=0.1)}
    g = {"w": _rand((32, 512), seed=51, scale=0.01)}

    def two_step_mean(use_kernel, n_keys=24):
        opt = adamw4bit(1e-3, stochastic_rounding=True, use_kernel=use_kernel)
        outs = []
        for i in range(n_keys):
            p, s = params, opt.init(params)
            for t in range(2):
                k = jax.random.fold_in(jax.random.PRNGKey(i), t)
                p, s = opt.update(g, s, p, key=k)
            outs.append(np.asarray(p["w"]))
        return np.mean(outs, axis=0), float(
            np.mean([np.abs(o - outs[0]).mean() for o in outs[1:]])
        )

    mean_fused, scatter = two_step_mean(True)
    mean_unfused, _ = two_step_mean(False)
    assert scatter > 0, "fused SR route produced no noise — key not plumbed?"
    gap = float(np.abs(mean_fused - mean_unfused).mean())
    assert gap < 0.5 * scatter, (gap, scatter)


# ---------------------------------------------------------------------------
# routing/eligibility
# ---------------------------------------------------------------------------


def _route(**kw):
    return FusedAdamWRoute(lr=1e-3, **kw)


def test_fused_route_eligibility_accepts_sr_and_stacked():
    m_sr = dataclasses.replace(M_4BIT, stochastic_rounding=True)
    v_sr = dataclasses.replace(V_4BIT, stochastic_rounding=True)
    p2 = jnp.zeros((16, 512))
    p3 = jnp.zeros((4, 16, 512))
    comp_rtn = {"m": quantize(p2, M_4BIT), "v": quantize(p2, V_4BIT)}
    comp_sr = {"m": quantize(p2, m_sr), "v": quantize(p2, v_sr)}
    comp_sr3 = {"m": quantize(p3, m_sr), "v": quantize(p3, v_sr)}
    route = _route()
    assert route.eligible(comp_rtn, p2)
    assert route.eligible(comp_sr, p2)           # SR now on the fast path
    assert route.eligible(comp_sr3, p3)          # stacked leading dims too
    # mixed SR flags would need two key streams per leaf — rejected
    mixed = {"m": quantize(p2, m_sr), "v": quantize(p2, V_4BIT)}
    assert not route.eligible(mixed, p2)
    # layout misfits stay off the kernel
    assert not route.eligible(comp_sr, jnp.zeros((16, 320)))  # 320 % 256 != 0
    assert not route.eligible({"m": comp_sr["m"]}, p2)        # missing v


def test_production4bit_body_leaves_route_through_kernel(monkeypatch):
    """Acceptance check: make_optimizer('production4bit') must put its 4-bit
    body leaves on the fused kernel route — SR enabled — while fp32 leaves
    and layout misfits take the unfused path."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "ref")
    from repro.kernels import ops as kernel_ops

    params = {
        "embed": _rand((64, 256), seed=60, scale=0.1),   # fp32 partition
        "body": _rand((2, 16, 512), seed=61, scale=0.1), # 4-bit, eligible
        "odd": _rand((16, 320), seed=62, scale=0.1),     # 4-bit, 320 % 256 != 0
        "bias": _rand((64,), seed=63),                   # fp32 partition
    }
    opt = make_optimizer("production4bit", 1e-3)
    state = opt.init(params)

    # the body moments are SR-configured QuantizedTensors and route-eligible
    body_state = state.states["4bit"]
    m_body = body_state["m"]["body"]
    v_body = body_state["v"]["body"]
    assert isinstance(m_body, QuantizedTensor) and m_body.config.stochastic_rounding
    route = _route()
    assert route.eligible({"m": m_body, "v": v_body}, params["body"])
    assert not route.eligible(
        {"m": body_state["m"]["odd"], "v": body_state["v"]["odd"]}, params["odd"]
    )

    seen = []
    orig = kernel_ops.fused_adamw4_leaf
    monkeypatch.setattr(
        kernel_ops,
        "fused_adamw4_leaf",
        lambda p, *a, **kw: seen.append(p.shape) or orig(p, *a, **kw),
    )
    g = {k: _rand(v.shape, seed=70, scale=0.01) for k, v in params.items()}
    p2, _ = opt.update(g, state, params, key=jax.random.PRNGKey(0))
    assert seen == [(2, 16, 512)], seen  # exactly the eligible body leaf
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree_util.tree_leaves(p2))


def test_optimizer_kernel_path_matches_reference_path(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    params = {"w": _rand((64, 512), seed=20, scale=0.1)}
    g = {"w": _rand((64, 512), seed=21, scale=0.01)}

    opt_ref = adamw4bit(1e-3, use_kernel=False)
    opt_ker = adamw4bit(1e-3, use_kernel=True)
    s_ref, s_ker = opt_ref.init(params), opt_ker.init(params)
    p_ref, p_ker = params, params
    for _ in range(3):
        p_ref, s_ref = opt_ref.update(g, s_ref, p_ref)
        p_ker, s_ker = opt_ker.update(g, s_ker, p_ker)

    np.testing.assert_allclose(
        np.asarray(p_ref["w"]), np.asarray(p_ker["w"]), rtol=3e-5, atol=1e-7
    )
    np.testing.assert_array_equal(
        np.asarray(s_ref["m"]["w"].codes), np.asarray(s_ker["m"]["w"].codes)
    )
    np.testing.assert_array_equal(
        np.asarray(s_ref["v"]["w"].codes), np.asarray(s_ker["v"]["w"].codes)
    )
