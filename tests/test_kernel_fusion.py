"""Single-launch fused kernel: stacked-leaf bit-exactness + trace gates.

Two invariants, both CI-enforced in the ``kernel-parity`` matrix:

1. **Bit-exactness** — ``fused_adamw4_leaf`` on a stacked ``(L, R, C)`` leaf
   must produce codes/scales/params bit-identical to the FROZEN historical
   per-slice implementation (one 2-d launch / oracle call per leading-dim
   slice, per-slice keys from sequential ``fold_in``), for RTN and SR,
   L in {1, 3, 8}, on both the ``ref`` and ``interpret`` backends.
2. **Trace size** — an ndim>=3 leaf traces exactly ONE ``pallas_call``
   (kernel backends), and the ``ref`` backend's equation count is independent
   of L (vmap, not Python unrolling).  This is the regression gate for the
   ROADMAP "fuse the stacked-leaf loop" item: a reintroduced per-slice loop
   fails here, not on a TPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.optimizers.adamw import M_4BIT, V_4BIT
from repro.core.quantizer import quantize
from repro.kernels import ops, ref
from repro.kernels.adamw4bit import fused_adamw4
from repro.kernels.sr import key_words

jax.config.update("jax_platform_name", "cpu")

HP = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)
LR, BC1, BC2 = 1e-3, 0.1, 0.001


def _rand(shape, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


def _mk_leaf(L, R=16, C=256, sr=False, seed=0):
    m_cfg = dataclasses.replace(M_4BIT, stochastic_rounding=sr)
    v_cfg = dataclasses.replace(V_4BIT, stochastic_rounding=sr)
    p = _rand((L, R, C), seed, 0.1)
    g = _rand((L, R, C), seed + 1, 0.01)
    m_q = quantize(_rand((L, R, C), seed + 2, 0.01), m_cfg)
    v_q = quantize(jnp.abs(_rand((L, R, C), seed + 3, 0.001)) + 1e-10, v_cfg)
    return p, g, m_q, v_q


def _frozen_per_slice_leaf(p, g, m_s, v_s, backend, key):
    """The pre-fusion ``ops.fused_adamw4_leaf``, frozen verbatim: a Python
    ``for l in range(L)`` loop of 2-d launches (interpret) / oracle calls
    (ref), slice keys from sequential ``fold_in(leaf_key, l)``.  The new
    single-launch path must reproduce its outputs bit-for-bit."""
    shape = p.shape
    R, C = shape[-2], shape[-1]
    L = p.size // (R * C)
    use_sr = bool(m_s.config.stochastic_rounding) and key is not None
    m_table, v_table = m_s.config.table(), v_s.config.table()
    lr, bc1, bc2 = jnp.float32(LR), jnp.float32(BC1), jnp.float32(BC2)

    p3 = p.reshape(L, R, C)
    g3 = g.astype(jnp.float32).reshape(L, R, C)
    m_packed = m_s.codes.reshape(L, R, C // 2)
    m_scale = m_s.scales[0].reshape(L, R, C // 128)
    v_packed = v_s.codes.reshape(L, R, C // 2)
    v_r, v_c = ops._rank1_slice_stats(v_s.scales, shape)

    v_old = jnp.stack(
        [ref.dequant_rank1(v_packed[l], v_r[l], v_c, v_table) for l in range(L)]
    )
    v_new = HP["b2"] * v_old + (1.0 - HP["b2"]) * g3 * g3
    new_stats = ops._rank1_new_stats(v_new.reshape(shape))
    v_r_new, v_c_new = ops._rank1_slice_stats(new_stats, shape)

    slice_keys = (
        [key_words(jax.random.fold_in(key, l)) for l in range(L)]
        if use_sr
        else [None] * L
    )

    outs = []
    for l in range(L):
        if backend == "ref":
            if use_sr:
                o = ref.fused_adamw4_sr_reference(
                    p3[l], g3[l], m_packed[l], m_scale[l], v_packed[l],
                    v_r[l], v_c, m_table, v_table,
                    lr, HP["b1"], HP["b2"], HP["eps"], HP["weight_decay"],
                    bc1, bc2, jnp.stack(slice_keys[l]), v_r_new[l], v_c_new,
                )[:4]
            else:
                o = ref.fused_adamw4_reference(
                    p3[l], g3[l], m_packed[l], m_scale[l], v_packed[l],
                    v_r[l], v_c, m_table, v_table,
                    lr, HP["b1"], HP["b2"], HP["eps"], HP["weight_decay"],
                    bc1, bc2, v_r_new[l], v_c_new,
                )[:4]
        else:
            seed = jnp.stack(slice_keys[l]) if use_sr else None
            o = fused_adamw4(
                p3[l], g3[l], m_packed[l], m_scale[l], v_packed[l],
                v_r[l], v_c, v_r_new[l], v_c_new,
                m_table, v_table, lr, bc1, bc2, seed,
                interpret=True, use_sr=use_sr, **HP,
            )
        outs.append(o)
    w3, mp3, ms3, vp3 = (jnp.stack(x) for x in zip(*outs))
    return w3.reshape(shape), mp3, ms3, vp3


def _run_new_leaf(p, g, m_q, v_q, key):
    return ops.fused_adamw4_leaf(
        p, g, m_q, v_q, jnp.float32(LR),
        HP["b1"], HP["b2"], HP["eps"], HP["weight_decay"],
        jnp.float32(BC1), jnp.float32(BC2), key=key,
    )


def _assert_bits_equal(a, b):
    """Bitwise equality, floats included (uint32 view — not just allclose)."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    if a.dtype == np.float32:
        np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
    else:
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# stacked-leaf bit-exactness: new single-launch vs frozen per-slice loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("use_sr", [False, True], ids=["rtn", "sr"])
@pytest.mark.parametrize("L", [1, 3, 8])
def test_stacked_leaf_bit_identical_to_per_slice_loop(
    monkeypatch, backend, use_sr, L
):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    p, g, m_q, v_q = _mk_leaf(L, sr=use_sr, seed=11 * L)
    key = jax.random.PRNGKey(7) if use_sr else None

    w_new, m2, v2 = _run_new_leaf(p, g, m_q, v_q, key)
    fw, fmp, fms, fvp = _frozen_per_slice_leaf(p, g, m_q, v_q, backend, key)

    _assert_bits_equal(w_new, fw)
    _assert_bits_equal(m2.codes, fmp.reshape(m2.codes.shape))
    _assert_bits_equal(m2.scales[0], fms.reshape(m2.scales[0].shape))
    _assert_bits_equal(v2.codes, fvp.reshape(v2.codes.shape))


def test_2d_leaf_unchanged(monkeypatch):
    """Plain 2-d leaves (no stacking) ride the same single launch, outputs
    bit-identical to the historical 2-d path."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    p3, g3, m_q3, v_q3 = _mk_leaf(1, sr=True, seed=5)
    p, g = p3[0], g3[0]
    m_q = quantize(
        _rand((1, 16, 256), 7, 0.01)[0],
        dataclasses.replace(M_4BIT, stochastic_rounding=True),
    )
    v_q = quantize(
        jnp.abs(_rand((1, 16, 256), 8, 0.001))[0] + 1e-10,
        dataclasses.replace(V_4BIT, stochastic_rounding=True),
    )
    key = jax.random.PRNGKey(3)
    w_new, m2, v2 = _run_new_leaf(p, g, m_q, v_q, key)
    fw, fmp, fms, fvp = _frozen_per_slice_leaf(p, g, m_q, v_q, "interpret", key)
    _assert_bits_equal(w_new, fw)
    _assert_bits_equal(m2.codes, fmp.reshape(m2.codes.shape))
    _assert_bits_equal(v2.codes, fvp.reshape(v2.codes.shape))


# ---------------------------------------------------------------------------
# trace-size regression gates (the CI single-launch invariant)
# ---------------------------------------------------------------------------


def _leaf_jaxpr(L, R, C, sr, backend, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    p, g, m_q, v_q = _mk_leaf(L, R, C, sr=sr, seed=1)
    if sr:
        fn = lambda p, g, key: _run_new_leaf(p, g, m_q, v_q, key)
        return jax.make_jaxpr(fn)(p, g, jax.random.PRNGKey(0))
    fn = lambda p, g: _run_new_leaf(p, g, m_q, v_q, None)
    return jax.make_jaxpr(fn)(p, g)


@pytest.mark.parametrize("use_sr", [False, True], ids=["rtn", "sr"])
def test_stacked_leaf_single_pallas_launch(monkeypatch, use_sr):
    """The acceptance gate: an (8, 256, 512) leaf issues exactly ONE
    pallas_call — L x launch overhead and L-unrolled jaxprs are regressions."""
    jaxpr = _leaf_jaxpr(8, 256, 512, use_sr, "interpret", monkeypatch)
    assert ops.count_pallas_calls(jaxpr) == 1, jaxpr


def test_ref_backend_trace_is_depth_independent(monkeypatch):
    """The ref backend vmaps the oracle: equation count must not grow with L
    (and it never launches a kernel)."""
    counts = {}
    for L in (1, 8):
        jaxpr = _leaf_jaxpr(L, 16, 256, True, "ref", monkeypatch)
        assert ops.count_pallas_calls(jaxpr) == 0
        counts[L] = ops.jaxpr_eqn_count(jaxpr)
    assert counts[1] == counts[8], counts


def test_4d_leaf_single_launch_and_bit_exact(monkeypatch):
    """ndim>3 stacked leaves (e.g. (G, L, R, C) grouped stacks) flatten their
    leading dims into the one launch's outer grid dim too."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    m_cfg = dataclasses.replace(M_4BIT, stochastic_rounding=True)
    v_cfg = dataclasses.replace(V_4BIT, stochastic_rounding=True)
    p = _rand((2, 3, 16, 256), 21, 0.1)
    g = _rand((2, 3, 16, 256), 22, 0.01)
    m_q = quantize(_rand((2, 3, 16, 256), 23, 0.01), m_cfg)
    v_q = quantize(jnp.abs(_rand((2, 3, 16, 256), 24, 0.001)) + 1e-10, v_cfg)
    key = jax.random.PRNGKey(9)

    jaxpr = jax.make_jaxpr(
        lambda p, g, key: _run_new_leaf(p, g, m_q, v_q, key)
    )(p, g, key)
    assert ops.count_pallas_calls(jaxpr) == 1

    w_new, m2, v2 = _run_new_leaf(p, g, m_q, v_q, key)
    fw, fmp, fms, fvp = _frozen_per_slice_leaf(p, g, m_q, v_q, "interpret", key)
    _assert_bits_equal(w_new, fw)
    _assert_bits_equal(m2.codes, fmp.reshape(m2.codes.shape))
    _assert_bits_equal(v2.codes, fvp.reshape(v2.codes.shape))


# ---------------------------------------------------------------------------
# mesh launches: one shard_map'd launch per device, bit-identical to one
# ---------------------------------------------------------------------------


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device host harness")
@pytest.mark.parametrize("use_sr", [False, True], ids=["rtn", "sr"])
@pytest.mark.parametrize(
    "L,R", [(2, 64), (8, 16), (3, 16)], ids=["split-rows", "split-slices", "whole"]
)
def test_mesh_launch_bit_identical_to_single_launch(monkeypatch, use_sr, L, R):
    """Inside a train step's sharding context the kernel runs under
    shard_map (XLA cannot partition a Mosaic kernel).  Whether each device
    takes a share of the rows (row0 keeps the SR counters global), a share
    of the slices, or the whole leaf, the outputs equal the one unsharded
    launch bit for bit."""
    from repro.launch.mesh import make_mesh
    from repro.sharding.context import sharding_ctx

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    p, g, m_q, v_q = _mk_leaf(L, R=R, sr=use_sr, seed=31)
    key = jax.random.PRNGKey(13) if use_sr else None
    mesh = make_mesh((2, 4), ("data", "model"))

    def on_mesh(*args):
        with sharding_ctx(mesh):
            return _run_new_leaf(*args)

    single = jax.jit(_run_new_leaf)(p, g, m_q, v_q, key)
    sharded = jax.jit(on_mesh)(p, g, m_q, v_q, key)
    for a, b in zip(jax.tree_util.tree_leaves(single), jax.tree_util.tree_leaves(sharded)):
        _assert_bits_equal(a, b)
