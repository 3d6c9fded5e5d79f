"""Quantized gradient communication (src/repro/comms).

The enforced invariants:

* ``quantized_all_reduce`` inside shard_map over 8 ranks is bitwise equal to
  the host oracle (quantize each rank's partial with the counter-based
  transport uniforms, dequantize, sum) — the wire really moves codes+scales.
* Stochastic transport rounding is unbiased: averaging the reduced value
  over independent keys converges to the true fp32 sum.
* ``reduce_grads`` is bit-identical across mesh layouts (2x4, 4x2, and the
  no-mesh numerics path) given the same logical gradients — the property
  that makes int4 transport safe under elastic restarts.  This is exactly
  where ``jax.random.uniform``-based SR fails (its draws depend on output
  sharding under the default non-partitionable Threefry), hence the
  counter-based derivation in ``repro.kernels.sr``.
* int4-comms training: save -> restore -> continue on the same mesh is
  bit-exact end to end; an elastic (2,4) -> (4,2) restore stays close and
  finite (reduction order upstream of comms legitimately differs).
* Accounting is exact: ``leaf_wire_bytes`` matches the bytes of the real
  quantized payload, and int4 clears the >= 4x acceptance floor.
"""

import functools
import warnings

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.comms import (
    CommsConfig,
    format_wire_table,
    grad_comm_key,
    leaf_wire_bytes,
    mode_totals,
    quantized_all_reduce,
    reduce_grads,
    wire_report,
)
from repro.core.optimizers import make_optimizer
from repro.core.quantizer import dequantize, quantize
from repro.kernels.sr import STREAM_GRAD, tensor_uniforms
from repro.models import LayerSpec, ModelConfig, init_model
from repro.train.checkpoint import restore_checkpoint, save_checkpoint
from repro.train.train_loop import (
    build_train_step,
    jit_train_step,
    make_train_state,
    train_state_shardings,
)

jax.config.update("jax_platform_name", "cpu")

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device host harness"
)


# ---------------------------------------------------------------------------
# config / migration
# ---------------------------------------------------------------------------


def test_commsconfig_parse_and_properties():
    cfg = CommsConfig.parse("INT4")
    assert cfg.mode == "int4" and cfg.bits == 4 and cfg.quantized
    assert cfg.compresses and cfg.cast_dtype is None
    q = cfg.quant_config()
    assert q.bits == 4 and q.signed and q.normalization == "blockwise"
    assert q.block_size == 128 and q.stochastic_rounding
    assert "int4" in cfg.name and "+SR" in cfg.name

    bf16 = CommsConfig(mode="bf16")
    assert not bf16.quantized and bf16.compresses
    assert bf16.cast_dtype == jnp.bfloat16 and bf16.quant_config() is None

    fp32 = CommsConfig()
    assert not fp32.compresses and fp32.quant_config() is None

    with pytest.raises(ValueError, match="unknown grad-comm mode"):
        CommsConfig(mode="int2")


def test_commsconfig_validates_mapping():
    # The mapping registry is the gatekeeper even for transport configs —
    # typos fail at construction, listing the registered maps.
    from repro.core import mappings

    with pytest.raises(ValueError, match="registered mappings"):
        CommsConfig(mode="int4", mapping="ed")
    for name in mappings.registered():
        assert CommsConfig(mode="int4", mapping=name).quant_config().mapping == name


def test_grad_dtype_knob_is_gone():
    # PR 6's deprecation path is finished: CommsConfig is the ONLY
    # wire-format knob, and the legacy kwarg fails loudly.
    cfg = _MICRO_CFG
    opt = make_optimizer("adamw32", 1e-3)
    with pytest.raises(TypeError):
        build_train_step(cfg, opt, grad_dtype=jnp.bfloat16)
    import repro.comms as comms_mod

    assert not hasattr(comms_mod, "from_grad_dtype")


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------


def _grads_fixture():
    rng = np.random.default_rng(0)
    return {
        "embed": jnp.asarray(rng.standard_normal((256, 64), dtype=np.float32)),
        "w": jnp.asarray(rng.standard_normal((128, 128), dtype=np.float32)),
        "bias": jnp.asarray(rng.standard_normal((64,), dtype=np.float32)),
    }


def test_leaf_wire_bytes_matches_real_payload():
    cfg = CommsConfig(mode="int4")
    qcfg = cfg.quant_config()
    g = _grads_fixture()["embed"]
    q = quantize(g, qcfg)
    fp32, wire = leaf_wire_bytes(g.shape, cfg)
    assert fp32 == g.size * 4
    assert wire == q.nbytes()  # codes + scales, exactly what the wire moves
    # sub-threshold leaves move fp32 in every mode
    assert leaf_wire_bytes((64,), cfg) == (256, 256)
    assert leaf_wire_bytes((64,), CommsConfig(mode="bf16")) == (256, 256 // 2)


def test_wire_report_ratios_and_floor():
    grads = _grads_fixture()
    reports = {r["mode"]: r for r in mode_totals(grads)}
    assert reports["fp32"]["ratio_vs_fp32"] == 1.0
    assert reports["bf16"]["ratio_vs_fp32"] == pytest.approx(2.0)
    assert reports["int8"]["ratio_vs_fp32"] > 3.5
    assert reports["int4"]["ratio_vs_fp32"] >= 4.0  # acceptance floor
    r = wire_report(grads, CommsConfig(mode="int4"))
    assert r["quantized_leaves"] == 2 and r["n_leaves"] == 3
    assert sum(row["wire_bytes"] for row in r["leaves"]) == r["total_wire_bytes"]
    table = format_wire_table(mode_totals(grads), title="t")
    assert "int4" in table and "| grad-comm |" in table


def test_wire_report_gpt2m_acceptance_floor():
    """ISSUE acceptance: >= 4x fewer gradient-collective bytes per step for
    int4 on the production-sized (GPT-2-M) tree."""
    from benchmarks.tables import _gpt2m_like_params

    r = wire_report(_gpt2m_like_params(), CommsConfig(mode="int4"))
    assert r["ratio_vs_fp32"] >= 4.0


# ---------------------------------------------------------------------------
# reduce_grads numerics
# ---------------------------------------------------------------------------


def test_reduce_grads_fp32_and_bf16_modes():
    grads = _grads_fixture()
    out = reduce_grads(grads, None, None, CommsConfig())
    for k in grads:
        np.testing.assert_array_equal(out[k], grads[k])
    out = reduce_grads(grads, None, None, CommsConfig(mode="bf16"))
    for k in grads:
        assert out[k].dtype == jnp.bfloat16
        np.testing.assert_array_equal(out[k], grads[k].astype(jnp.bfloat16))


def test_reduce_grads_quantized_threshold_and_error():
    grads = _grads_fixture()
    cfg = CommsConfig(mode="int4")
    key = grad_comm_key(jax.random.PRNGKey(0), jnp.int32(0))
    out = reduce_grads(grads, None, None, cfg, key=key)
    # sub-threshold leaf passes through untouched (and fp32)
    np.testing.assert_array_equal(out["bias"], grads["bias"])
    # quantized leaves carry bounded blockwise-relative error
    for k in ("embed", "w"):
        g = np.asarray(grads[k])
        d = np.abs(np.asarray(out[k]) - g)
        assert d.max() <= np.abs(g).max()  # scales bound the error
        assert d.mean() < 0.2 * np.abs(g).mean()
        assert not np.array_equal(np.asarray(out[k]), g)


def test_reduce_grads_rtn_without_key_is_deterministic():
    grads = _grads_fixture()
    cfg = CommsConfig(mode="int4")
    a = reduce_grads(grads, None, None, cfg, key=None)
    b = reduce_grads(grads, None, None, cfg, key=None)
    for k in grads:
        np.testing.assert_array_equal(a[k], b[k])


def test_grad_comm_key_stream():
    assert grad_comm_key(None, jnp.int32(3)) is None
    base = jax.random.PRNGKey(7)
    k3 = grad_comm_key(base, jnp.int32(3))
    # pure function of (base, step): replayable, and step-separated
    assert np.array_equal(
        jax.random.key_data(k3),
        jax.random.key_data(grad_comm_key(base, jnp.int32(3))),
    )
    k4 = grad_comm_key(base, jnp.int32(4))
    assert not np.array_equal(jax.random.key_data(k3), jax.random.key_data(k4))
    # domain-separated from the optimizer's per-step key
    opt_k3 = jax.random.fold_in(base, jnp.int32(3))
    assert not np.array_equal(jax.random.key_data(k3), jax.random.key_data(opt_k3))


_AXES = {"embed": ("vocab", "embed"), "w": ("embed", "mlp"), "bias": ("embed",)}


def _run_reduce(grads, cfg, key, mesh_shape):
    if mesh_shape is None:
        fn = jax.jit(lambda g: reduce_grads(g, None, None, cfg, key=key))
        return jax.device_get(fn(grads))
    devs = np.array(jax.devices()[: mesh_shape[0] * mesh_shape[1]]).reshape(mesh_shape)
    mesh = Mesh(devs, ("data", "model"))
    fn = jax.jit(lambda g: reduce_grads(g, _AXES, mesh, cfg, key=key))
    with mesh:
        return jax.device_get(fn(grads))


@needs_8_devices
@pytest.mark.parametrize("mode", ["int4", "int8"])
def test_reduce_grads_bit_identical_across_mesh_layouts(mode):
    """The elastic-restart guarantee: same logical gradients + same
    checkpointed key stream -> bit-identical reduced gradients on (2,4),
    (4,2), and without a mesh.  Fails with jax.random-based SR."""
    grads = _grads_fixture()
    cfg = CommsConfig(mode=mode)
    key = grad_comm_key(jax.random.PRNGKey(7), jnp.int32(3))
    r24 = _run_reduce(grads, cfg, key, (2, 4))
    r42 = _run_reduce(grads, cfg, key, (4, 2))
    rn = _run_reduce(grads, cfg, key, None)
    for k in grads:
        np.testing.assert_array_equal(r24[k], r42[k], err_msg=f"2x4 vs 4x2: {k}")
        np.testing.assert_array_equal(r24[k], rn[k], err_msg=f"mesh vs none: {k}")


# ---------------------------------------------------------------------------
# quantized_all_reduce (the shard_map wire primitive)
# ---------------------------------------------------------------------------


def _all_reduce_fn(mesh, qcfg, key):
    @functools.partial(
        shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False,  # vmapped dequantize defeats replication inference
    )
    def reduced(xs):
        return quantized_all_reduce(xs[0], qcfg, "data", key=key)[None]

    return reduced


@needs_8_devices
def test_quantized_all_reduce_matches_host_oracle():
    qcfg = CommsConfig(mode="int4").quant_config()
    key = jax.random.PRNGKey(11)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 16, 128), dtype=np.float32))

    out = jax.device_get(_all_reduce_fn(mesh, qcfg, key)(x))
    for r in range(1, 8):  # every rank holds the same reduced value
        np.testing.assert_array_equal(out[0], out[r])

    deqs = []
    for r in range(8):
        kr = jax.random.fold_in(key, r)
        u = tensor_uniforms(kr, (16, 128), STREAM_GRAD)
        deqs.append(dequantize(quantize(x[r], qcfg, uniforms=u)))
    oracle = jax.device_get(jnp.sum(jnp.stack(deqs), axis=0))
    np.testing.assert_array_equal(out[0], oracle)


@needs_8_devices
def test_quantized_all_reduce_sr_unbiased():
    """Mean over independent keys approaches the exact fp32 sum ~1/sqrt(K)
    — the transported quantization noise is zero-mean (App. E.3 transferred
    to the wire)."""
    qcfg = CommsConfig(mode="int4").quant_config()
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("data",))
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((8, 8, 128), dtype=np.float32))
    true = jax.device_get(jnp.sum(x, axis=0))

    n_keys = 16
    acc = np.zeros_like(true)
    for s in range(n_keys):
        out = jax.device_get(
            _all_reduce_fn(mesh, qcfg, jax.random.PRNGKey(100 + s))(x)
        )
        acc += out[0]
    single_err = np.abs(
        jax.device_get(_all_reduce_fn(mesh, qcfg, jax.random.PRNGKey(100))(x))[0]
        - true
    ).mean()
    mean_err = np.abs(acc / n_keys - true).mean()
    assert mean_err < 0.5 * single_err, (mean_err, single_err)


# ---------------------------------------------------------------------------
# end-to-end training with int4 transport
# ---------------------------------------------------------------------------

_MICRO_CFG = ModelConfig(
    name="micro-comms-lm",
    num_layers=1,
    d_model=64,
    num_heads=2,
    num_kv_heads=1,
    head_dim=32,
    d_ff=128,
    vocab_size=256,  # embed 256*64 = 16384 > threshold -> quantized transport
    blocks=(LayerSpec("dense", 0),),
    remat=False,
)


def _batch(t):
    from repro.data.pipeline import DataConfig, SyntheticLM

    data = SyntheticLM(DataConfig(_MICRO_CFG.vocab_size, 16, 8, seed=2))
    return {k: jnp.asarray(v) for k, v in data.batch_at(t).items()}


def _assert_states_bitwise(a, b, what=""):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb, f"{what}: tree structure mismatch"
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=what)


def _comms_mesh_step(opt, mesh, axes, state, comms):
    step = build_train_step(_MICRO_CFG, opt, mesh, axes, zero=True, comms=comms)
    return jit_train_step(step, state, _batch(0), axes, mesh, donate=False)


def test_int4_comms_training_moves_loss_single_process():
    """The numerics-only path: int4 transport trains the micro LM to a loss
    close to the fp32-collective run (same seeds)."""
    opt = make_optimizer("adamw32", 3e-3)
    losses = {}
    for mode in ("fp32", "int4"):
        params, _ = init_model(jax.random.PRNGKey(0), _MICRO_CFG)
        state = make_train_state(params, opt, key=jax.random.PRNGKey(5))
        step = jax.jit(
            build_train_step(_MICRO_CFG, opt, comms=CommsConfig(mode=mode))
        )
        for t in range(12):
            state, metrics = step(state, _batch(t))
        losses[mode] = float(metrics["loss"])
    assert np.isfinite(losses["int4"])
    assert losses["int4"] < 5.6  # trains (init loss ~ ln 256 = 5.55)
    assert abs(losses["int4"] - losses["fp32"]) < 0.3


@needs_8_devices
def test_int4_comms_mesh_resume_bit_exact(tmp_path):
    """int4-transport SR training on a (2,4) mesh: save -> restore onto a
    fresh mesh -> continue == uninterrupted, bit-exact — the transport key
    stream is a pure function of the checkpointed (base key, step)."""
    opt = make_optimizer("production4bit", 3e-3)
    comms = CommsConfig(mode="int4")
    params, axes = init_model(jax.random.PRNGKey(0), _MICRO_CFG)
    key = jax.random.PRNGKey(11)

    mesh1 = jax.make_mesh((2, 4), ("data", "model"))
    state = make_train_state(params, opt, key=key)
    step1 = _comms_mesh_step(opt, mesh1, axes, state, comms)
    for t in range(2):
        state, metrics = step1(state, _batch(t))
    assert np.isfinite(float(metrics["loss"]))
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 2, state)
    uninterrupted = state
    for t in range(2, 4):
        uninterrupted, _ = step1(uninterrupted, _batch(t))

    mesh2 = jax.make_mesh((2, 4), ("data", "model"))
    target = jax.eval_shape(lambda: make_train_state(params, opt, key=key))
    shardings = train_state_shardings(target, axes, mesh2, zero=True)
    restored, _ = restore_checkpoint(d, target, shardings=shardings)
    step2 = _comms_mesh_step(opt, mesh2, axes, restored, comms)
    for t in range(2, 4):
        restored, _ = step2(restored, _batch(t))
    _assert_states_bitwise(restored, uninterrupted, "int4-comms mesh resume")


@needs_8_devices
def test_int4_comms_elastic_restore_close(tmp_path):
    """(2,4) -> (4,2) elastic restore under int4 transport: the comms
    transform itself is mesh-invariant (bit-equality test above), but the
    data-parallel loss reduction upstream legitimately reorders, so end to
    end this asserts close + finite with bounded outliers — the same
    contract the fp32-collective elastic test pins down."""
    opt = make_optimizer("production4bit", 3e-3)
    comms = CommsConfig(mode="int4")
    params, axes = init_model(jax.random.PRNGKey(0), _MICRO_CFG)
    key = jax.random.PRNGKey(11)
    mesh1 = jax.make_mesh((2, 4), ("data", "model"))
    state = make_train_state(params, opt, key=key)
    step1 = _comms_mesh_step(opt, mesh1, axes, state, comms)
    for t in range(2):
        state, _ = step1(state, _batch(t))
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 2, state)
    ref, _ = step1(state, _batch(2))

    mesh2 = jax.make_mesh((4, 2), ("data", "model"))
    target = jax.eval_shape(lambda: make_train_state(params, opt, key=key))
    shardings = train_state_shardings(target, axes, mesh2, zero=True)
    restored, _ = restore_checkpoint(d, target, shardings=shardings)
    step2 = _comms_mesh_step(opt, mesh2, axes, restored, comms)
    cont, metrics = step2(restored, _batch(2))
    assert np.isfinite(float(metrics["loss"]))
    for a, b in zip(
        jax.tree_util.tree_leaves(ref.params), jax.tree_util.tree_leaves(cont.params)
    ):
        diff = np.abs(np.asarray(a) - np.asarray(b))
        # Transport quantization snaps the (legitimate) reduction-order
        # perturbation to whole code bins, so the outlier fraction runs a
        # few x higher than the fp32-collective elastic case — bound it at
        # 1% with the same magnitude cap.
        assert float(np.mean(diff > 5e-4)) < 1e-2, float(np.mean(diff > 5e-4))
        assert float(diff.max()) < 5e-3, float(diff.max())
