"""The plain references: the layer-by-layer training steps equal the same
steps taken on the whole model at once, and the served weights' 4-bit
quantizer keeps the properties the paper states."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import model, q4
from bench.reference.serve import ServeReference
from bench.reference.train import Reference, adamw_step

C = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
     "d_ff": 128, "vocab_size": 97, "rope_variant": "rope", "rope_theta": 10000.0,
     "rms_norm_eps": 1e-6}
HP = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01}


def batch(seed, b=4, s=16):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, C["vocab_size"], (b, s)).astype(np.int32)
    labels = np.concatenate([toks[:, 1:], -np.ones((b, 1), np.int32)], axis=1)
    return toks, labels


def whole_model_steps(c, key, batches):
    """Float32 AdamW on the whole model, one jax.grad per step; returns the
    losses, the final weights and the first moments after step one."""
    P = model.init_params(key, c)
    M = jax.tree_util.tree_map(jnp.zeros_like, P)
    V = jax.tree_util.tree_map(jnp.zeros_like, P)
    losses, first = [], None
    for t, (toks, labels) in enumerate(batches, start=1):
        loss, g = jax.value_and_grad(model.loss)(P, toks, labels, c)
        losses.append(float(loss))
        for k in P:
            P[k], M[k], V[k] = adamw_step(P[k], M[k], V[k], g[k], t, HP)
        first = first or dict(M)
    return losses, P, first


@pytest.mark.parametrize("rope_variant", ["rope", "rope2d"])
def test_layerwise_steps_equal_whole_model_steps(rope_variant):
    c = dict(C, rope_variant=rope_variant)
    key = model.seed_key(3_000_000_017)
    batches = [batch(i) for i in range(3)]
    got = Reference(c, HP, row_blocks=2).run(key, batches, 3)
    losses, P, M = whole_model_steps(c, key, batches)
    np.testing.assert_allclose(got.loss, losses, rtol=1e-5)
    for name in ("embed", "head", "final_norm", "wq.1", "w2.0", "norm1.0"):
        base, _, layer = name.partition(".")
        m = M[base] if not layer else M[base][int(layer)]
        assert got.grad[name] == pytest.approx(float(jnp.linalg.norm(m / (1 - HP["b1"]))), rel=1e-4)
        p0 = model.init_params(key, c)[base]
        d = (P[base] - p0) if not layer else (P[base] - p0)[int(layer)]
        assert got.change[name] == pytest.approx(float(jnp.linalg.norm(d)), rel=1e-4)


def test_maps_are_the_papers():
    de = np.array(q4.DE_SIGNED_4)
    assert de.shape == (16,) and np.all(np.diff(de) > 0) and 0.0 in de and de[-1] == 1.0
    assert -1.0 not in de and de[0] == -0.8875


def test_zero_weights_stay_zero():
    z = jnp.zeros((256, 512))
    assert float(jnp.abs(q4.quantize_b128_de(z)).max()) == 0.0


def test_scales():
    x = jnp.arange(1.0, 257.0).reshape(2, 128)
    np.testing.assert_array_equal(q4.blockwise_scale(x)[:, 0], [128.0, 256.0])
    q = q4.quantize_b128_de(jnp.array([0.3, -0.05, 1.0] + [0.0] * 125))
    np.testing.assert_allclose(q[:3], [0.2125, -0.0325, 1.0])


def test_rounder_lowers_precision():
    x = jax.random.normal(jax.random.PRNGKey(2), (64, 64))
    assert model.Rounder().q(x) is x
    y = model.Rounder("float8_e4m3fn").q(x)
    rel = float(jnp.abs(y - x).max() / jnp.abs(x).max())
    assert 1e-3 < rel < 0.1


def test_serve_reference_reads_logits_by_position():
    key = model.seed_key(5)
    ref = ServeReference(C, key, "bf16")
    toks = list(range(1, 40))
    chunks = ref.logits(toks)
    assert len(chunks) == 1 and chunks[0].shape == (512, C["vocab_size"])
    P = model.init_params(key, C)
    # the first position sees only the first token
    first = ref.logits(toks[:1])[0][0]
    np.testing.assert_allclose(first, chunks[0][0], rtol=1e-5, atol=1e-6)
    assert not np.allclose(chunks[0][1], chunks[0][0])
    assert P["embed"].shape == (C["vocab_size"], C["d_model"])
