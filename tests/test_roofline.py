"""Roofline machinery tests: collective parsing, scan undercount evidence,
cross-validation of the decomposed-compile methodology, sharding rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import (
    _ring_bytes,
    collective_bytes_from_hlo,
    roofline_terms,
)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

SAMPLE_HLO = """
  %all-reduce = f32[1024,512]{1,0} all-reduce(%x), channel_id=1, replica_groups=[2,8]<=[16], to_apply=%add
  %ag = bf16[16,4096]{1,0} all-gather(%y), channel_id=2, replica_groups=[4,4]<=[16], dimensions={0}
  %rs = f32[64]{0} reduce-scatter(%z), channel_id=3, replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = bf16[128]{0} collective-permute(%w), channel_id=4, source_target_pairs={{0,1}}
  %other = f32[8] add(%a, %b)
"""


def test_collective_parse_kinds_and_ring_bytes():
    out = collective_bytes_from_hlo(SAMPLE_HLO)
    assert out["ops"] == 4
    # all-reduce: result 1024*512*4 B, K=8 -> 2*(7/8)*R
    r = 1024 * 512 * 4
    assert out["all-reduce"] == pytest.approx(2 * 7 / 8 * r)
    # all-gather: result 16*4096*2 B, K=4 -> (3/4)*R
    assert out["all-gather"] == pytest.approx(3 / 4 * 16 * 4096 * 2)
    # reduce-scatter: result 64*4 B, K=4 (explicit group) -> (K-1)*R
    assert out["reduce-scatter"] == pytest.approx(3 * 64 * 4)
    # collective-permute: R
    assert out["collective-permute"] == pytest.approx(128 * 2)
    assert out["total"] == pytest.approx(
        out["all-reduce"] + out["all-gather"] + out["reduce-scatter"]
        + out["collective-permute"]
    )


def test_collective_parse_multiplier():
    a = collective_bytes_from_hlo(SAMPLE_HLO, multiplier=3.0)
    b = collective_bytes_from_hlo(SAMPLE_HLO)
    assert a["total"] == pytest.approx(3 * b["total"])


def test_ring_formulas_k1_is_free():
    assert _ring_bytes("all-reduce", 100.0, 1) == 0.0


# ---------------------------------------------------------------------------
# the motivating defect: XLA cost_analysis counts scan bodies once
# ---------------------------------------------------------------------------


def test_xla_cost_analysis_undercounts_scans():
    """Documents WHY the roofline uses decomposed compilation: a scan of N
    matmuls reports ~1/N of the unrolled FLOPs."""
    w = jnp.zeros((64, 64))

    def f_scan(x):
        return jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None, length=16)[0]

    def f_unroll(x):
        for _ in range(16):
            x = jnp.tanh(x @ w)
        return x

    x = jnp.zeros((64, 64))
    fl_scan = jax.jit(f_scan).lower(x).compile().cost_analysis()["flops"]
    fl_unroll = jax.jit(f_unroll).lower(x).compile().cost_analysis()["flops"]
    assert fl_unroll > 10 * fl_scan  # would be ~equal if scans were counted


# ---------------------------------------------------------------------------
# methodology cross-check: decomposed sum == whole-model unrolled compile
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_decomposed_cost_matches_unrolled_whole_model():
    """For a tiny 4-layer model, per-layer-cost x 4 + tail must match the
    fully-unrolled single-module compile within tolerance."""
    import dataclasses

    from repro.configs import reduced_config
    from repro.models import init_model, loss_fn
    from repro.models.blocks import apply_block

    cfg = dataclasses.replace(
        reduced_config("internlm2-1.8b"), unroll_scans=True, remat=False,
    )
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    B, S = 4, 64
    batch = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "labels": jnp.zeros((B, S), jnp.int32),
    }

    # whole model, layers unrolled via num_layers separate apply calls
    def whole(params):
        return loss_fn(params, cfg, batch)[0]

    # force the layer scan to unroll by building a 1-layer-units config
    # (plan_scan_units gives one scan of 4 for the uniform pattern; compare
    # against manual unrolled application instead)
    from repro.models.layers import embed_lookup, chunked_cross_entropy, rmsnorm

    def manual(params):
        x = embed_lookup(params["embed"], batch["tokens"])
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        p_unit = params["decoder"][0]["sub0"]
        for layer in range(cfg.num_layers):
            p_l = jax.tree_util.tree_map(lambda a: a[layer], p_unit)
            x, _, _ = apply_block(
                p_l, x, cfg.blocks[0], cfg, positions=pos, cache=None,
                cur_pos=None,
            )
        x = rmsnorm(x, params["final_norm"])
        return chunked_cross_entropy(
            x, params["head"], batch["labels"], unroll=True
        )

    g_whole = jax.jit(jax.grad(whole))
    g_manual = jax.jit(jax.grad(manual))
    fl_scan = g_whole.lower(params).compile().cost_analysis()["flops"]
    fl_manual = g_manual.lower(params).compile().cost_analysis()["flops"]
    # manual-unrolled counts every layer; the scanned module counts one body.
    # Reconstruct: scan_total ~= per_layer x L (+ tails)
    per_layer_upper = fl_scan  # scan module ~ 1 body + tails
    assert fl_manual > 2.5 * per_layer_upper  # scan undercount visible
    # decomposition bound: manual total < (1 body + tails) * L
    assert fl_manual < fl_scan * cfg.num_layers * 1.5

    # numerics agree between the two formulations
    l1 = float(whole(params))
    l2 = float(manual(params))
    np.testing.assert_allclose(l1, l2, rtol=5e-4)  # bf16 reassociation


# ---------------------------------------------------------------------------
# sharding rules (duck-typed mesh — no devices needed)
# ---------------------------------------------------------------------------


class FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        self.devices = np.empty(tuple(sizes.values()))


def test_spec_rules_divisibility_fallbacks():
    from repro.sharding.rules import spec_for

    mesh = FakeMesh({"data": 16, "model": 16})
    # mixtral experts=8 not divisible by 16 -> falls through to mlp
    spec = spec_for((8, 4096, 14336), ("experts", "embed", "mlp"), mesh)
    assert tuple(spec) == (None, None, "model")
    # phi3.5 experts=16 divides -> EP
    spec = spec_for((16, 4096, 6400), ("experts", "embed", "mlp"), mesh)
    assert tuple(spec) == ("model", None, None)
    # hymba 25 heads -> row-parallel embed fallback
    spec = spec_for((1600, 25, 64), ("embed", "heads", "head_dim"), mesh)
    assert tuple(spec) == ("model", None, None)
    # never shard head_dim / layers
    spec = spec_for((32, 4096, 32, 128), ("layers", "embed", "heads", "head_dim"), mesh)
    assert tuple(spec) == (None, None, "model", None)


def test_with_zero_adds_dp_on_largest_free_dim():
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import with_zero

    mesh = FakeMesh({"data": 16, "model": 16})
    spec = with_zero((32, 4096, 14336), P(None, None, "model"), mesh,
                     axes=("layers", "embed", "mlp"))
    assert tuple(spec) == (None, "data", "model")  # 4096 free -> data; L=32 skipped
    # multi-pod: both dp axes
    mesh2 = FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = with_zero((4096, 4096), P(None, "model"), mesh2)
    assert tuple(spec) == (("pod", "data"), "model")


def test_roofline_terms_bottleneck():
    t = roofline_terms(
        {"flops": 197e12, "bytes accessed": 819e9 * 2}, 50e9 * 0.5, 256, 1e15
    )
    assert t.compute_s == pytest.approx(1.0)
    assert t.memory_s == pytest.approx(2.0)
    assert t.collective_s == pytest.approx(0.5)
    assert t.bottleneck == "memory"
