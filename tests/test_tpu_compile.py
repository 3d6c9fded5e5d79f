"""The fused 4-bit AdamW kernel compiles for a TPU v5e at real widths.

Nothing here runs: the TPU compiler, which ships with jax's TPU plugin,
compiles for a v5e that is described, not attached.  That catches what
interpret mode cannot — block shapes the chip's tiling refuses, vector
relayouts Mosaic cannot lower, dtype casts the chip lacks — before any chip
time is spent.  Shapes are internlm2-1.8b MLP leaves — one ``(2048, 8192)``
matrix and an 8-deep stacked ``(8, 2048, 8192)`` leaf (the grid's outer
slice dim with L > 1) — and mixtral-8x7b's stacked expert leaf
``(8, 4096, 14336)``, whose wider rows leave the minimum 8 rows per tile;
each with round-to-nearest and stochastic rounding.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adamw4bit import fused_adamw4

HP = dict(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _operands(shape, use_sr, sharding):
    lead, (R, C) = shape[:-2], shape[-2:]
    s = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=sharding)
    f32, u8 = jnp.float32, jnp.uint8
    return (
        s(shape, f32), s(shape, f32),                     # w, g
        s(lead + (R, C // 2), u8),                        # m codes
        s(lead + (R, C // 128), f32),                     # m B128 scales
        s(lead + (R, C // 2), u8),                        # v codes
        s(lead + (R,), f32), s((C,), f32),                # old v rank-1 stats
        s(lead + (R,), f32), s((C,), f32),                # new v rank-1 stats
        s((16,), f32), s((16,), f32),                     # m / v tables
        s((), f32), s((), f32), s((), f32),               # lr, bc1, bc2
        s(lead + (2,), jnp.uint32) if use_sr else None,   # SR seed rows
    )


@pytest.mark.parametrize("use_sr", [False, True], ids=["rtn", "sr"])
@pytest.mark.parametrize(
    "shape",
    [(2048, 8192), (8, 2048, 8192), (8, 4096, 14336)],
    ids=["matrix", "stacked8", "mixtral-experts"],
)
def test_fused_adamw4_compiles_for_v5e(one_chip, shape, use_sr):
    step = jax.jit(
        lambda *a: fused_adamw4(*a, use_sr=use_sr, **HP)
    )
    compiled = step.lower(*_operands(shape, use_sr, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_adamw4_keeps_its_trace_name(one_chip):
    """The kernel's custom call is named ``fused_adamw4`` (``.N``), the name
    ``bench/trace.py`` reads its device time by, whatever Python function
    wraps it: here its body called from a function of another name."""
    body = fused_adamw4.__wrapped__

    def update_leaf(*a):
        return body(*a, use_sr=False, **HP)

    text = jax.jit(update_leaf).lower(*_operands((256, 512), False, one_chip)).compile().as_text()
    calls = [line.split(" = ")[0].split()[-1].lstrip("%") for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls and all(re.fullmatch(r"fused_adamw4(\.\d+)?", c) for c in calls), calls
