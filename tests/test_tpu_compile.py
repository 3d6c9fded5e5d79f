"""The fused 4-bit AdamW kernel and the serving decode chunk compile for a
TPU v5e at real widths.

Nothing here runs: the TPU compiler, which ships with jax's TPU plugin,
compiles for a v5e that is described, not attached.  That catches what
interpret mode cannot — block shapes the chip's tiling refuses, vector
relayouts Mosaic cannot lower, dtype casts the chip lacks — before any chip
time is spent.  Shapes are internlm2-1.8b MLP leaves — one ``(2048, 8192)``
matrix and an 8-deep stacked ``(8, 2048, 8192)`` leaf (the grid's outer
slice dim with L > 1) — and mixtral-8x7b's stacked expert leaf
``(8, 4096, 14336)``, whose wider rows leave the minimum 8 rows per tile;
each with round-to-nearest and stochastic rounding.  The decode chunk is
read for what it does with its KV cache: written in place, never copied.

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


def _hlo_instructions(text):
    """(computation roots, instructions) of an optimized HLO module's text:
    ``roots`` maps each computation to its ROOT opcode; each instruction is
    ``(dtype, dims, opcode, called computation or None)`` for array-shaped
    results (tuples are left out: they only pass buffers on)."""
    roots, instrs, comp = {}, [], None
    head = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
    instr = re.compile(r"^\s*(ROOT )?%\S+ = (\w+)\[([\d,]*)\]\{[^}]*\} ([\w-]+)\((.*)$")
    for line in text.splitlines():
        m = head.match(line)
        if m:
            comp = m.group(1)
            continue
        m = instr.match(line)
        if not m:
            continue
        root, dtype, dims, opcode, rest = m.groups()
        if root:
            roots[comp] = opcode
        called = re.search(r"calls=%([\w.-]+)", rest)
        instrs.append((dtype, tuple(int(d) for d in dims.split(",") if d), opcode,
                       called.group(1) if called else None))
    return roots, instrs


def test_decode_chunk_keeps_the_kv_cache_in_place_on_v5e(one_chip):
    """``ServeEngine``'s decode chunk at internlm2-1.8b's published widths
    (2 layers), 32 slots of 2048 positions, bf16 weights, compiled for a
    described v5e: no instruction makes a layer's or the stack's K, V or
    position cache apart from the in-place scatter of the new token, the
    temporaries stay under one K stack, and both caches alias the output
    (they are donated)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_model
    from repro.serve import ServeEngine

    base = get_config("internlm2-1.8b")
    L, B, S = 2, 32, 2048
    cfg = dataclasses.replace(base, num_layers=L, blocks=base.blocks[:L])
    built = {}

    def build():
        params = init_model(jax.random.PRNGKey(0), cfg)[0]
        built["engine"] = ServeEngine(cfg, params, max_batch=B, s_max=S,
                                      weights="bf16", drain_every=8)
        return built["engine"]._decode_args()

    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(build),
    )
    compiled = built["engine"]._decode.lower(*args).compile()

    kv = (L, B, S, cfg.num_kv_heads, cfg.head_dim)
    cache_shapes = {("bf16", kv), ("bf16", kv[1:]), ("s32", kv[:3]), ("s32", kv[1:3])}
    roots, instrs = _hlo_instructions(compiled.as_text())
    made = [
        (opcode, called) for dtype, dims, opcode, called in instrs
        if (dtype, dims) in cache_shapes
        and opcode not in ("parameter", "get-tuple-element", "bitcast", "scatter")
        and not (opcode == "fusion" and roots.get(called) == "scatter")
    ]
    assert not made, made

    mem = compiled.memory_analysis()
    k_stack = L * B * S * cfg.num_kv_heads * cfg.head_dim * 2
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(args[1]))
    assert mem.temp_size_in_bytes < k_stack, mem
    assert mem.alias_size_in_bytes >= cache_bytes, mem
