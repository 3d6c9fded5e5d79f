"""Standalone Pallas kernels: block-wise 4-bit quantize / dequantize.

No caller outside the tests: the simplest validation target for the shared
decode/encode/pack primitives of the fused AdamW kernel, on the same
whole-row tiles.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.adamw4bit import (_decode16, _encode16, _guard, _pack,
                                     _unpack, pad16, pick_tile_r)

__all__ = ["quantize_blockwise_4bit", "dequantize_blockwise_4bit"]

_BLOCK = 128


def _quant_kernel(table_ref, x_ref, packed_ref, scale_ref, *, num_points: int):
    x = x_ref[...].astype(jnp.float32)
    tr, tc = x.shape
    blocks = x.reshape(tr, tc // _BLOCK, _BLOCK)
    scale = _guard(jnp.max(jnp.abs(blocks), axis=-1))
    scale_ref[...] = scale
    n = x / jnp.repeat(scale, _BLOCK, axis=1)
    packed_ref[...] = _pack(_encode16(n, table_ref, num_points))


def _dequant_kernel(table_ref, packed_ref, scale_ref, x_ref):
    codes = _unpack(packed_ref[...])
    vals = _decode16(codes, table_ref)
    x_ref[...] = vals * jnp.repeat(scale_ref[...], _BLOCK, axis=1)


def _row_tiles(R: int, C: int, tile_r):
    """Whole-row (TR, C) tiles, as in the fused kernel (see ``pick_tile_r``)."""
    tr = pick_tile_r(R, C) if tile_r is None else tile_r
    assert R % tr == 0 and (tr % 8 == 0 or tr == R) and C % 256 == 0, (R, C, tr)
    return tr, lambda c: pl.BlockSpec((tr, c), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("interpret", "tile_r"))
def quantize_blockwise_4bit(
    x: jnp.ndarray,
    table: jnp.ndarray,
    *,
    interpret: bool = False,
    tile_r: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    R, C = x.shape
    tr, tile = _row_tiles(R, C, tile_r)
    kernel = functools.partial(_quant_kernel, num_points=int(table.shape[0]))
    return pl.pallas_call(
        kernel,
        grid=(R // tr,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile(C)],
        out_specs=[tile(C // 2), tile(C // _BLOCK)],
        out_shape=(
            jax.ShapeDtypeStruct((R, C // 2), jnp.uint8),
            jax.ShapeDtypeStruct((R, C // _BLOCK), jnp.float32),
        ),
        interpret=interpret,
    )(pad16(table), x)


@functools.partial(jax.jit, static_argnames=("interpret", "tile_r"))
def dequantize_blockwise_4bit(
    packed: jnp.ndarray,
    scale: jnp.ndarray,
    table: jnp.ndarray,
    *,
    interpret: bool = False,
    tile_r: Optional[int] = None,
) -> jnp.ndarray:
    R, Ch = packed.shape
    C = Ch * 2
    tr, tile = _row_tiles(R, C, tile_r)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(R // tr,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            tile(C // 2),
            tile(C // _BLOCK),
        ],
        out_specs=tile(C),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(pad16(table), packed, scale)
