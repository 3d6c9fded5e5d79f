"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth the kernels must match bit-for-bit (codes) /
allclose (floats). They intentionally mirror the kernel's *structured* layout:
2-d tensors with the last dim a multiple of 256 (so nibble pairs and B128
blocks never straddle tiles), m quantized B128/<table> per row-major block,
v quantized rank-1/<table> with externally supplied new scales.

Every function here is vmap-safe (shape-generic jnp ops, no data-dependent
Python): ``ops.fused_adamw4_leaf``'s ref backend vmaps
``fused_adamw4_reference`` / ``fused_adamw4_sr_reference`` over the leading
slice dim of stacked leaves, tracing O(1) equations regardless of depth —
the oracle twin of the kernel's single launch.  Keep new helpers
free of per-call Python loops over array contents for the same reason.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core.mappings import lookup
from repro.kernels.sr import STREAM_M, STREAM_V, element_uniforms

__all__ = [
    "unpack_codes",
    "pack_codes",
    "dequant_blockwise",
    "dequant_rank1",
    "encode_table",
    "encode_table_stochastic_bits",
    "fused_adamw4_reference",
    "fused_adamw4_sr_reference",
]


def unpack_codes(packed: jnp.ndarray) -> jnp.ndarray:
    """(R, C/2) uint8 -> (R, C) uint8 codes (low nibble first)."""
    lo = packed & jnp.uint8(0x0F)
    hi = (packed >> 4) & jnp.uint8(0x0F)
    return jnp.stack([lo, hi], axis=-1).reshape(packed.shape[0], -1)


def pack_codes(codes: jnp.ndarray) -> jnp.ndarray:
    """(R, C) uint8 -> (R, C/2) uint8 (low nibble first)."""
    pairs = codes.reshape(codes.shape[0], -1, 2)
    return (pairs[..., 0] | (pairs[..., 1] << 4)).astype(jnp.uint8)


def decode_table(codes: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    return lookup(table, codes)


def encode_table(n: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    mids = (table[1:] + table[:-1]) / 2.0
    return jnp.sum(n[..., None] > mids, axis=-1).astype(jnp.uint8)


def encode_table_stochastic_bits(
    n: jnp.ndarray, table: jnp.ndarray, u: jnp.ndarray
) -> jnp.ndarray:
    """Stochastic codes driven by explicit uniforms ``u`` in [0, 1).

    Identical bracketing/probability math as the in-kernel ``_encode16_sr``
    (and ``mappings.encode_stochastic``), so the fused kernel's SR codes are
    reproducible bit-for-bit by feeding the same counter-derived uniforms.
    """
    k = table.shape[0]
    lo = jnp.clip(jnp.sum(n[..., None] >= table, axis=-1) - 1, 0, k - 2)
    t_lo = lookup(table, lo)
    t_hi = lookup(table, lo + 1)
    span = jnp.maximum(t_hi - t_lo, 1e-12)
    p_hi = jnp.clip((n - t_lo) / span, 0.0, 1.0)
    idx = lo + (u < p_hi).astype(lo.dtype)
    return idx.astype(jnp.uint8)


def _guard(s: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(s > 0, s, jnp.ones_like(s))


def dequant_blockwise(
    packed: jnp.ndarray, scale: jnp.ndarray, table: jnp.ndarray, block: int = 128
) -> jnp.ndarray:
    """packed (R, C/2), scale (R, C/block) -> (R, C) fp32."""
    codes = unpack_codes(packed)
    vals = decode_table(codes, table)
    R, C = vals.shape
    per_elem = jnp.repeat(scale, block, axis=1)
    return vals * per_elem


def dequant_rank1(
    packed: jnp.ndarray, r: jnp.ndarray, c: jnp.ndarray, table: jnp.ndarray
) -> jnp.ndarray:
    """packed (R, C/2), r (R,), c (C,) -> (R, C) fp32."""
    codes = unpack_codes(packed)
    vals = decode_table(codes, table)
    scale = _guard(jnp.minimum(r[:, None], c[None, :]))
    return vals * scale


def quant_blockwise(
    x: jnp.ndarray, table: jnp.ndarray, block: int = 128
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(R, C) -> packed (R, C/2), scale (R, C/block)."""
    R, C = x.shape
    blocks = x.reshape(R, C // block, block)
    scale = _guard(jnp.max(jnp.abs(blocks), axis=-1))  # (R, C/block)
    n = (blocks / scale[..., None]).reshape(R, C)
    codes = encode_table(n, table)
    return pack_codes(codes), scale


def quant_rank1_given_scales(
    x: jnp.ndarray, r: jnp.ndarray, c: jnp.ndarray, table: jnp.ndarray
) -> jnp.ndarray:
    """(R, C) with given new rank-1 stats -> packed codes (R, C/2)."""
    scale = _guard(jnp.minimum(r[:, None], c[None, :]))
    codes = encode_table(x / scale, table)
    return pack_codes(codes)


def fused_adamw4_reference(
    w: jnp.ndarray,          # (R, C) param
    g: jnp.ndarray,          # (R, C) grad
    m_packed: jnp.ndarray,   # (R, C/2)
    m_scale: jnp.ndarray,    # (R, C/128)
    v_packed: jnp.ndarray,   # (R, C/2)
    v_r: jnp.ndarray,        # (R,)
    v_c: jnp.ndarray,        # (C,)
    m_table: jnp.ndarray,    # (16,) signed DE
    v_table: jnp.ndarray,    # (16,) unsigned Linear
    lr: jnp.ndarray,
    b1: float,
    b2: float,
    eps: float,
    weight_decay: float,
    bc1: jnp.ndarray,
    bc2: jnp.ndarray,
    v_r_new: jnp.ndarray = None,
    v_c_new: jnp.ndarray = None,
):
    """Oracle for the fused kernel: dequant -> AdamW (Eq. 1) -> requant.

    Returns (w_new, m_packed_new, m_scale_new, v_packed_new, v_r_new, v_c_new).
    New rank-1 scales are row/col maxes of the updated v (the kernel receives
    them precomputed — the two-pass structure described in DESIGN.md §3);
    pass ``v_r_new``/``v_c_new`` explicitly when the slice is part of a larger
    stacked leaf whose rank-1 stats are global (see ``ops.fused_adamw4_leaf``).
    """
    g32 = g.astype(jnp.float32)
    m = dequant_blockwise(m_packed, m_scale, m_table)
    v = dequant_rank1(v_packed, v_r, v_c, v_table)

    m_new = b1 * m + (1.0 - b1) * g32
    v_new = b2 * v + (1.0 - b2) * g32 * g32

    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    w_new = (w.astype(jnp.float32) - lr * (u + weight_decay * w.astype(jnp.float32))).astype(w.dtype)

    m_packed_new, m_scale_new = quant_blockwise(m_new, m_table)
    if v_r_new is None:
        v_r_new = jnp.max(v_new, axis=1)
    if v_c_new is None:
        v_c_new = jnp.max(v_new, axis=0)
    v_packed_new = quant_rank1_given_scales(v_new, v_r_new, v_c_new, v_table)
    return w_new, m_packed_new, m_scale_new, v_packed_new, v_r_new, v_c_new


def fused_adamw4_sr_reference(
    w: jnp.ndarray,
    g: jnp.ndarray,
    m_packed: jnp.ndarray,
    m_scale: jnp.ndarray,
    v_packed: jnp.ndarray,
    v_r: jnp.ndarray,
    v_c: jnp.ndarray,
    m_table: jnp.ndarray,
    v_table: jnp.ndarray,
    lr: jnp.ndarray,
    b1: float,
    b2: float,
    eps: float,
    weight_decay: float,
    bc1: jnp.ndarray,
    bc2: jnp.ndarray,
    seed: jnp.ndarray,  # (2,) uint32 per-slice key words
    v_r_new: jnp.ndarray = None,
    v_c_new: jnp.ndarray = None,
):
    """Stochastic-rounding oracle for the fused kernel.

    Identical to ``fused_adamw4_reference`` except both moments requantize
    stochastically, with uniforms derived from counter-based Threefry on the
    element index (``sr.element_uniforms``) — the exact bits the Pallas kernel
    draws in-tile, so codes match the kernel bit-for-bit given ``seed``.
    """
    g32 = g.astype(jnp.float32)
    m = dequant_blockwise(m_packed, m_scale, m_table)
    v = dequant_rank1(v_packed, v_r, v_c, v_table)

    m_new = b1 * m + (1.0 - b1) * g32
    v_new = b2 * v + (1.0 - b2) * g32 * g32

    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    w_new = (w.astype(jnp.float32) - lr * (u + weight_decay * w.astype(jnp.float32))).astype(w.dtype)

    R, C = w.shape
    u_m = element_uniforms(seed[0], seed[1], (R, C), STREAM_M)
    u_v = element_uniforms(seed[0], seed[1], (R, C), STREAM_V)

    blocks = m_new.reshape(R, C // 128, 128)
    m_scale_new = _guard(jnp.max(jnp.abs(blocks), axis=-1))
    m_n = (blocks / m_scale_new[..., None]).reshape(R, C)
    m_packed_new = pack_codes(encode_table_stochastic_bits(m_n, m_table, u_m))

    if v_r_new is None:
        v_r_new = jnp.max(v_new, axis=1)
    if v_c_new is None:
        v_c_new = jnp.max(v_new, axis=0)
    v_scale_new = _guard(jnp.minimum(v_r_new[:, None], v_c_new[None, :]))
    v_n = v_new / v_scale_new
    v_packed_new = pack_codes(encode_table_stochastic_bits(v_n, v_table, u_v))
    return w_new, m_packed_new, m_scale_new, v_packed_new, v_r_new, v_c_new
