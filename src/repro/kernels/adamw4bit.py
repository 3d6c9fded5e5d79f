"""Fused 4-bit AdamW update as a Pallas TPU kernel.

This is the paper's "fused operator" (Tab. 4's `4-bit AdamW (fused)` row)
adapted to TPU: one kernel pass reads packed 4-bit moment codes + params +
grads tile-by-tile from HBM into VMEM, dequantizes on the VPU, applies the
AdamW step (Eq. 1) in fp32, requantizes, and writes packed codes + updated
params back — the precise fp32 moments never round-trip through HBM.

TPU adaptation (vs the CUDA original):
  * table lookup is a branchless 16-way select tree (no per-thread binary
    search; the 16-entry tables and the scalars live in SMEM),
  * encoding is a midpoint compare-and-sum: idx = sum_k [n > mid_k],
  * nibble pack/unpack are lane shuffles, done as exact 0/1 matmuls on the
    otherwise idle MXU (Mosaic cannot lower them as reshapes),
  * first-moment B128 block scales are computed inside the tile (tiles span
    whole rows, so blocks never straddle tiles),
  * second-moment rank-1 scales of the NEW v need global row/col maxes, so
    they are computed in a prepass (XLA fuses dequant+max; nothing fp32 is
    materialized in HBM) and fed to the kernel — the two-pass structure that
    replaces CUDA's atomics-based reduction.

Tiles are (TR, C): whole rows, because a block's last dim must be a multiple
of 128 or the full dim and the B128 scale block is C/128 wide.  C is a
multiple of 256 so packed codes (C/2) and the MXU shuffles (256 columns a
step) stay integral; TR shrinks as C grows (``pick_tile_r``).

Stacked leaves run as ONE launch: ``fused_adamw4`` takes (L, R, C) operands
and a grid (L, R/TR) whose outer dim walks the leading slices — no
per-slice Python loop, no L-unrolled jaxpr, one kernel launch per leaf.  Per
slice the v scale is ``min(row_stat, col_stat)`` with per-slice row stats
(L, R) and column stats (C,) shared across slices (rank-1 stats are global
per-dim vectors; leading-dim stats fold into the row stat upstream).  2-d
operands are accepted and treated as L == 1.

Stochastic rounding (``use_sr=True``) requantizes both moments with
counter-based Threefry-2x32 noise generated *inside* the tile: the counter is
the element's global index in its (R, C) slice, the key the slice's two
seed words (indexed by the outer grid dim), and the stream id separates m
from v — so the noise is a pure function of (key, element), independent of
tiling, mesh layout, AND of whether slices launch separately or in one
launch; it is bit-identical to the pure-jnp SR oracle in
``ref.py`` (see ``sr.py``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sr import (
    STREAM_M,
    STREAM_V,
    threefry2x32,
    uniform_from_bits,
)

__all__ = ["fused_adamw4", "pick_tile_r", "TILE_ELEMS"]

TILE_ELEMS = 64 * 1024  # elements per (TR, C) tile; TR shrinks as C grows
_BLOCK = 128  # first-moment block size (B128)


def _decode16(codes, table_ref):
    """Branchless 16-way select: vals[i] = table[codes[i]] (table in SMEM)."""
    acc = jnp.zeros(codes.shape, jnp.float32)
    for k in range(16):
        acc = jnp.where(codes == k, table_ref[k], acc)
    return acc


def _encode16(n, table_ref, num_points: int):
    """Round-to-nearest codes via midpoint compare-and-sum."""
    idx = jnp.zeros(n.shape, jnp.int32)
    for k in range(num_points - 1):
        mid = (table_ref[k] + table_ref[k + 1]) * 0.5
        idx = idx + (n > mid).astype(jnp.int32)
    return idx


def _encode16_sr(n, table_ref, num_points: int, u):
    """Stochastic codes: round to the bracketing table points with probability
    proportional to proximity, deciding with the uniform draw ``u``.

    Same bracketing/probability math as ``mappings.encode_stochastic`` /
    ``ref.encode_table_stochastic_bits`` (branchless select-tree form), so the
    kernel's SR codes match the jnp oracle bit-for-bit given the same ``u``.
    """
    ge = jnp.zeros(n.shape, jnp.int32)
    for k in range(num_points):
        ge = ge + (n >= table_ref[k]).astype(jnp.int32)
    lo = jnp.clip(ge - 1, 0, num_points - 2)
    t_lo = jnp.zeros(n.shape, jnp.float32)
    t_hi = jnp.zeros(n.shape, jnp.float32)
    for k in range(num_points - 1):
        t_lo = jnp.where(lo == k, table_ref[k], t_lo)
        t_hi = jnp.where(lo == k, table_ref[k + 1], t_hi)
    span = jnp.maximum(t_hi - t_lo, 1e-12)
    p_hi = jnp.clip((n - t_lo) / span, 0.0, 1.0)
    return lo + (u < p_hi).astype(jnp.int32)


def _tile_uniforms(seed_ref, row0_ref, tile_shape, stream: int):
    """Per-element uniforms for this tile, counter = slice-local r * C + c.

    Keyed on (per-slice seed words, element index, moment stream) — the
    in-kernel twin of ``sr.element_uniforms``, evaluated tile-locally so no
    random tensor ever touches HBM.  Tiles span whole rows, so the counter is
    the element's index in its own (R, C) slice: the tile's first row (grid
    dim 1) plus ``row0`` — the first row of this launch's shard when the
    rows are split across devices, else 0.  The outer slice dim (grid dim 0)
    picks the slice's two seed words instead of shifting the counter, so the
    bits equal what a standalone, unsharded per-slice launch would draw.
    """
    l = pl.program_id(0)
    i = pl.program_id(1)
    tr, tc = tile_shape
    rows = jax.lax.broadcasted_iota(jnp.uint32, (tr, tc), 0) + (
        i * tr + row0_ref[0]
    ).astype(jnp.uint32)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (tr, tc), 1)
    linear = rows * jnp.uint32(tc) + cols
    bits, _ = threefry2x32(
        seed_ref[2 * l], seed_ref[2 * l + 1], linear, jnp.uint32(stream)
    )
    return uniform_from_bits(bits)


def _unpack(packed):
    """(TR, C/2) packed bytes -> (TR, C) int32 codes (low nibble first).

    The nibble interleave (byte k -> columns 2k, 2k+1) is a lane shuffle that
    Mosaic cannot express as a reshape, so it runs on the MXU: per 128-byte
    chunk, ``lo @ E + hi @ O`` with 0/1 matrices placing byte k's nibbles at
    lanes 2k and 2k+1.  Codes are <= 15, so bf16 operands with fp32
    accumulation are exact.
    """
    p = packed.astype(jnp.int32)
    lo = (p & 0x0F).astype(jnp.bfloat16)
    hi = ((p >> 4) & 0x0F).astype(jnp.bfloat16)
    r = jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, 2 * _BLOCK), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, 2 * _BLOCK), 1)
    even = (c == 2 * r).astype(jnp.bfloat16)
    odd = (c == 2 * r + 1).astype(jnp.bfloat16)
    chunks = []
    for k in range(p.shape[1] // _BLOCK):
        sl = slice(k * _BLOCK, (k + 1) * _BLOCK)
        out = jnp.dot(lo[:, sl], even, preferred_element_type=jnp.float32)
        out = out + jnp.dot(hi[:, sl], odd, preferred_element_type=jnp.float32)
        chunks.append(out)
    return jnp.concatenate(chunks, axis=1).astype(jnp.int32)


def _pack(codes):
    """(TR, C) codes -> (TR, C/2) uint8 bytes (low nibble first).

    The inverse lane shuffle of ``_unpack``, also on the MXU: per 256-column
    chunk, ``codes @ S`` with S[2k, k] = 1 and S[2k+1, k] = 16 sums each
    nibble pair into one byte value <= 255, exact in bf16 x bf16 -> fp32.
    """
    c16 = codes.astype(jnp.bfloat16)
    r = jax.lax.broadcasted_iota(jnp.int32, (2 * _BLOCK, _BLOCK), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (2 * _BLOCK, _BLOCK), 1)
    sel = jnp.where(r == 2 * c, 1.0, jnp.where(r == 2 * c + 1, 16.0, 0.0))
    sel = sel.astype(jnp.bfloat16)
    chunks = [
        jnp.dot(
            c16[:, k * 2 * _BLOCK:(k + 1) * 2 * _BLOCK], sel,
            preferred_element_type=jnp.float32,
        )
        for k in range(codes.shape[1] // (2 * _BLOCK))
    ]
    return jnp.concatenate(chunks, axis=1).astype(jnp.int32).astype(jnp.uint8)


def _guard(s):
    return jnp.where(s > 0, s, jnp.ones_like(s))


def pad16(t):
    """A table padded to the select tree's fixed 16 entries (extras unused)."""
    t = t.astype(jnp.float32)
    return jnp.pad(t, (0, 16 - t.shape[0]))


def pick_tile_r(R: int, C: int, budget: int = TILE_ELEMS) -> int:
    """Rows per (TR, C) tile: the largest multiple-of-8 divisor of R with
    TR * C <= budget (8 at least, the fp32 sublane tile); R itself when R
    has no multiple-of-8 divisor (a full-extent block is always legal)."""
    cap = max(8, budget // C)
    for d in range(min(R, cap) // 8 * 8, 0, -8):
        if R % d == 0:
            return d
    return R


def _kernel(
    # SMEM inputs
    scalars_ref, m_table_ref, v_table_ref, seed_ref, row0_ref,
    # VMEM inputs
    w_ref, g_ref, m_packed_ref, m_scale_ref, v_packed_ref,
    vr_ref, vc_ref, vr_new_ref, vc_new_ref,
    # outputs
    w_out_ref, m_packed_out_ref, m_scale_out_ref, v_packed_out_ref,
    *, m_points: int, v_points: int, use_sr: bool,
):
    lr = scalars_ref[0]
    b1 = scalars_ref[1]
    b2 = scalars_ref[2]
    eps = scalars_ref[3]
    wd = scalars_ref[4]
    bc1 = scalars_ref[5]
    bc2 = scalars_ref[6]

    # Tensor blocks carry a leading slice dim of extent 1 (the outer grid
    # dim selects which slice); [0] views them as the 2-d tile.
    w = w_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    tr, tc = w.shape

    # ---- decompress (Alg. 1 line 3) ----------------------------------
    m_vals = _decode16(_unpack(m_packed_ref[0]), m_table_ref)
    m = m_vals * jnp.repeat(m_scale_ref[0], _BLOCK, axis=1)  # (TR, C/128)

    v_vals = _decode16(_unpack(v_packed_ref[0]), v_table_ref)
    v_scale = _guard(jnp.minimum(vr_ref[0], vc_ref[...]))  # (TR,1)x(1,C)
    v = v_vals * v_scale

    # ---- inner optimizer A: AdamW (Eq. 1) -----------------------------
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    w_out_ref[0] = (w - lr * (u + wd * w)).astype(w_out_ref.dtype)

    # ---- compress (Alg. 1 line 5) -------------------------------------
    m_blocks = m_new.reshape(tr, tc // _BLOCK, _BLOCK)
    m_scale_new = _guard(jnp.max(jnp.abs(m_blocks), axis=-1))  # (TR, C/128)
    m_scale_out_ref[0] = m_scale_new
    m_n = m_new / jnp.repeat(m_scale_new, _BLOCK, axis=1)
    if use_sr:
        u_m = _tile_uniforms(seed_ref, row0_ref, (tr, tc), STREAM_M)
        m_codes = _encode16_sr(m_n, m_table_ref, m_points, u_m)
    else:
        m_codes = _encode16(m_n, m_table_ref, m_points)
    m_packed_out_ref[0] = _pack(m_codes)

    v_scale_new = _guard(jnp.minimum(vr_new_ref[0], vc_new_ref[...]))
    v_n = v_new / v_scale_new
    if use_sr:
        u_v = _tile_uniforms(seed_ref, row0_ref, (tr, tc), STREAM_V)
        v_codes = _encode16_sr(v_n, v_table_ref, v_points, u_v)
    else:
        v_codes = _encode16(v_n, v_table_ref, v_points)
    v_packed_out_ref[0] = _pack(v_codes)


@functools.partial(
    jax.jit,
    static_argnames=("b1", "b2", "eps", "weight_decay", "interpret", "tile_r", "use_sr"),
)
def fused_adamw4(
    w: jnp.ndarray,          # (L, R, C) — or (R, C), treated as L == 1
    g: jnp.ndarray,          # like w
    m_packed: jnp.ndarray,   # (L, R, C/2) uint8
    m_scale: jnp.ndarray,    # (L, R, C/128) f32
    v_packed: jnp.ndarray,   # (L, R, C/2) uint8
    v_r: jnp.ndarray,        # (L, R) f32 — old per-slice rank-1 row stats
    v_c: jnp.ndarray,        # (C,) f32 — old rank-1 col stats (shared)
    v_r_new: jnp.ndarray,    # (L, R) f32 — precomputed stats of updated v
    v_c_new: jnp.ndarray,    # (C,) f32
    m_table: jnp.ndarray,    # (16,) signed (DE) table
    v_table: jnp.ndarray,    # (<=16,) unsigned (Linear) table
    lr: jnp.ndarray,
    bc1: jnp.ndarray,        # 1 - b1^t
    bc2: jnp.ndarray,        # 1 - b2^t
    sr_seed: Optional[jnp.ndarray] = None,  # (L, 2) uint32 per-slice key rows
    row0: Optional[jnp.ndarray] = None,     # int32: first row of this shard
    *,
    b1: float,
    b2: float,
    eps: float,
    weight_decay: float,
    interpret: bool = False,
    tile_r: Optional[int] = None,
    use_sr: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the fused update as ONE 2-d-grid launch over all stacked slices.

    The grid is (L, R/TR) over whole-row (TR, C) tiles; the outer dim walks
    the leading slices and selects each slice's row-stat block and SR seed
    words.  Because the SR counter stays slice-local, outputs are
    bit-identical to launching the 2-d kernel once per slice.  2-d operands
    are accepted (L == 1, stats ``(R,)`` / seed ``(2,)``) and return 2-d
    outputs.  ``tile_r`` overrides the row count per tile (``pick_tile_r``
    by default); it must divide R and be a multiple of 8 or R itself.

    ``use_sr=True`` requantizes stochastically with in-tile Threefry noise
    keyed by ``sr_seed`` (required in that case); ``use_sr=False`` is the
    bit-exact round-to-nearest path.  ``row0`` (default 0) is the global
    index of the operands' first row when they are one device's row shard
    of larger slices, so the SR counters stay those of the whole slice.

    Returns (w_new, m_packed_new, m_scale_new, v_packed_new).
    """
    squeeze = w.ndim == 2
    if squeeze:
        (R, C), L = w.shape, 1
    else:
        L, R, C = w.shape
    tr = pick_tile_r(R, C) if tile_r is None else tile_r
    assert R % tr == 0 and (tr % 8 == 0 or tr == R) and C % 256 == 0, (R, C, tr)

    m_points = int(m_table.shape[0])
    v_points = int(v_table.shape[0])

    if use_sr and sr_seed is None:
        raise ValueError("fused_adamw4(use_sr=True) requires sr_seed")
    seed = (
        jnp.zeros((2 * L,), jnp.uint32)
        if sr_seed is None
        else jnp.asarray(sr_seed, jnp.uint32).reshape(2 * L)
    )
    scalars = jnp.stack(
        [
            jnp.asarray(lr, jnp.float32),
            jnp.float32(b1),
            jnp.float32(b2),
            jnp.float32(eps),
            jnp.float32(weight_decay),
            jnp.asarray(bc1, jnp.float32),
            jnp.asarray(bc2, jnp.float32),
        ]
    )

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tile = lambda c: pl.BlockSpec((1, tr, c), lambda l, i: (l, i, 0))
    row = tile(1)
    col = pl.BlockSpec((1, C), lambda l, i: (0, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((L, R, C), w.dtype),
        jax.ShapeDtypeStruct((L, R, C // 2), jnp.uint8),
        jax.ShapeDtypeStruct((L, R, C // _BLOCK), jnp.float32),
        jax.ShapeDtypeStruct((L, R, C // 2), jnp.uint8),
    )

    kernel = functools.partial(
        _kernel, m_points=m_points, v_points=v_points, use_sr=use_sr
    )
    out = pl.pallas_call(
        kernel,
        grid=(L, R // tr),
        in_specs=[
            smem,                     # scalars (7,)
            smem,                     # m_table (16,)
            smem,                     # v_table (16,)
            smem,                     # SR seed words (2L,): slice l at 2l, 2l+1
            smem,                     # row0 (1,)
            tile(C),                  # w
            tile(C),                  # g
            tile(C // 2),             # m_packed
            tile(C // _BLOCK),        # m_scale
            tile(C // 2),             # v_packed
            row,                      # v_r (L,R,1)
            col,                      # v_c (1,C)
            row,                      # v_r_new
            col,                      # v_c_new
        ],
        out_specs=[
            tile(C),                  # w_new
            tile(C // 2),             # m_packed_new
            tile(C // _BLOCK),        # m_scale_new
            tile(C // 2),             # v_packed_new
        ],
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
        name="fused_adamw4",  # the trace's name for the kernel
    )(
        scalars,
        pad16(m_table),
        pad16(v_table),
        seed,
        jnp.zeros((1,), jnp.int32) if row0 is None else jnp.reshape(row0, (1,)).astype(jnp.int32),
        w.reshape(L, R, C),
        g.reshape(L, R, C),
        m_packed.reshape(L, R, C // 2),
        m_scale.reshape(L, R, C // _BLOCK),
        v_packed.reshape(L, R, C // 2),
        v_r.reshape(L, R, 1),
        v_c.reshape(1, C),
        v_r_new.reshape(L, R, 1),
        v_c_new.reshape(1, C),
    )
    if squeeze:
        out = tuple(o.reshape(o.shape[1:]) for o in out)
    return out
