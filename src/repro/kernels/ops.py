"""jit'd wrappers around the Pallas kernels with backend dispatch.

`fused_adamw4_leaf` is the integration point used by
``FusedAdamWRoute`` (``repro.core.optimizers.transform``): it takes a
(param, grad, QuantizedTensor m, QuantizedTensor v) leaf and returns the
updated triple, computing the new rank-1 scales in a prepass and running the
elementwise dequant->AdamW->requant in ONE Pallas launch.

Leaves may have stacked leading dims (the model stores per-layer-group
tensors ``(L, d_in, d_out)``): the leaf is viewed as L 2-d slices, all
updated by a single ``pallas_call`` with a grid ``(L, R/TR)`` whose
outer dim walks the slices — no per-slice Python loop, so a 24-deep layer
stack costs one launch and traces O(1) jaxpr equations (test-enforced in
``tests/test_kernel_fusion.py``).  The rank-1 v scales stay *global* per-dim
stats (matching ``rank1_normalize``); per slice, the leading-dim stats fold
into the row stat — ``min(lead_l, r_i, c_j) == min(min(lead_l, r_i), c_j)``
— so each slice is exactly the kernel's ``min(row, col)`` contract, with
per-slice row stats ``(L, R)`` and shared col stats ``(C,)``.

Stochastic rounding: the per-leaf SR key (handed down from ``compressed()``'s
``fold_in(step key, leaf index)`` stream) derives one key per slice with a
single vmapped ``fold_in(leaf_key, slice index)``; the resulting ``(L, 2)``
seed rows feed the kernel's outer grid dim.  The kernel (and the reference
oracle) expand each row to per-element Threefry noise counter-keyed on the
element's slice-local index, so the noise is independent of tiling, mesh
layout, and launch batching, and identical across backends — the single
launch is bit-identical to the historical per-slice launches.

Backend selection (``kernel_backend``): on TPU the kernel runs compiled;
elsewhere the pure-jnp reference runs (fast on CPU, bit-identical to the
kernel's interpret mode), and ``REPRO_KERNEL_BACKEND=interpret`` runs the
kernel body emulated instead.  The ref path vmaps the per-slice oracle over
the leading dim, so it also traces O(1) equations regardless of L.  On a
mesh the kernel launches under ``shard_map`` (``_launch``).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
from jax.sharding import PartitionSpec as P

from repro.core.quantizer import QuantizedTensor
from repro.kernels import ref
from repro.kernels.adamw4bit import fused_adamw4
from repro.kernels.sr import key_rows
from repro.sharding.context import ctx_mesh

__all__ = [
    "fused_adamw4_leaf",
    "kernel_backend",
    "count_pallas_calls",
    "pallas_calls",
    "jaxpr_eqn_count",
]

_BLOCK = 128


def kernel_backend() -> str:
    """'tpu' -> compiled pallas; 'interpret' -> pallas interpret mode;
    'ref' -> pure-jnp oracle (fast on CPU).

    Decided by the platform alone — 'tpu' on a TPU backend, 'ref' elsewhere
    — unless ``REPRO_KERNEL_BACKEND`` names a route explicitly."""
    override = os.environ.get("REPRO_KERNEL_BACKEND")
    if override:
        return override
    platform = jax.default_backend()
    if platform == "tpu":
        return "tpu"
    return "ref"


def _sub_jaxprs(eqn):
    """Nested jaxprs of an equation (pjit/scan/cond/custom_* bodies)."""
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def pallas_calls(jaxpr) -> list:
    """Every ``pallas_call`` equation anywhere in ``jaxpr`` (recursive).

    Accepts a ``Jaxpr`` or ``ClosedJaxpr``.  A fused-AdamW equation's first
    output is the updated param, so ``eqn.outvars[0].aval.size`` is the
    number of params that launch updates.
    """
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in _sub_jaxprs(eqn):
            found.extend(pallas_calls(sub))
    return found


def count_pallas_calls(jaxpr) -> int:
    """Number of ``pallas_call`` equations anywhere in ``jaxpr``.

    The launch-count invariant's measuring stick: an ndim>=3 leaf through
    ``fused_adamw4_leaf`` must trace exactly ONE (CI trace-size gate).
    """
    return len(pallas_calls(jaxpr))


def jaxpr_eqn_count(jaxpr) -> int:
    """Total equation count including nested jaxprs — the trace-size metric
    the CI gate compares across L to prove the ref path does not unroll."""
    if isinstance(jaxpr, jex_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in _sub_jaxprs(eqn):
            n += jaxpr_eqn_count(sub)
    return n


def _rank1_slice_stats(
    stats: Tuple[jnp.ndarray, ...], shape: Tuple[int, ...]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-dim rank-1 stats -> per-slice (L, R) row stats + shared (C,) cols.

    Leading-dim stats fold into the row stat (min is associative), so each
    2-d slice sees the same per-element scale ``rank1_denorm`` would build.
    """
    lead_shape = shape[:-2]
    row, col = stats[-2], stats[-1]
    if not lead_shape:
        return row[None, :], col
    lead = None
    for r, st in enumerate(stats[:-2]):
        view = [1] * len(lead_shape)
        view[r] = lead_shape[r]
        b = st.reshape(view)
        lead = b if lead is None else jnp.minimum(lead, b)
    lead = jnp.broadcast_to(lead, lead_shape).reshape(-1)  # (L,)
    return jnp.minimum(lead[:, None], row[None, :]), col


def _rank1_new_stats(v_new: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Per-dim absmax stats of the updated v (rank1_normalize's layout).
    v_new is nonnegative, so plain maxes are absmaxes."""
    nd = v_new.ndim
    return tuple(
        jnp.max(v_new, axis=tuple(i for i in range(nd) if i != r))
        for r in range(nd)
    )


def _launch(kernel, mesh, sliced, shared):
    """Run ``kernel`` on the whole leaf, or once per device on a mesh.

    A Mosaic kernel cannot be partitioned by XLA, so inside a sharding
    context (a train step on a mesh) the launch runs under ``shard_map``:
    each device updates a contiguous share of the rows of every slice
    (``row0`` keeps the SR counters those of the whole slice), or of the
    slices when the rows do not split into multiples of 8, or — when
    neither divides — the whole leaf, redundantly.  XLA moves the operands
    into that layout and back; the results are bit-identical to one
    unsharded launch.  ``sliced`` operands lead with the (L, R) dims
    (``seed_rows`` with L only, or None under RTN); ``shared`` ones are
    whole on every device.
    """

    def run(sliced, shared, row0=None):
        p3, g3, m_packed, m_scale, v_packed, v_r, v_r_new, seed_rows = sliced
        v_c, v_c_new, m_table, v_table, lr, bc1, bc2 = shared
        return kernel(
            p3, g3, m_packed, m_scale, v_packed, v_r, v_c, v_r_new, v_c_new,
            m_table, v_table, lr, bc1, bc2, seed_rows, row0,
        )

    if mesh is None or mesh.size == 1:
        return run(sliced, shared)
    L, R = sliced[0].shape[:2]
    n, names = mesh.size, tuple(mesh.axis_names)
    split_rows = R % (8 * n) == 0
    if split_rows:
        rows, seeds = P(None, names), P()
    elif L % n == 0:
        rows, seeds = P(names), P(names)
    else:
        rows, seeds = P(), P()

    def body(sliced, shared):
        row0 = jax.lax.axis_index(names) * (R // n) if split_rows else None
        return run(sliced, shared, row0)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=((rows,) * 7 + (seeds,), P()),
        out_specs=(rows,) * 4, check_vma=False,
    )(sliced, shared)


def fused_adamw4_leaf(
    p: jnp.ndarray,
    g: jnp.ndarray,
    m_s: QuantizedTensor,
    v_s: QuantizedTensor,
    lr: jnp.ndarray,
    b1: float,
    b2: float,
    eps: float,
    weight_decay: float,
    bc1: jnp.ndarray,
    bc2: jnp.ndarray,
    key: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jnp.ndarray, QuantizedTensor, QuantizedTensor]:
    """One fused-kernel AdamW step for an ndim>=2 leaf with 4-bit m (B128)
    and 4-bit v (rank-1) — one Pallas launch regardless of stacked leading
    dims.  ``key`` activates in-kernel stochastic rounding when the configs
    request it (caller guards eligibility; no key => RTN, mirroring
    ``quantize()``'s fallback).  ``backend`` (default ``kernel_backend()``)
    names the route explicitly — a parity check runs ``"tpu"`` against
    ``"ref"`` on the same chip."""
    shape = p.shape
    R, C = shape[-2], shape[-1]
    L = p.size // (R * C)
    use_sr = bool(m_s.config.stochastic_rounding) and key is not None

    m_table = m_s.config.table()
    v_table = v_s.config.table()

    p3 = p.reshape(L, R, C)
    g3 = g.astype(jnp.float32).reshape(L, R, C)
    m_packed = m_s.codes.reshape(L, R, C // 2)
    m_scale = m_s.scales[0].reshape(L, R, C // _BLOCK)
    v_packed = v_s.codes.reshape(L, R, C // 2)
    v_r, v_c = _rank1_slice_stats(v_s.scales, shape)  # (L, R), (C,)

    # Prepass: global rank-1 stats of the UPDATED v, via batched dequant
    # (XLA fuses dequant+max; nothing fp32 is materialized in HBM on the
    # compiled path).
    v_old = jax.vmap(ref.dequant_rank1, in_axes=(0, 0, None, None))(
        v_packed, v_r, v_c, v_table
    )
    v_new_expr = b2 * v_old + (1.0 - b2) * g3 * g3
    new_stats = _rank1_new_stats(v_new_expr.reshape(shape))
    v_r_new, v_c_new = _rank1_slice_stats(new_stats, shape)

    # One vmapped fold_in derives every slice key; the (L, 2) seed rows feed
    # the kernel's outer grid dim (row l seeds slice l).
    seed_rows = (
        key_rows(
            jax.vmap(jax.random.fold_in, in_axes=(None, 0))(key, jnp.arange(L))
        )
        if use_sr
        else None
    )

    backend = backend or kernel_backend()
    if backend == "ref":
        # vmap the per-slice oracle: O(1) trace regardless of L.
        if use_sr:
            def _slice(w, g2, mp, ms, vp, vr, vrn, sd):
                return ref.fused_adamw4_sr_reference(
                    w, g2, mp, ms, vp, vr, v_c, m_table, v_table,
                    lr, b1, b2, eps, weight_decay, bc1, bc2,
                    sd, vrn, v_c_new,
                )

            w3, mp3, ms3, vp3, _, _ = jax.vmap(_slice)(
                p3, g3, m_packed, m_scale, v_packed, v_r, v_r_new, seed_rows
            )
        else:
            def _slice(w, g2, mp, ms, vp, vr, vrn):
                return ref.fused_adamw4_reference(
                    w, g2, mp, ms, vp, vr, v_c, m_table, v_table,
                    lr, b1, b2, eps, weight_decay, bc1, bc2,
                    vrn, v_c_new,
                )

            w3, mp3, ms3, vp3, _, _ = jax.vmap(_slice)(
                p3, g3, m_packed, m_scale, v_packed, v_r, v_r_new
            )
    else:
        # One pallas_call covers every slice.
        kernel = functools.partial(
            fused_adamw4, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
            interpret=(backend != "tpu"), use_sr=use_sr,
        )
        w3, mp3, ms3, vp3 = _launch(
            kernel, ctx_mesh(),
            (p3, g3, m_packed, m_scale, v_packed, v_r, v_r_new, seed_rows),
            (v_c, v_c_new, m_table, v_table, lr, bc1, bc2),
        )

    w_new = w3.reshape(shape).astype(p.dtype)
    m_codes = mp3.reshape(m_s.codes.shape)
    m_scales = ms3.reshape(m_s.scales[0].shape)
    v_codes = vp3.reshape(v_s.codes.shape)

    m2 = QuantizedTensor(m_codes, (m_scales,), m_s.shape, m_s.config)
    v2 = QuantizedTensor(v_codes, new_stats, v_s.shape, v_s.config)
    return w_new, m2, v2
