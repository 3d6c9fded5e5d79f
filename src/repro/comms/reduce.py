"""Quantized gradient-collective primitives.

Two layers:

``quantized_all_reduce``
    The wire-format primitive, for use inside ``shard_map`` over the
    data-parallel mesh axes.  Each rank block-quantizes its *local partial*
    gradient (codes + absmax scales), the collective all-gathers codes and
    scales (that is what moves on the wire — uint8 instead of fp32), and
    every rank dequantizes each participant's contribution and sums in a
    fixed rank order.  With stochastic rounding the per-rank key is
    ``fold_in(key, axis_index)``, so the transported noise is a pure
    function of (key, rank) — deterministic and replayable.

``reduce_grads``
    The train-step integration that replaces the ad-hoc ``grad_dtype``
    cast in ``train_loop._constrain_grads_zero``.  The gradients arriving
    here are SPMD-global (autodiff already summed over data parallelism),
    so the quantized modes apply the transport quantizer to the logical
    gradient — quantize (SR keyed off the checkpointed step key) ->
    constrain the *codes and scales* to the ZeRO wire layout (the
    resharding collective moves compressed bytes) -> dequantize into fp32
    for the optimizer.  Numerically this is transport quantization applied
    once per reduction; because every op is elementwise or an exact
    (max/reshape) block statistic of the logical tensor, the result is
    bit-identical for any mesh layout given the same logical gradients —
    the property the elastic-restart tests pin down.

Stochastic-rounding noise is generated with the counter-based Threefry of
``repro.kernels.sr`` (counter = the leaf's flattened global element index,
stream ``STREAM_GRAD``), NOT ``jax.random.uniform``: under jax's default
non-partitionable Threefry lowering, ``uniform`` draws depend on the output
sharding, which would silently break the cross-mesh bit-reproducibility
promise above.  The counter derivation replays identical bits per
(key, element) on any mesh — the same trick the fused optimizer kernel uses
for tiling-invariant in-kernel SR.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.comms.config import GRAD_COMM_KEY_DOMAIN, CommsConfig
from repro.core.quantizer import QuantConfig, QuantizedTensor, dequantize, quantize
from repro.kernels.sr import STREAM_GRAD, tensor_uniforms
from repro.sharding.rules import auto_axes, wire_spec

__all__ = ["quantized_all_reduce", "reduce_grads", "grad_comm_key"]

_IS_AXES_LEAF = lambda a: isinstance(a, tuple) and all(isinstance(s, str) for s in a)


def grad_comm_key(
    base_key: Optional[jax.Array], step: jnp.ndarray
) -> Optional[jax.Array]:
    """Per-step transport SR key: a pure function of the checkpointed
    ``(TrainState.key, step)`` pair, domain-separated from the optimizer's
    state-quantization stream (which folds bare leaf indices into the same
    ``fold_in(key, step)``)."""
    if base_key is None:
        return None
    step_key = jax.random.fold_in(base_key, step)
    return jax.random.fold_in(step_key, GRAD_COMM_KEY_DOMAIN)


def quantized_all_reduce(
    x: jnp.ndarray,
    config: QuantConfig,
    axis_name,
    key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Sum ``x`` over ``axis_name`` moving codes+scales, not fp32.

    For use inside ``shard_map``: ``x`` is this rank's partial sum.  Returns
    ``sum_r dequantize(quantize(x_r))`` — the dequantize-and-sum schedule, in
    ascending rank order on every rank (deterministic, rank-count exact).
    """
    u = None
    if key is not None and config.stochastic_rounding:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
        u = tensor_uniforms(key, tuple(x.shape), STREAM_GRAD)
    q = quantize(x, config, uniforms=u)
    # The collective: codes (uint8) + scales (fp32 block absmax) on the wire.
    codes = jax.lax.all_gather(q.codes, axis_name)
    scales = tuple(jax.lax.all_gather(s, axis_name) for s in q.scales)

    def deq_one(c, ss):
        return dequantize(QuantizedTensor(c, ss, x.shape, config))

    return jnp.sum(jax.vmap(deq_one)(codes, scales), axis=0)


def _transport_quantize(
    g: jnp.ndarray,
    qcfg: QuantConfig,
    axes: Optional[Tuple[str, ...]],
    mesh: Optional[Mesh],
    key: Optional[jax.Array],
) -> jnp.ndarray:
    """Quantize -> constrain codes/scales to the wire layout -> dequantize."""
    u = (
        tensor_uniforms(key, tuple(g.shape), STREAM_GRAD)
        if key is not None and qcfg.stochastic_rounding
        else None
    )
    q = quantize(g.astype(jnp.float32), qcfg, uniforms=u)
    codes, scales = q.codes, q.scales
    if mesh is not None and axes is not None and len(axes) == codes.ndim:
        # The compressed payload is what reshards into the ZeRO layout.
        spec = wire_spec(tuple(codes.shape), axes, mesh)
        codes = jax.lax.with_sharding_constraint(codes, NamedSharding(mesh, spec))
    out = dequantize(QuantizedTensor(codes, scales, q.shape, qcfg))
    if mesh is not None and axes is not None:
        spec = wire_spec(tuple(out.shape), axes, mesh)
        out = jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))
    return out


def reduce_grads(
    grads,
    axes,
    mesh: Optional[Mesh],
    config: CommsConfig,
    *,
    key: Optional[jax.Array] = None,
):
    """Apply the configured gradient-collective wire format to a grad tree.

    * ``fp32``  — constrain each leaf to the ZeRO layout (reduce-scatter),
      exactly the legacy ``_constrain_grads_zero`` behaviour.
    * ``bf16``  — cast before the constraint (half the collective bytes);
      leaves stay bf16 downstream, matching the legacy ``grad_dtype`` path
      bit for bit.
    * ``int8``/``int4`` — transport quantization per leaf (see module
      docstring).  Leaves with <= ``config.threshold`` elements move fp32.

    ``mesh=None`` applies the numerics without layout constraints (the
    single-process benchmark path measures exactly the quantization error a
    mesh run pays).  ``key`` (from ``grad_comm_key``) enables stochastic
    rounding; without it quantized modes fall back to round-to-nearest.
    """
    if mesh is not None:
        mesh = auto_axes(mesh)
    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    if axes is not None:
        a_leaves = jax.tree_util.tree_leaves(axes, is_leaf=_IS_AXES_LEAF)
    else:
        a_leaves = [None] * len(g_leaves)
    qcfg = config.quant_config()
    out = []
    for i, (g, a) in enumerate(zip(g_leaves, a_leaves)):
        quantize_leaf = qcfg is not None and g.size > config.threshold
        if quantize_leaf:
            leaf_key = jax.random.fold_in(key, i) if key is not None else None
            g = _transport_quantize(g, qcfg, a, mesh, leaf_key)
        else:
            if config.cast_dtype is not None:
                g = g.astype(config.cast_dtype)
            if mesh is not None and a is not None:
                spec = wire_spec(tuple(g.shape), a, mesh)
                g = jax.lax.with_sharding_constraint(g, NamedSharding(mesh, spec))
        out.append(g)
    return jax.tree_util.tree_unflatten(treedef, out)
