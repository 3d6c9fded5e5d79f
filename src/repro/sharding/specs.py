"""Sharding trees for params, optimizer states, batches, and caches.

The optimizer-state walker mirrors the param tree: each param leaf maps to a
state leaf that may be a raw array (same spec + ZeRO), a QuantizedTensor
(codes shaped like the param with a halved last dim -> param spec + ZeRO;
scales replicated or ZeRO-sharded when large), or a FactoredMoment (small —
replicated).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.optimizers.base import FactoredMoment
from repro.core.quantizer import QuantizedTensor
from repro.sharding.rules import auto_axes, dp_axes, dp_size, spec_for, with_zero

__all__ = [
    "param_shardings",
    "opt_state_shardings",
    "batch_shardings",
    "cache_shardings",
    "replicated",
]

_IS_AXES_LEAF = lambda a: isinstance(a, tuple) and all(isinstance(s, str) for s in a)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(auto_axes(mesh), P())


def param_shardings(params, axes, mesh: Mesh, zero: bool = False):
    """Tree of NamedSharding matching ``params``. ``zero=True`` additionally
    shards each tensor's largest free dim over pod×data (ZeRO-3-style master
    sharding: fp32 masters never exist replicated; compute all-gathers bf16
    casts on demand)."""

    mesh = auto_axes(mesh)
    def one(p, a):
        spec = spec_for(tuple(p.shape), a, mesh)
        if zero:
            spec = with_zero(tuple(p.shape), spec, mesh, axes=a)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(one, params, axes, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict))


def _zero_spec(shape: Tuple[int, ...], base: P, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, with_zero(shape, base, mesh))


def _sanitize_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop axis assignments whose dim is no longer divisible (e.g. packed
    4-bit codes halve the last dim: a 16-expert 'model' shard of dim 16
    becomes dim 8)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for d, e in enumerate(entries):
        if e is None:
            continue
        names = e if isinstance(e, tuple) else (e,)
        k = 1
        for n in names:
            k *= sizes[n]
        if shape[d] % k:
            entries[d] = None
    return P(*entries)


def _state_leaf_shardings(param, axes, leaf, mesh: Mesh, zero: bool):
    """Sharding subtree for one optimizer-state leaf.

    A leaf whose logical shape matches the param's is a *moment* and follows
    the param's TP spec (+ ZeRO).  A leaf with a different shape is a
    *matrix-factor* stack (Shampoo's blocked Kronecker statistics /
    preconditioners, shape ``(nblocks, B, B)``): it has no TP layout of its
    own, so it carries no base spec and is ZeRO-sharded over its largest
    divisible dim — factor state is by far the heaviest part of a Shampoo
    tree, and leaving it replicated would forfeit the ZeRO win exactly where
    it matters most.  Empty placeholders (vector params' ``(0,)`` factor
    slots) stay replicated.
    """
    p_spec = spec_for(tuple(param.shape), axes, mesh)
    mirrors = tuple(getattr(leaf, "shape", ())) == tuple(param.shape)

    if isinstance(leaf, QuantizedTensor):
        codes_shape = tuple(leaf.codes.shape)
        codes_spec = _sanitize_spec(p_spec if mirrors else P(), codes_shape, mesh)
        if zero:
            codes = _zero_spec(codes_shape, codes_spec, mesh)
        else:
            codes = NamedSharding(mesh, codes_spec)
        scale_shardings = []
        for s in leaf.scales:
            if zero and s.size >= 1 << 16 and s.ndim == 1 and s.shape[0] % dp_size(mesh) == 0:
                scale_shardings.append(_zero_spec(tuple(s.shape), P(), mesh))
            else:
                scale_shardings.append(replicated(mesh))
        return QuantizedTensor(codes, tuple(scale_shardings), leaf.shape, leaf.config)
    if isinstance(leaf, FactoredMoment):
        return FactoredMoment(replicated(mesh), replicated(mesh), leaf.shape)
    if not mirrors and (leaf.size == 0 or not zero):
        return replicated(mesh)
    # raw fp32 moment (param spec + ZeRO) or factor stack (ZeRO only)
    if zero:
        return _zero_spec(tuple(leaf.shape), p_spec if mirrors else P(), mesh)
    return NamedSharding(mesh, p_spec)


def opt_state_shardings(opt_state, params, axes, mesh: Mesh, zero: bool = True):
    """Shardings mirroring any optimizer-state pytree.

    Works structurally rather than by fixed dict keys so it covers both the
    legacy ``{'m':…, 'v':…, 'step':…}`` layout and transform-chain states
    (``ChainState`` / ``CompressedState`` / per-rule NamedTuples): any
    subtree that *mirrors the param tree* (one state leaf per param leaf with
    matching logical shape — raw array, ``QuantizedTensor``, or
    ``FactoredMoment``) is sharded like the params (+ ZeRO); step counters
    and other scalars are replicated; unrecognized leaves fall back to
    replicated.
    """
    mesh = auto_axes(mesh)
    from repro.core.optimizers.transform import ChainState, MaskedNode, PartitionState

    treedef = jax.tree_util.tree_structure(params)
    p_leaves = jax.tree_util.tree_leaves(params)
    a_leaves = jax.tree_util.tree_leaves(axes, is_leaf=_IS_AXES_LEAF)

    def _mirror_leaves(sub):
        """State subtrees at param-leaf positions, or None if not a mirror.

        ``MaskedNode`` leaves (partitioned states: positions owned by another
        partition) count as mirroring — they flatten to nothing, so the
        sharding tree just carries a matching ``MaskedNode`` placeholder.
        Leaf shapes need NOT match the param's: a mismatched array (or
        ``QuantizedTensor``) at a param position is a matrix-factor leaf
        (Shampoo Kronecker blocks) and gets factor sharding in
        ``_state_leaf_shardings``.
        """
        try:
            s_leaves = treedef.flatten_up_to(sub)
        except (ValueError, TypeError, KeyError):
            return None
        if len(s_leaves) != len(p_leaves):
            return None
        for s in s_leaves:
            if isinstance(s, (MaskedNode, QuantizedTensor, FactoredMoment)):
                continue
            if hasattr(s, "shape") and not isinstance(s, (dict, list, tuple)):
                continue
            return None
        return s_leaves

    def walk(sub):
        if sub is None:
            return None
        s_leaves = _mirror_leaves(sub)
        if s_leaves is not None:
            return jax.tree_util.tree_unflatten(
                treedef,
                [
                    s
                    if isinstance(s, MaskedNode)
                    else _state_leaf_shardings(p, a, s, mesh, zero)
                    for p, a, s in zip(p_leaves, a_leaves, s_leaves)
                ],
            )
        if isinstance(sub, ChainState):
            return ChainState(walk(s) for s in sub.states)
        if isinstance(sub, PartitionState):
            return PartitionState(
                {lab: walk(s) for lab, s in sub.states.items()}, sub.param_paths
            )
        if isinstance(sub, tuple) and hasattr(sub, "_fields"):  # NamedTuple state
            return type(sub)(*(walk(v) for v in sub))
        if isinstance(sub, dict):
            return {k: walk(v) for k, v in sub.items()}
        if isinstance(sub, (tuple, list)):
            return type(sub)(walk(v) for v in sub)
        if isinstance(sub, QuantizedTensor):  # outside a mirror: stay replicated
            return QuantizedTensor(
                replicated(mesh),
                tuple(replicated(mesh) for _ in sub.scales),
                sub.shape,
                sub.config,
            )
        if isinstance(sub, FactoredMoment):
            return FactoredMoment(replicated(mesh), replicated(mesh), sub.shape)
        return replicated(mesh)  # step counters and other scalars/arrays

    return walk(opt_state)


def batch_shardings(batch, mesh: Mesh):
    """Shard the leading (batch) dim over pod×data when divisible."""
    mesh = auto_axes(mesh)
    dps = dp_axes(mesh)
    n_dp = dp_size(mesh)
    dp_entry = dps if len(dps) > 1 else (dps[0] if dps else None)

    def one(x):
        if x.ndim == 0:
            return replicated(mesh)
        # mrope positions are (3, B, S): batch on dim 1
        batch_dim = 1 if (x.ndim >= 2 and x.shape[0] == 3 and x.shape[1] != 3) else 0
        if x.shape[batch_dim] % n_dp == 0 and n_dp > 1:
            entries = [None] * x.ndim
            entries[batch_dim] = dp_entry
            return NamedSharding(mesh, P(*entries))
        return replicated(mesh)

    return jax.tree_util.tree_map(one, batch)


def cache_shardings(caches, mesh: Mesh):
    """Decode caches: batch over dp AND cache slots over 'model'.

    Slot sharding is split-K (flash-decoding) STORAGE: a 32k x batch-128 KV
    cache is 26-40 GB/device when only batch-sharded; slots over the 16-way
    model axis cut it 16x. Attention reads gather one slot-chunk at a time
    (transient), so HBM residency stays sharded. When batch does not divide
    dp (long_500k batch=1), slots shard over 'data' as well (sequence
    parallelism)."""
    mesh = auto_axes(mesh)
    dps = dp_axes(mesh)
    n_dp = dp_size(mesh)
    dp_entry = dps if len(dps) > 1 else (dps[0] if dps else None)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(x):
        entries = [None] * x.ndim
        used_batch = False
        # stacked cache leaves: (repeat, B, slots?, ...) for KV / GLA states
        if x.ndim >= 2 and n_dp > 1 and x.shape[1] % n_dp == 0:
            entries[1] = dp_entry
            used_batch = True
        if x.ndim >= 4 and "model" in sizes:
            # dim 2 is the slots dim of stacked KV caches (rank >= 4)
            if x.shape[2] % sizes["model"] == 0 and x.shape[2] >= 256:
                entries[2] = "model"
                if not used_batch and "data" in sizes and x.shape[2] % (
                    sizes["model"] * sizes["data"]
                ) == 0:
                    entries[2] = ("data", "model")
        if any(e is not None for e in entries):
            return NamedSharding(mesh, P(*entries))
        return replicated(mesh)

    return jax.tree_util.tree_map(one, caches)
