"""Sharding context: lets the model apply per-layer sharding constraints
inside scan bodies without coupling model code to a mesh.

Why this exists: gradients of scanned (stacked) parameters are accumulated in
the backward while-loop carry. GSPMD does not reliably propagate an
*after-the-fact* output constraint into that carry, so without an in-body
constraint the accumulator materializes replicated — for mixtral that is a
~188 GB fp32 buffer per device. Constraining the *sliced forward params*
inside the body transposes (VJP of with_sharding_constraint is
with_sharding_constraint) onto the grad slices, keeping the accumulator in
the ZeRO layout.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.rules import auto_axes, dp_axes, dp_size, spec_for, with_zero

__all__ = [
    "sharding_ctx",
    "ctx_axes",
    "ctx_mesh",
    "constrain_layer_params",
    "constrain_activation",
]

_CTX: contextvars.ContextVar[Optional[dict]] = contextvars.ContextVar(
    "repro_sharding_ctx", default=None
)

_IS_AXES_LEAF = lambda a: isinstance(a, tuple) and all(isinstance(s, str) for s in a)


@contextlib.contextmanager
def sharding_ctx(mesh: Mesh, axes=None, *, zero: bool = True):
    """``axes`` is the full model axes tree (from init_model); the model pulls
    per-unit sub-axes out of it when applying in-body constraints."""
    token = _CTX.set({"mesh": auto_axes(mesh), "zero": zero, "axes": axes})
    try:
        yield
    finally:
        _CTX.reset(token)


def ctx_mesh() -> Optional[Mesh]:
    """The active context's mesh (``Auto`` axes), or None outside one."""
    ctx = _CTX.get()
    return None if ctx is None else ctx["mesh"]


def ctx_axes(section: str):
    """Axes list for 'decoder'/'encoder' units, or None if no context."""
    ctx = _CTX.get()
    if ctx is None or ctx.get("axes") is None:
        return None
    return ctx["axes"].get(section)


def constrain_layer_params(p_sub, axes_sub):
    """Constrain one layer's (sliced) params to their TP(+ZeRO) layout.
    axes_sub leaves still carry the leading 'layers' name — dropped here."""
    ctx = _CTX.get()
    if ctx is None:
        return p_sub
    mesh, zero = ctx["mesh"], ctx["zero"]

    def one(x, a):
        a = a[1:] if (len(a) == x.ndim + 1 and a[0] == "layers") else a
        if len(a) != x.ndim:
            return x
        spec = spec_for(tuple(x.shape), a, mesh)
        if zero:
            spec = with_zero(tuple(x.shape), spec, mesh, axes=a)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(one, p_sub, axes_sub, is_leaf=_IS_AXES_LEAF)


def constrain_activation(x):
    """Constrain a (B, S, D) activation to batch-over-dp."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh = ctx["mesh"]
    n_dp = dp_size(mesh)
    if x.ndim < 2 or n_dp <= 1 or x.shape[0] % n_dp:
        return x
    dps = dp_axes(mesh)
    entry = dps if len(dps) > 1 else dps[0]
    spec = P(*([entry] + [None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
