"""Composable gradient-transformation API (optax-style) with one shared
compressed-state wrapper implementing the paper's Alg. 1.

Why
---
Every optimizer in the paper's zoo used to re-implement its own pytree
flatten / per-leaf decompress->step->compress loop.  This module factors the
optimizer layer into orthogonal pieces so the Alg. 1 compression machinery
exists exactly once:

* ``GradientTransformation`` — an ``(init, update)`` pair over *updates*
  (gradients flowing through the chain), not parameters.  ``update`` has the
  signature ``update(updates, state, params=None, *, key=None)`` and returns
  ``(new_updates, new_state)``.
* pure update rules — ``scale_by_adam`` (Eq. 1), ``trace`` (Alg. 2 SGDM
  accumulator), ``scale_by_sm3``, ``scale_by_factored_rms`` (Adafactor),
  ``add_decayed_weights``, ``scale_by_learning_rate`` (schedule-aware).
* ``compressed(inner, policies)`` — THE Alg. 1 wrapper.  It owns per-leaf
  ``QuantPolicy`` resolution (paper App. D.1), decompress (line 3) before the
  inner rule runs, compress (line 5) after, the stochastic-rounding PRNG-key
  plumbing, and routing of eligible leaves through the fused Pallas kernel
  (``FusedAdamWRoute``).  Inner transforms only ever see fp32 moments (or a
  ``FactoredMoment``, which they update structurally).
* ``chain(*transforms)`` — composes transforms left to right.
* ``partition(transforms, labels)`` — optax.multi_transform-style routing of
  parameter subtrees to different chains (e.g. fp32 embeddings + 4-bit body),
  subsuming the regex ``exclude`` mechanism for new configurations.
* ``as_optimizer(tx)`` — adapts a chain to the repo-wide ``Optimizer``
  facade: ``params2 = params + final_updates`` (with ``Replace`` leaves from
  the fused kernel applied verbatim).

How ``compressed`` maps to Alg. 1
---------------------------------
For each parameter leaf ``p`` with gradient ``g`` and compressed state
``s̄``::

    line 3:  s  = decompress(s̄)            # compressed() before inner.update
    line 4:  s' = A(g, s, p)               # the wrapped inner transform
    line 5:  s̄' = compress(s')             # compressed() after inner.update

``policies`` maps *inner-state field names* (e.g. ``{"m": ..., "v": ...}``)
to ``QuantPolicy``.  Per leaf, the policy resolves to 'raw' (fp32), 'quant'
(``QuantizedTensor``) or 'factor' (``FactoredMoment``, for rules that
understand it, e.g. the second moment of ``scale_by_adam``).

Migration notes (pre-chain ``quantized_adamw`` callers)
-------------------------------------------------------
* Constructors (``adamw32/8bit/4bit``, ``factor4bit``, ``sgdm{,4bit}``,
  ``sm3``, ``adafactor``) keep their exact signatures and produce
  bit-identical trajectories (tests/test_transforms.py); only the *state
  pytree layout* changed: it is now a ``ChainState`` of per-transform states,
  so old checkpoints must be re-created.
* ``state["m"] / state["v"] / state["trace"]`` still work: ``ChainState``
  resolves string keys by searching the nested transform states, so code
  that inspects moments (tests, memory accounting) needs no change.  SGDM's
  momentum field is named ``trace`` (was ``"m"``).
* ``opt.update(grads, state, params, key=...)`` is unchanged at the
  ``Optimizer`` facade; the key now threads through ``compressed()``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.optimizers.base import (
    FactoredMoment,
    Optimizer,
    QuantPolicy,
    compress_moment,
    decompress_moment,
    tree_paths,
)
from repro.core.quantizer import QuantizedTensor, quantize

__all__ = [
    "GradientTransformation",
    "ChainState",
    "EmptyState",
    "Replace",
    "chain",
    "compressed",
    "partition",
    "PartitionState",
    "MaskedNode",
    "label_by_regex",
    "as_optimizer",
    "apply_updates",
    "scale_by_adam",
    "trace",
    "scale_by_sm3",
    "scale_by_factored_rms",
    "scale_by_shampoo",
    "add_decayed_weights",
    "scale_by_learning_rate",
    "FusedAdamWRoute",
]

PyTree = Any
Schedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


class GradientTransformation(NamedTuple):
    """An (init, update) pair over *updates* (optax-style).

    ``init(params) -> state``;
    ``update(updates, state, params=None, *, key=None) -> (updates, state)``.
    """

    init: Callable[[PyTree], PyTree]
    update: Callable[..., Tuple[PyTree, PyTree]]


class EmptyState(NamedTuple):
    """State of a stateless transform."""


def _resolve_lr(lr: Schedule, step: jnp.ndarray) -> jnp.ndarray:
    return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


# ---------------------------------------------------------------------------
# update-tree plumbing: Replace leaves + leaf-wise maps that respect them
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Replace:
    """An update leaf carrying the *new parameter value* verbatim.

    Emitted by fused whole-step paths (the Pallas kernel computes
    ``w_new`` in-kernel, including lr/weight-decay).  Downstream transforms
    pass it through untouched and ``apply_updates`` installs it as-is, so the
    fused result is bit-identical regardless of what else is in the chain.
    """

    def __init__(self, value):
        self.value = value

    def tree_flatten(self):
        return (self.value,), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def __repr__(self) -> str:  # pragma: no cover
        return f"Replace({self.value!r})"


_IS_UPDATE_LEAF = lambda x: isinstance(x, Replace)


def tree_map_updates(f, updates: PyTree, *rest: PyTree) -> PyTree:
    """tree_map over update leaves that passes ``Replace`` leaves through."""
    leaves, treedef = jax.tree_util.tree_flatten(updates, is_leaf=_IS_UPDATE_LEAF)
    rest_leaves = [treedef.flatten_up_to(r) for r in rest]
    out = [
        u if isinstance(u, Replace) else f(u, *(rl[i] for rl in rest_leaves))
        for i, u in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    """``p' = (p_f32 + u).astype(p.dtype)``; ``Replace`` leaves verbatim."""
    leaves_u, treedef = jax.tree_util.tree_flatten(updates, is_leaf=_IS_UPDATE_LEAF)
    leaves_p = treedef.flatten_up_to(params)
    out = [
        u.value
        if isinstance(u, Replace)
        else (p.astype(jnp.float32) + u).astype(p.dtype)
        for p, u in zip(leaves_p, leaves_u)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# chain
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_with_keys_class
class ChainState:
    """Tuple of per-transform states with a migration-friendly ``[]``.

    ``state[i]`` is the i-th transform's state; ``state["m"]`` searches the
    nested states for a field of that name (so pre-refactor code reading
    ``state["m"]["w"].codes`` keeps working on chain-built optimizers).
    """

    __slots__ = ("states",)

    def __init__(self, states):
        self.states = tuple(states)

    def tree_flatten_with_keys(self):
        # keyed flattening => checkpoint manifests record readable paths
        # (".states[2].inner.m['embed'].codes") instead of flat indices.
        return ((jax.tree_util.GetAttrKey("states"), self.states),), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0])

    def __getitem__(self, key):
        if isinstance(key, (int, slice)):
            return self.states[key]
        found = _find_state_field(self.states, key)
        if found is _NOT_FOUND:
            raise KeyError(key)
        return found

    def __len__(self) -> int:
        return len(self.states)

    def __repr__(self) -> str:  # pragma: no cover
        return f"ChainState({list(self.states)!r})"


_NOT_FOUND = object()


def _find_state_field(node, name: str):
    """DFS for a NamedTuple field (or dict key) called ``name``."""
    if isinstance(node, ChainState):
        node = node.states
    if isinstance(node, tuple) and hasattr(node, "_fields"):
        if name in node._fields and getattr(node, name) is not None:
            # None fields are absent moments (e.g. adafactor b1=0 has no m);
            # keep searching so the lookup raises KeyError like the old dicts.
            return getattr(node, name)
        children = tuple(node)
    elif isinstance(node, dict):
        if name in node:
            return node[name]
        children = tuple(node.values())
    elif isinstance(node, (tuple, list)):
        children = tuple(node)
    else:
        return _NOT_FOUND
    for child in children:
        found = _find_state_field(child, name)
        if found is not _NOT_FOUND:
            return found
    return _NOT_FOUND


def chain(*transforms: GradientTransformation) -> GradientTransformation:
    """Compose transforms; updates flow left to right through each."""

    def init(params):
        return ChainState(tx.init(params) for tx in transforms)

    def update(updates, state, params=None, *, key=None):
        new_states = []
        for tx, s in zip(transforms, state.states):
            updates, s2 = tx.update(updates, s, params, key=key)
            new_states.append(s2)
        return updates, ChainState(new_states)

    return GradientTransformation(init, update)


def as_optimizer(tx: GradientTransformation, name: str = "optimizer") -> Optimizer:
    """Adapt a transformation chain to the (init, update)->params facade."""

    def init(params):
        return tx.init(params)

    def update(grads, state, params, key: Optional[jax.Array] = None):
        updates, new_state = tx.update(grads, state, params, key=key)
        return apply_updates(params, updates), new_state

    return Optimizer(init=init, update=update, name=name)


# ---------------------------------------------------------------------------
# pure update rules
# ---------------------------------------------------------------------------


class ScaleByAdamState(NamedTuple):
    count: jnp.ndarray
    m: PyTree
    v: PyTree


def scale_by_adam(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8
) -> GradientTransformation:
    """Bias-corrected Adam direction (paper Eq. 1): ``m̂ / (sqrt(v̂)+eps)``.

    A second-moment leaf may be a ``FactoredMoment`` (installed by
    ``compressed`` under a ``factor_2d`` policy): it is updated structurally
    via its row/col EMA and reconstructed for the denominator.
    """

    def init(params):
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        return ScaleByAdamState(jnp.zeros((), jnp.int32), zeros(), zeros())

    def update(updates, state, params=None, *, key=None):
        del params, key
        count = state.count + 1
        bc1 = 1.0 - jnp.power(jnp.float32(b1), count.astype(jnp.float32))
        bc2 = 1.0 - jnp.power(jnp.float32(b2), count.astype(jnp.float32))

        leaves_g, treedef = jax.tree_util.tree_flatten(updates)
        leaves_m = treedef.flatten_up_to(state.m)
        leaves_v = treedef.flatten_up_to(state.v)

        out, new_m, new_v = [], [], []
        for g, m, v in zip(leaves_g, leaves_m, leaves_v):
            g = g.astype(jnp.float32)
            m2 = b1 * m + (1.0 - b1) * g
            if isinstance(v, FactoredMoment):
                v2 = v.ema_update(g * g, b2)
                v_full = v2.reconstruct()
            else:
                v2 = b2 * v + (1.0 - b2) * g * g
                v_full = v2
            m_hat = m2 / bc1
            v_hat = v_full / bc2
            out.append(m_hat / (jnp.sqrt(v_hat) + eps))
            new_m.append(m2)
            new_v.append(v2)

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        return unf(out), ScaleByAdamState(count, unf(new_m), unf(new_v))

    return GradientTransformation(init, update)


class TraceState(NamedTuple):
    trace: PyTree


def trace(decay: float) -> GradientTransformation:
    """SGDM accumulator (paper Alg. 2 line 4): ``t = decay*t + g`` (no
    ``(1-decay)`` damping — the convention Theorem 1's constants assume)."""

    def init(params):
        return TraceState(
            jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        )

    def update(updates, state, params=None, *, key=None):
        del params, key
        leaves_g, treedef = jax.tree_util.tree_flatten(updates)
        leaves_t = treedef.flatten_up_to(state.trace)
        new_t = [decay * t + g.astype(jnp.float32) for g, t in zip(leaves_g, leaves_t)]
        tree = jax.tree_util.tree_unflatten(treedef, new_t)
        return tree, TraceState(tree)

    return GradientTransformation(init, update)


class Sm3State(NamedTuple):
    acc: PyTree
    m: PyTree


def _broadcast_min(accs, shape):
    """nu_ij = min_r acc_r[i_r] broadcast to ``shape`` (SM3 Alg. 4 style)."""
    out = None
    for r, acc in enumerate(accs):
        view = [1] * len(shape)
        view[r] = shape[r]
        b = acc.reshape(view)
        out = b if out is None else jnp.minimum(out, b)
    return jnp.broadcast_to(out, shape)


def scale_by_sm3(b1: float = 0.9, eps: float = 1e-8) -> GradientTransformation:
    """SM3 (Anil et al. 2019): sublinear accumulators (one vector per tensor
    dim) + the β1>0 momentum variant the paper compares against."""

    def init(params):
        def init_acc(p):
            if p.ndim == 0:
                return (jnp.zeros((1,), jnp.float32),)
            return tuple(jnp.zeros((d,), jnp.float32) for d in p.shape)

        return Sm3State(
            acc=jax.tree_util.tree_map(
                init_acc, params, is_leaf=lambda x: hasattr(x, "shape")
            ),
            m=jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ),
        )

    def update(updates, state, params=None, *, key=None):
        del params, key
        leaves_g, treedef = jax.tree_util.tree_flatten(updates)
        leaves_acc = treedef.flatten_up_to(state.acc)
        leaves_m = treedef.flatten_up_to(state.m)

        out, new_acc, new_m = [], [], []
        for g, accs, m in zip(leaves_g, leaves_acc, leaves_m):
            g = g.astype(jnp.float32)
            shape = g.shape if g.ndim > 0 else (1,)
            g_ = g.reshape(shape)
            nu = _broadcast_min(accs, shape) + g_ * g_
            accs2 = tuple(
                jnp.max(nu, axis=tuple(i for i in range(len(shape)) if i != r))
                for r in range(len(shape))
            )
            u = (g_ / (jnp.sqrt(nu) + eps)).reshape(g.shape)
            m2 = b1 * m + (1 - b1) * u
            out.append(m2)
            new_acc.append(accs2)
            new_m.append(m2)

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        return unf(out), Sm3State(unf(new_acc), unf(new_m))

    return GradientTransformation(init, update)


class FactoredRmsState(NamedTuple):
    count: jnp.ndarray
    v: PyTree
    m: Optional[PyTree]


def scale_by_factored_rms(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-30,
    clip_threshold: float = 1.0,
) -> GradientTransformation:
    """Adafactor (Shazeer & Stern 2018): factored second moment for ndim>=2,
    RMS update clipping, optional first moment (``b1 == 0`` disables it)."""

    def init(params):
        v = jax.tree_util.tree_map(
            lambda p: FactoredMoment.zeros(p.shape)
            if p.ndim >= 2
            else jnp.zeros(p.shape, jnp.float32),
            params,
        )
        m = None
        if b1 > 0:
            m = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
        return FactoredRmsState(jnp.zeros((), jnp.int32), v, m)

    def update(updates, state, params=None, *, key=None):
        del params, key
        count = state.count + 1
        bc2 = 1.0 - jnp.power(jnp.float32(b2), count.astype(jnp.float32))

        leaves_g, treedef = jax.tree_util.tree_flatten(updates)
        leaves_v = treedef.flatten_up_to(state.v)
        leaves_m = (
            treedef.flatten_up_to(state.m)
            if state.m is not None
            else [None] * len(leaves_g)
        )

        out, new_v, new_m = [], [], []
        for g, v, m in zip(leaves_g, leaves_v, leaves_m):
            g = g.astype(jnp.float32)
            sq = g * g + eps
            if isinstance(v, FactoredMoment):
                v2 = v.ema_update(sq, b2)
                v_hat = v2.reconstruct() / bc2
            else:
                v2 = b2 * v + (1 - b2) * sq
                v_hat = v2 / bc2
            u = g / jnp.sqrt(jnp.maximum(v_hat, eps))
            # Adafactor update clipping: divide by max(1, RMS(u)/d).
            rms_u = jnp.sqrt(jnp.mean(u * u) + 1e-30)
            u = u / jnp.maximum(1.0, rms_u / clip_threshold)
            if m is not None:
                m2 = b1 * m + (1 - b1) * u
                new_m.append(m2)
                u = m2
            out.append(u)
            new_v.append(v2)

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        return unf(out), FactoredRmsState(
            count, unf(new_v), unf(new_m) if state.m is not None else None
        )

    return GradientTransformation(init, update)


class ScaleByShampooState(NamedTuple):
    count: jnp.ndarray
    m: PyTree  # grafting first moment (Adam m)
    v: PyTree  # grafting second moment (Adam v)
    stats_l: PyTree  # (nblocks, Br, Br) left Kronecker statistics L += G Gᵀ
    stats_r: PyTree  # (nblocks, Bc, Bc) right Kronecker statistics R += Gᵀ G
    precond_l: PyTree  # (nblocks, Br, Br) L^{-1/4}
    precond_r: PyTree  # (nblocks, Bc, Bc) R^{-1/4}


def _shampoo_geometry(shape: Tuple[int, ...], block_size: int):
    """Static blocking of a >=2-d param: leading dims merge into rows, the
    trailing dim is columns; each dim tiles at min(block_size, dim)."""
    n = 1
    for d in shape[:-1]:
        n *= int(d)
    m = int(shape[-1])
    br = min(block_size, n)
    bc = min(block_size, m)
    nb_r = -(-n // br)
    nb_c = -(-m // bc)
    return n, m, br, bc, nb_r, nb_c


def _shampoo_to_blocks(x2d, n, m, br, bc, nb_r, nb_c):
    x = jnp.pad(x2d, ((0, nb_r * br - n), (0, nb_c * bc - m)))
    x = x.reshape(nb_r, br, nb_c, bc).transpose(0, 2, 1, 3)
    return x.reshape(nb_r * nb_c, br, bc)


def _shampoo_from_blocks(bx, n, m, br, bc, nb_r, nb_c):
    x = bx.reshape(nb_r, nb_c, br, bc).transpose(0, 2, 1, 3)
    return x.reshape(nb_r * br, nb_c * bc)[:n, :m]


def _shampoo_pad_diag(n, m, br, bc, nb_r, nb_c):
    """Per-block diagonal indicators of PADDED rows/cols (static fp32 masks).

    Padded dims get +1.0 on the statistics diagonal before the inverse root
    so their eigenvalues sit at ~1.0 (inert: the root maps them to ~1.0)
    instead of at the ridge eps, whose eps^{-1/4} would both poison the
    blockwise absmax scales of quantized preconditioner factors and be
    multiplied only by zero-padded gradient entries anyway.
    """
    rows = np.arange(nb_r * br).reshape(nb_r, br) >= n
    cols = np.arange(nb_c * bc).reshape(nb_c, bc) >= m
    pad_l = np.repeat(rows, nb_c, axis=0).astype(np.float32)  # (nb, br)
    pad_r = np.tile(cols, (nb_r, 1)).astype(np.float32)  # (nb, bc)
    return jnp.asarray(pad_l), jnp.asarray(pad_r)


def _inv_quarter_root(stats, pad_diag, ridge, floor_rel):
    """(stats + ridge*I + diag(pad))^{-1/4} per block, via batched eigh.

    Eigenvalues are floored at ``max(ridge, floor_rel * λ_max)`` per block.
    The RELATIVE floor is load-bearing for quantized factors: 4-bit
    requantization noise on the statistics manufactures spurious near-zero
    (even negative) eigenvalues, and an absolute floor lets their ^{-1/4}
    amplification (~ridge^{-1/4}) dominate the direction with pure noise as
    gradients shrink.  Flooring relative to the block's top eigenvalue caps
    the amplification ratio at ``floor_rel^{-1/4}`` no matter the scale —
    the same trick production Shampoo implementations use for their
    ``matrix_epsilon``.
    """
    d = stats.shape[-1]
    eye = jnp.eye(d, dtype=jnp.float32)
    a = stats + ridge * eye + pad_diag[:, :, None] * eye
    w, u = jnp.linalg.eigh(a)
    wmax = jnp.max(w, axis=-1, keepdims=True)
    w = jnp.maximum(w, jnp.maximum(ridge, floor_rel * wmax))
    return jnp.einsum("kij,kj,klj->kil", u, w**-0.25, u)


def scale_by_shampoo(
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    *,
    block_size: int = 128,
    precond_every: int = 10,
    matrix_eps: float = 1e-6,
    floor_rel: float = 0.01,
) -> GradientTransformation:
    """Blocked Shampoo (Gupta et al. 2018, the block-diagonal variant of
    Anil et al. 2020) with AdamW-shaped grafting, as a pure rule.

    Each >=2-d param is matricized (leading dims -> rows) and tiled into
    blocks of at most ``block_size`` per side.  Per block::

        L <- b2 L + (1-b2) G Gᵀ        R <- b2 R + (1-b2) Gᵀ G
        every precond_every steps:  P_L = L̂^{-1/4},  P_R = R̂^{-1/4}   (eigh)
        direction  D = P_L m̂ P_R       (m̂ = bias-corrected momentum)

    The emitted update grafts D onto the AdamW direction's norm
    (``D * ||adam_dir|| / ||D||`` per leaf), so the step SIZE schedule is
    exactly AdamW's while the step DIRECTION is second-order — the standard
    trick that lets Shampoo reuse first-order lr tuning, and what makes the
    downstream chain (weight decay + lr) AdamW-shaped.  Params with ndim < 2
    fall back to the AdamW direction and hold empty ``(0,)`` factor
    placeholders.

    All four factor trees (``stats_l/stats_r/precond_l/precond_r``) mirror
    the param tree one array per leaf, so ``compressed()`` can hold them as
    4-bit ``QuantizedTensor``s like any first-order moment (*4-bit Shampoo*).
    Inverse roots are recomputed every ``precond_every`` steps under
    ``lax.cond``; between recomputes the stale ``P`` is reused.
    ``floor_rel`` floors each block's eigenvalues relative to its largest
    before the inverse root — see ``_inv_quarter_root`` for why this is
    essential once the factors are quantized.
    """

    def _placeholder():
        return jnp.zeros((0,), jnp.float32)

    def init(params):
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def factor(p, side, identity):
            if p.ndim < 2:
                return _placeholder()
            n, m, br, bc, nb_r, nb_c = _shampoo_geometry(p.shape, block_size)
            d = br if side == "l" else bc
            nb = nb_r * nb_c
            base = jnp.zeros((nb, d, d), jnp.float32)
            return base + jnp.eye(d, dtype=jnp.float32) if identity else base

        f = lambda side, identity: jax.tree_util.tree_map(
            lambda p: factor(p, side, identity), params
        )
        return ScaleByShampooState(
            jnp.zeros((), jnp.int32),
            zeros(),
            zeros(),
            f("l", False),
            f("r", False),
            f("l", True),
            f("r", True),
        )

    def update(updates, state, params=None, *, key=None):
        del params, key
        count = state.count + 1
        cf = count.astype(jnp.float32)
        bc1 = 1.0 - jnp.power(jnp.float32(b1), cf)
        bc2 = 1.0 - jnp.power(jnp.float32(b2), cf)
        # Recompute on step 1 (so the first update is already preconditioned
        # by the first gradient's statistics) and every precond_every after.
        recompute = ((count - 1) % precond_every) == 0

        leaves_g, treedef = jax.tree_util.tree_flatten(updates)
        fields = {
            name: treedef.flatten_up_to(getattr(state, name))
            for name in ("m", "v", "stats_l", "stats_r", "precond_l", "precond_r")
        }

        out = []
        new = {name: [] for name in fields}
        for i, g in enumerate(leaves_g):
            g = g.astype(jnp.float32)
            m, v = fields["m"][i], fields["v"][i]
            sl, sr = fields["stats_l"][i], fields["stats_r"][i]
            pl, pr = fields["precond_l"][i], fields["precond_r"][i]

            m2 = b1 * m + (1.0 - b1) * g
            v2 = b2 * v + (1.0 - b2) * g * g
            adam_dir = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            new["m"].append(m2)
            new["v"].append(v2)

            if g.ndim < 2:
                out.append(adam_dir)
                for name in ("stats_l", "stats_r", "precond_l", "precond_r"):
                    new[name].append(fields[name][i])
                continue

            geo = _shampoo_geometry(g.shape, block_size)
            n, mm = geo[0], geo[1]
            pad_l, pad_r = _shampoo_pad_diag(*geo)
            gb = _shampoo_to_blocks(g.reshape(n, mm), *geo)
            sl2 = b2 * sl + (1.0 - b2) * jnp.einsum("kij,klj->kil", gb, gb)
            sr2 = b2 * sr + (1.0 - b2) * jnp.einsum("kji,kjl->kil", gb, gb)
            pl2 = jax.lax.cond(
                recompute,
                lambda s, old: _inv_quarter_root(s / bc2, pad_l, matrix_eps, floor_rel),
                lambda s, old: old,
                sl2,
                pl,
            )
            pr2 = jax.lax.cond(
                recompute,
                lambda s, old: _inv_quarter_root(s / bc2, pad_r, matrix_eps, floor_rel),
                lambda s, old: old,
                sr2,
                pr,
            )
            mb = _shampoo_to_blocks((m2 / bc1).reshape(n, mm), *geo)
            db = jnp.einsum("kij,kjl,klo->kio", pl2, mb, pr2)
            d = _shampoo_from_blocks(db, *geo).reshape(g.shape)
            a_norm = jnp.sqrt(jnp.sum(adam_dir * adam_dir))
            d_norm = jnp.sqrt(jnp.sum(d * d))
            out.append(d * (a_norm / (d_norm + 1e-30)))
            new["stats_l"].append(sl2)
            new["stats_r"].append(sr2)
            new["precond_l"].append(pl2)
            new["precond_r"].append(pr2)

        unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)
        return unf(out), ScaleByShampooState(
            count, *(unf(new[name]) for name in ScaleByShampooState._fields[1:])
        )

    return GradientTransformation(init, update)


def add_decayed_weights(weight_decay: float) -> GradientTransformation:
    """Decoupled weight decay: ``u <- u + weight_decay * p`` (AdamW-style)."""

    def init(params):
        del params
        return EmptyState()

    def update(updates, state, params=None, *, key=None):
        del key
        return (
            tree_map_updates(lambda u, p: u + weight_decay * p, updates, params),
            state,
        )

    return GradientTransformation(init, update)


class ScaleByScheduleState(NamedTuple):
    count: jnp.ndarray


def scale_by_learning_rate(
    lr: Schedule, flip_sign: bool = True
) -> GradientTransformation:
    """Multiply updates by ``-lr(step)`` (descent; ``flip_sign=False`` for
    the raw schedule value).  Keeps its own step count."""

    def init(params):
        del params
        return ScaleByScheduleState(jnp.zeros((), jnp.int32))

    def update(updates, state, params=None, *, key=None):
        del params, key
        count = state.count + 1
        lr_t = _resolve_lr(lr, count)
        mult = -lr_t if flip_sign else lr_t
        return (
            tree_map_updates(lambda u: u * mult, updates),
            ScaleByScheduleState(count),
        )

    return GradientTransformation(init, update)


# ---------------------------------------------------------------------------
# compressed(): the one Alg. 1 wrapper
# ---------------------------------------------------------------------------


class CompressedState(NamedTuple):
    count: jnp.ndarray  # drives bias correction on the fused-kernel path
    inner: Any  # inner state with policy-managed moment trees held compressed


@dataclasses.dataclass(frozen=True)
class FusedAdamWRoute:
    """Routes eligible (p, g, m̄, v̄) leaves through the fused Pallas kernel.

    The kernel computes the *whole* AdamW step (dequant -> Eq. 1 -> requant
    -> param write) in one pass, so the route needs the full hyperparameters
    and emits a ``Replace`` update leaf.  Eligibility mirrors the kernel's
    layout contract: 4-bit B128 m, 4-bit rank-1 v, ndim>=2 param with the
    last dim a multiple of 256 (nibble + B128 tile alignment); leading dims
    run as stacked 2-d slices of ONE launch (the outer grid dim
    walks the slices — a deep layer stack costs a single ``pallas_call``,
    not L of them).  Stochastic-rounding configs are eligible — the kernel
    requantizes with in-tile counter-based Threefry noise keyed by the
    per-leaf SR key, expanded to per-slice seed rows by one vmapped
    ``fold_in`` in ``ops.fused_adamw4_leaf`` (both moments must agree on SR
    so one key derivation covers the leaf).
    """

    lr: Schedule
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    m_field: str = "m"
    v_field: str = "v"

    def eligible(self, comp: Mapping[str, Any], p: jnp.ndarray) -> bool:
        m_s = comp.get(self.m_field)
        v_s = comp.get(self.v_field)
        return (
            isinstance(m_s, QuantizedTensor)
            and m_s.config.bits == 4
            and m_s.config.normalization == "blockwise"
            and m_s.config.block_size == 128
            and isinstance(v_s, QuantizedTensor)
            and v_s.config.bits == 4
            and v_s.config.normalization == "rank1"
            and m_s.config.stochastic_rounding == v_s.config.stochastic_rounding
            and p.ndim >= 2
            and p.shape[-1] % 256 == 0
        )

    def run(
        self,
        p: jnp.ndarray,
        g: jnp.ndarray,
        comp: Mapping[str, Any],
        step: jnp.ndarray,
        key: Optional[jax.Array] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        from repro.kernels import ops as kernel_ops

        lr_t = _resolve_lr(self.lr, step)
        bc1 = 1.0 - jnp.power(jnp.float32(self.b1), step.astype(jnp.float32))
        bc2 = 1.0 - jnp.power(jnp.float32(self.b2), step.astype(jnp.float32))
        w_new, m2, v2 = kernel_ops.fused_adamw4_leaf(
            p, g, comp[self.m_field], comp[self.v_field],
            lr_t, self.b1, self.b2, self.eps, self.weight_decay, bc1, bc2,
            key=key,
        )
        return w_new, {self.m_field: m2, self.v_field: v2}


def compressed(
    inner: GradientTransformation,
    policies: Mapping[str, QuantPolicy],
    *,
    kernel: Optional[FusedAdamWRoute] = None,
) -> GradientTransformation:
    """Wrap ``inner`` so the state trees named by ``policies`` persist
    compressed (Alg. 1).  See the module docstring for the line-by-line
    mapping.  ``kernel`` optionally routes eligible leaves through the fused
    Pallas whole-step path, emitting ``Replace`` update leaves.
    """
    policies = dict(policies)
    field_names = tuple(policies)

    def _leaf_modes(params):
        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        paths = jax.tree_util.tree_leaves(tree_paths(params))
        modes = {
            name: [pol.mode(path, tuple(p.shape)) for path, p in zip(paths, leaves_p)]
            for name, pol in policies.items()
        }
        return leaves_p, treedef, modes

    def init(params):
        leaves_p, treedef, modes = _leaf_modes(params)
        inner_state = inner.init(params)
        replacements = {}
        for name, pol in policies.items():
            s_leaves = treedef.flatten_up_to(getattr(inner_state, name))
            comp = []
            for p, s, mode in zip(leaves_p, s_leaves, modes[name]):
                if mode == "factor":
                    comp.append(FactoredMoment.zeros(tuple(p.shape)))
                else:
                    comp.append(compress_moment(s, mode, pol.config))
            replacements[name] = jax.tree_util.tree_unflatten(treedef, comp)
        return CompressedState(
            jnp.zeros((), jnp.int32), inner_state._replace(**replacements)
        )

    def update(updates, state, params=None, *, key=None):
        count = state.count + 1
        leaves_g, treedef = jax.tree_util.tree_flatten(updates)
        leaves_p = treedef.flatten_up_to(params)
        n = len(leaves_g)

        comp_leaves = {
            name: treedef.flatten_up_to(getattr(state.inner, name))
            for name in field_names
        }

        # Alg. 1 line 3: hand the inner rule fp32 views of quantized moments
        # (FactoredMoment and raw leaves pass through structurally).
        dec_trees = {
            name: jax.tree_util.tree_unflatten(
                treedef,
                [
                    decompress_moment(s) if isinstance(s, QuantizedTensor) else s
                    for s in comp_leaves[name]
                ],
            )
            for name in field_names
        }

        # Alg. 1 line 4: the inner optimizer A.  Kernel-routed leaves are
        # recomputed below and their reference results DCE'd under jit.
        inner_updates, new_inner = inner.update(
            updates, state.inner._replace(**dec_trees), params, key=key
        )
        u_leaves = treedef.flatten_up_to(inner_updates)
        new_leaves = {
            name: treedef.flatten_up_to(getattr(new_inner, name))
            for name in field_names
        }

        out_u = []
        out_state = {name: [] for name in field_names}
        for i in range(n):
            comp_i = {name: comp_leaves[name][i] for name in field_names}
            leaf_key = jax.random.fold_in(key, i) if key is not None else None
            if kernel is not None and kernel.eligible(comp_i, leaves_p[i]):
                w_new, new_comp = kernel.run(
                    leaves_p[i], leaves_g[i], comp_i, count, key=leaf_key
                )
                out_u.append(Replace(w_new))
                for name in field_names:
                    out_state[name].append(new_comp[name])
                continue

            # Alg. 1 line 5: recompress, with per-leaf/per-moment SR keys.
            if leaf_key is not None and len(field_names) > 1:
                field_keys = dict(
                    zip(field_names, jax.random.split(leaf_key, len(field_names)))
                )
            else:
                field_keys = {name: leaf_key for name in field_names}
            out_u.append(u_leaves[i])
            for name in field_names:
                old = comp_i[name]
                new = new_leaves[name][i]
                if isinstance(old, QuantizedTensor):
                    out_state[name].append(
                        quantize(new, old.config, key=field_keys[name])
                    )
                else:
                    out_state[name].append(new)

        replacements = {
            name: jax.tree_util.tree_unflatten(treedef, out_state[name])
            for name in field_names
        }
        return (
            jax.tree_util.tree_unflatten(treedef, out_u),
            CompressedState(count, new_inner._replace(**replacements)),
        )

    return GradientTransformation(init, update)


# ---------------------------------------------------------------------------
# partition(): per-subtree transform routing (optax.multi_transform-style)
# ---------------------------------------------------------------------------


class MaskedNode(NamedTuple):
    """Placeholder for leaves owned by a different partition (no children,
    so masked positions simply vanish from flattened views)."""


@jax.tree_util.register_pytree_with_keys_class
class PartitionState:
    """Per-label sub-states plus the init-time param paths (static aux).

    Recording the paths lets ``update`` detect a param tree that drifted
    since ``init`` — a leaf added after init raises ``KeyError`` instead of
    silently training it with garbage (or no) state.
    """

    __slots__ = ("states", "param_paths")

    def __init__(self, states, param_paths=None):
        self.states = dict(states)
        self.param_paths = None if param_paths is None else tuple(param_paths)

    def tree_flatten_with_keys(self):
        items = sorted(self.states.items())
        return (
            tuple((jax.tree_util.DictKey(k), v) for k, v in items),
            (tuple(k for k, _ in items), self.param_paths),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, paths = aux
        return cls(dict(zip(keys, children)), paths)

    def __repr__(self) -> str:  # pragma: no cover
        return f"PartitionState(labels={sorted(self.states)})"


def label_by_regex(
    patterns, match_label: str, default_label: str
) -> Callable[[str, Any], str]:
    """Label fn: ``match_label`` when the '/'-joined leaf path matches any
    regex, else ``default_label``.  Subsumes ``QuantPolicy.exclude`` at the
    whole-optimizer level (e.g. fp32-AdamW embeddings + 4-bit body)."""
    pats = tuple(patterns)

    def fn(path: str, leaf) -> str:
        del leaf
        return (
            match_label
            if any(re.search(p, path) for p in pats)
            else default_label
        )

    return fn


def partition(
    transforms: Mapping[str, GradientTransformation],
    labels,
) -> GradientTransformation:
    """Route parameter subtrees to different transforms.

    ``labels`` is either a pytree of label strings matching ``params`` or a
    callable ``(path, param) -> label``.  Every label must name an entry of
    ``transforms``.  Each sub-transform sees the full tree with non-owned
    leaves replaced by ``MaskedNode`` (which flatten to nothing), so leaf
    paths — and hence ``QuantPolicy`` decisions — are unchanged.

    Label resolution (path building + regex matching) is cached by the param
    tree's (treedef, leaf shapes): labels are pure functions of structure and
    shape, so steady-state ``update`` calls skip the per-leaf regex walk
    entirely instead of re-labelling every step.
    """
    transforms = dict(transforms)
    _resolved_cache: Dict[Any, Tuple[Any, Tuple[str, ...], Tuple[str, ...]]] = {}

    def _mask(tree, lab_tree, label):
        return jax.tree_util.tree_map(
            lambda x, l: x if l == label else MaskedNode(), tree, lab_tree
        )

    def _check(lab_leaves):
        for l in lab_leaves:
            if l not in transforms:
                raise ValueError(
                    f"partition(): label {l!r} has no transform; "
                    f"known labels: {sorted(transforms)}"
                )

    def _resolved(params):
        """(label tree, label leaves, param paths), cached per tree layout.

        The key covers everything a label fn may legitimately inspect about a
        leaf (structure, shape, dtype) — value-dependent labels would be
        untraceable under jit anyway.
        """
        treedef = jax.tree_util.tree_structure(params)
        shapes = tuple(
            (tuple(getattr(p, "shape", ())), str(getattr(p, "dtype", "")))
            for p in jax.tree_util.tree_leaves(params)
        )
        cache_key = (treedef, shapes)
        hit = _resolved_cache.get(cache_key)
        if hit is None:
            paths_tree = tree_paths(params)
            if callable(labels):
                lab_tree = jax.tree_util.tree_map(labels, paths_tree, params)
            else:
                lab_tree = labels
            lab_leaves = tuple(jax.tree_util.tree_leaves(lab_tree))
            _check(lab_leaves)
            paths = tuple(jax.tree_util.tree_leaves(paths_tree))
            hit = (lab_tree, lab_leaves, paths)
            _resolved_cache[cache_key] = hit
        return hit

    def init(params):
        lab_tree, _, paths = _resolved(params)
        return PartitionState(
            {
                lab: tx.init(_mask(params, lab_tree, lab))
                for lab, tx in transforms.items()
            },
            paths,
        )

    def update(updates, state, params=None, *, key=None):
        lab_tree, lab_leaves, cur = _resolved(params)
        if state.param_paths is not None:
            if cur != state.param_paths:
                added = set(cur) - set(state.param_paths)
                removed = set(state.param_paths) - set(cur)
                raise KeyError(
                    "partition(): param tree changed since init() — "
                    f"added {sorted(added)}, removed {sorted(removed)}; "
                    "re-init the optimizer state (or migrate it) instead of "
                    "training new params with stale partition state"
                )
        treedef = jax.tree_util.tree_structure(lab_tree)

        # Distinct SR key per partition: leaf indices restart at 0 inside each
        # masked subtree, so handing every partition the same key would give
        # correlated quantization noise across partitions.
        label_order = {lab: i for i, lab in enumerate(sorted(transforms))}
        per_label_u: Dict[str, Any] = {}
        new_states: Dict[str, Any] = {}
        for lab, tx in transforms.items():
            k_lab = (
                jax.random.fold_in(key, label_order[lab]) if key is not None else None
            )
            u_l, s_l = tx.update(
                _mask(updates, lab_tree, lab),
                state.states[lab],
                _mask(params, lab_tree, lab),
                key=k_lab,
            )
            per_label_u[lab] = treedef.flatten_up_to(u_l)
            new_states[lab] = s_l

        merged = [per_label_u[lab][i] for i, lab in enumerate(lab_leaves)]
        return (
            jax.tree_util.tree_unflatten(treedef, merged),
            PartitionState(new_states, state.param_paths),
        )

    return GradientTransformation(init, update)
