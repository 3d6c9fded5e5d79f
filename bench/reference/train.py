"""The reference's first training steps, layer by layer so that it fits.

Float32 weights, both moments and the saved layer inputs stay on the device;
each layer's gradient is taken by recomputing the layer (``jax.vjp``, rows
in blocks) and is consumed at once by that layer's AdamW step, so no whole
gradient tree ever exists.

Readings, by leaf (``name`` or ``name.layer``):

* ``loss[k]``: the loss of step k+1, before its update;
* ``grad``: the norm of the first gradient as it stands in the optimizer's
  state after one step (first moment over 1 - b1);
* ``change``: the norm of each weight's change over the steps taken.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.model import (
    LAYER_LEAVES,
    MODEL_KEYS,
    Rounder,
    block,
    head_loss_sum,
    init_leaf,
    init_params,
    leaf_shapes,
    layer_params,
)

TOKEN_CHUNK = 1024


def adamw_step(p, m, v, g, t, hp: Dict[str, float]):
    """One float32 AdamW step at (1-based) step ``t``: (p', m', v')."""
    b1, b2 = hp["b1"], hp["b2"]
    m2 = b1 * m + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * g * g
    tf = jnp.asarray(t, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(b1), tf)
    bc2 = 1.0 - jnp.power(jnp.float32(b2), tf)
    u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + hp["eps"]) + hp["weight_decay"] * p
    return p - hp["lr"] * u, m2, v2


@dataclasses.dataclass
class Readings:
    loss: List[float]
    grad: Dict[str, float]
    change: Dict[str, float]


def leaf_norms(name: str, x: jnp.ndarray, stacked: bool) -> Dict[str, jnp.ndarray]:
    """{leaf id: norm}; a stacked leaf gives one norm per layer."""
    if not stacked:
        return {name: jnp.sqrt(jnp.sum(x * x))}
    per = jnp.sqrt(jnp.sum((x * x).reshape(x.shape[0], -1), axis=1))
    return {f"{name}.{l}": per[l] for l in range(x.shape[0])}


def host(tree) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


class Reference:
    """``steps`` float32 AdamW steps of the reference model from the seed's
    weights.

    ``fmt`` names the lower float of the control (``None``: float32);
    ``row_blocks`` splits the batch rows of each layer's backward pass.
    """

    def __init__(self, c: dict, hp: dict, *, fmt: Optional[str] = None, row_blocks: int = 1):
        self.c, self.hp = c, hp
        self.c_key = tuple((k, c[k]) for k in MODEL_KEYS)
        self.shapes = leaf_shapes(c)
        r = Rounder(fmt)
        L = c["num_layers"]

        def stack_of(P):
            return {n: P[n] for n in LAYER_LEAVES}

        @jax.jit
        def layer_fwd(stack, l, x):
            return block(x, layer_params(stack, l), c, r)

        @jax.jit
        def layer_bwd(stack, l, x, gy):
            p = layer_params(stack, l)
            B = x.shape[0]
            xb = x.reshape((row_blocks, B // row_blocks) + x.shape[1:])
            gb = gy.reshape(xb.shape)

            def body(acc, xg):
                _, pull = jax.vjp(lambda xx, pp: block(xx, pp, c, r), xg[0], p)
                gx, gp = pull(xg[1])
                return jax.tree_util.tree_map(jnp.add, acc, gp), gx

            acc0 = jax.tree_util.tree_map(jnp.zeros_like, p)
            gp, gx = jax.lax.scan(body, acc0, (xb, gb))
            return gx.reshape(x.shape), gp

        @jax.jit
        def head_grad(x, final_norm, head, labels):
            D = x.shape[-1]
            chunk = min(TOKEN_CHUNK, labels.size)
            xs = x.reshape(-1, chunk, D)
            ls = labels.reshape(-1, chunk)
            n = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)

            def total(xs, fn, hd):
                def body(acc, xl):
                    return acc + head_loss_sum(xl[0], fn, hd, xl[1], c, r), None

                s, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0), (xs, ls))
                return s / n

            val, (gx, gfn, ghead) = jax.value_and_grad(total, argnums=(0, 1, 2))(
                xs, final_norm, head
            )
            return val, gx.reshape(x.shape), gfn, ghead

        @jax.jit
        def embed_grad(gx, tokens):
            V, D = self.shapes["embed"]
            return jnp.zeros((V, D), jnp.float32).at[tokens.reshape(-1)].add(
                gx.reshape(-1, D)
            )

        def adam_leaf(p, m, v, g, t):
            return adamw_step(p, m, v, g, t, hp)

        def _adam_layer(stack, ms, vs, g, l, t):
            out = ({}, {}, {})
            for n in LAYER_LEAVES:
                pl, ml, vl = (jax.lax.dynamic_index_in_dim(a[n], l, keepdims=False)
                              for a in (stack, ms, vs))
                new = adamw_step(pl, ml, vl, g[n], t, hp)
                for dst, src, val in zip(out, (stack, ms, vs), new):
                    dst[n] = jax.lax.dynamic_update_index_in_dim(src[n], val, l, 0)
            return out

        adam_layer = jax.jit(_adam_layer, donate_argnums=(0, 1, 2))

        self._layer_fwd, self._layer_bwd = layer_fwd, layer_bwd
        self._head_grad, self._embed_grad = head_grad, embed_grad
        self._adam_leaf = jax.jit(adam_leaf, donate_argnums=(0, 1, 2))
        self._adam_layer = adam_layer
        self._stack_of = stack_of
        self.L = L

    # ------------------------------------------------------------------
    def init(self, key):
        P = _init_params(key, self.c_key)
        M = {n: jnp.zeros_like(a) for n, a in P.items()}
        V = {n: jnp.zeros_like(a) for n, a in P.items()}
        return P, M, V

    def step(self, P, M, V, tokens, labels, t: int):
        x = P["embed"][tokens]
        xs = []
        for l in range(self.L):
            xs.append(x)
            x = self._layer_fwd(self._stack_of(P), l, x)
        loss, gx, gfn, ghead = self._head_grad(x, P["final_norm"], P["head"], labels)
        del x
        for n, g in (("final_norm", gfn), ("head", ghead)):
            P[n], M[n], V[n] = self._adam_leaf(P[n], M[n], V[n], g, t)
        del ghead
        for l in reversed(range(self.L)):
            gx, gp = self._layer_bwd(self._stack_of(P), l, xs[l], gx)
            # the layer below still reads this layer's old weights only
            # through gx, which is already taken
            sp, sm, sv = self._adam_layer(
                self._stack_of(P), self._stack_of(M), self._stack_of(V), gp, l, t
            )
            P.update(sp), M.update(sm), V.update(sv)
            xs[l] = None
        ge = self._embed_grad(gx, tokens)
        P["embed"], M["embed"], V["embed"] = self._adam_leaf(
            P["embed"], M["embed"], V["embed"], ge, t
        )
        del ge, gx
        return float(loss)

    def run(self, key, batches, steps: int) -> Readings:
        """``batches``: a list of (tokens, labels) numpy pairs, one per step."""
        P, M, V = self.init(key)
        losses, grad = [], {}
        for t in range(1, steps + 1):
            tokens, labels = (jnp.asarray(a) for a in batches[t - 1])
            losses.append(self.step(P, M, V, tokens, labels, t))
            if t == 1:
                grad = host(self._grad_norms(M))
        change = host(self._change_norms(P, key))
        del P, M, V
        return Readings(losses, grad, change)

    def _grad_norms(self, M):
        return _grad_norms(M, 1.0 / (1.0 - self.hp["b1"]))

    def _change_norms(self, P, key):
        return _change_norms(P, key, self.c_key)


@functools.partial(jax.jit, static_argnums=(1,))
def _init_params(key, c_key):
    return init_params(key, dict(c_key))


@jax.jit
def _grad_norms(M, scale):
    out = {}
    for name, m in M.items():
        out.update(leaf_norms(name, m * scale, name in LAYER_LEAVES))
    return out


@functools.partial(jax.jit, static_argnums=(2,))
def _change_norms(P, key, c_key):
    shapes = leaf_shapes(dict(c_key))
    out = {}
    for i, (name, shape) in enumerate(shapes.items()):
        d = P[name] - init_leaf(key, i, name, shape)
        out.update(leaf_norms(name, d, name in LAYER_LEAVES))
    return out


def batches_from(feed, steps: int, label_rows: Optional[int] = None):
    """(tokens, labels) of the feed's first ``steps`` steps; ``label_rows``
    keeps the labels of only that many leading rows (the half-batch fault)."""
    out = []
    for t in range(steps):
        b = feed.batch_at(t)
        labels = np.array(b["labels"])
        if label_rows is not None:
            labels[label_rows:] = -1
        out.append((b["tokens"], labels))
    return out
