"""Plain float32 reference of the dense GQA decoder the train cells run.

Pre-norm blocks: RMSNorm -> grouped-query attention with rotary positions ->
residual -> RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and an untied
output head; mean next-token cross entropy over labels >= 0.  Attention is
the plain causal softmax, every matmul runs in float32 at HIGHEST precision.

Layers are held stacked, ``(L, ...)`` per weight, the layout in which the
optimizer sees them (its rank-1 statistics span the stack).  The weights of
a run are drawn here from the seed (``init_leaf``); the harness hands the
same arrays to the program, so both start from one point.

``Rounder`` is what the control swaps in: every matmul's inputs and output
(forward and backward) rounded to a lower-precision float with a per-tensor
scale, the way a scaled fp8 matmul would see them.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "w1", "w2", "w3", "norm1", "norm2")
# What of a configuration file the reference reads.
MODEL_KEYS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
              "vocab_size", "rope_variant", "rope_theta", "rms_norm_eps")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (64 bits are used)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def leaf_shapes(c: dict) -> Dict[str, Tuple[int, ...]]:
    """Every weight of the model, in a fixed order, with its shape."""
    L, D, F, V = c["num_layers"], c["d_model"], c["d_ff"], c["vocab_size"]
    H, K, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    layer = {
        "wq": (D, H, hd), "wk": (D, K, hd), "wv": (D, K, hd), "wo": (H, hd, D),
        "w1": (D, F), "w2": (F, D), "w3": (D, F), "norm1": (D,), "norm2": (D,),
    }
    shapes = {"embed": (V, D)}
    shapes.update({name: (L,) + s for name, s in layer.items()})
    shapes["final_norm"] = (D,)
    shapes["head"] = (D, V)
    return shapes


def init_leaf(key: jax.Array, index: int, name: str, shape) -> jnp.ndarray:
    """Weight ``index`` of the model: norms start at one, the rest ~N(0, 0.02²)."""
    if "norm" in name:
        return jnp.ones(shape, jnp.float32)
    return INIT_STD * jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)


def init_params(key: jax.Array, c: dict) -> Dict[str, jnp.ndarray]:
    return {
        name: init_leaf(key, i, name, shape)
        for i, (name, shape) in enumerate(leaf_shapes(c).items())
    }


# Gradients take the wider-ranged fp8 format, as fp8 training does (e4m3
# forward, e5m2 backward).
GRAD_FORMAT = {"float8_e4m3fn": "float8_e5m2"}


def _rounding(fmt: str):
    dt = jnp.dtype(fmt)
    fmax = float(jnp.finfo(dt).max)

    def rnd(x):
        s = jnp.max(jnp.abs(x)) / fmax
        s = jnp.where(s > 0, s, 1.0)
        # clip: x / s may round past the format's largest value, which
        # a format without infinities turns into NaN
        return jnp.clip(x / s, -fmax, fmax).astype(dt).astype(jnp.float32) * s

    return rnd


class Rounder:
    """Rounds matmul operands and results to ``fmt`` with a per-tensor scale,
    and their gradients to ``GRAD_FORMAT[fmt]`` (``fmt=None``: float32,
    nothing is rounded)."""

    def __init__(self, fmt: Optional[str] = None):
        self.fmt = fmt
        if fmt is None:
            self.q = lambda x: x
            return
        rnd, rnd_grad = _rounding(fmt), _rounding(GRAD_FORMAT.get(fmt, fmt))

        @jax.custom_vjp
        def q(x):
            return rnd(x)

        q.defvjp(lambda x: (rnd(x), None), lambda _, g: (rnd_grad(g),))
        self.q = q

    def mm(self, spec: str, a, b):
        q = self.q
        return q(jnp.einsum(spec, q(a), q(b), precision=HIGHEST))


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, theta: float, rot_dims: int):
    """Rotate the first ``rot_dims`` dims of each head by position, pairing
    dim i with dim i + rot_dims/2; the rest pass through."""
    S = x.shape[1]
    half = rot_dims // 2
    inv = 1.0 / theta ** (jnp.arange(0, rot_dims, 2, dtype=jnp.float32) / rot_dims)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot_dims], x[..., rot_dims:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def rot_dims(c: dict) -> int:
    return c["head_dim"] // 2 if c["rope_variant"] == "rope2d" else c["head_dim"]


def block(x, p, c: dict, r: Rounder):
    """One decoder layer on ``x`` (B, S, D) float32."""
    eps, theta, rd = c["rms_norm_eps"], c["rope_theta"], rot_dims(c)
    h = rmsnorm(x, p["norm1"], eps)
    q = rotary(r.mm("bsd,dhe->bshe", h, p["wq"]), theta, rd)
    k = rotary(r.mm("bsd,dhe->bshe", h, p["wk"]), theta, rd)
    v = r.mm("bsd,dhe->bshe", h, p["wv"])
    B, S, H, hd = q.shape
    K = k.shape[2]
    qg = q.reshape(B, S, K, H // K, hd)
    s = r.mm("bqkgd,bskd->bkgqs", qg, k) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = r.mm("bkgqs,bskd->bqkgd", a, v).reshape(B, S, H, hd)
    x = x + r.mm("bshe,hed->bsd", o, p["wo"])
    h = rmsnorm(x, p["norm2"], eps)
    u = jax.nn.silu(r.mm("bsd,df->bsf", h, p["w1"])) * r.mm("bsd,df->bsf", h, p["w3"])
    return x + r.mm("bsf,fd->bsd", u, p["w2"])


def head_loss_sum(x, final_norm, head, labels, c: dict, r: Rounder):
    """Sum over rows of ``x`` (N, D) of the cross entropy at ``labels`` >= 0."""
    logits = r.mm("nd,dv->nv", rmsnorm(x, final_norm, c["rms_norm_eps"]), head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, lse - gold, 0.0))


def layer_params(stacked: Dict[str, jnp.ndarray], l) -> Dict[str, jnp.ndarray]:
    return {n: jax.lax.dynamic_index_in_dim(stacked[n], l, keepdims=False) for n in LAYER_LEAVES}


def loss(params, tokens, labels, c: dict, r: Optional[Rounder] = None):
    """Whole-model mean loss in one piece (small sizes: tests)."""
    r = r or Rounder()
    x = params["embed"][tokens]
    for l in range(c["num_layers"]):
        x = block(x, layer_params(params, l), c, r)
    n = jnp.maximum(jnp.sum(labels >= 0), 1)
    D = x.shape[-1]
    return head_loss_sum(
        x.reshape(-1, D), params["final_norm"], params["head"], labels.reshape(-1), c, r
    ) / n
