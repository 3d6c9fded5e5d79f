"""4-bit served weights (q4), as Li et al. (NeurIPS 2023) state the B128/DE
quantizer: absmax over blocks of 128 of the row-major flattened tensor, and
a signed dynamic-exponent map, rounded to nearest.  Values are held here as
the float32 numbers their codes stand for."""

from __future__ import annotations

import jax.numpy as jnp

# Signed dynamic-exponent map, 4 bits: a sign bit, then E leading zeros, an
# indicator bit and the remaining bits as a linear fraction in (0.1, 1)
# (midpoints), valued 10^-E * fraction.  The all-zero pattern is 0 and the
# negative zero pattern stands for +1.
DE_SIGNED_4 = (
    -0.8875, -0.6625, -0.4375, -0.2125, -0.0775, -0.0325, -0.0055, 0.0,
    0.0055, 0.0325, 0.0775, 0.2125, 0.4375, 0.6625, 0.8875, 1.0,
)
BLOCK = 128


def blockwise_scale(x: jnp.ndarray, block: int = BLOCK) -> jnp.ndarray:
    """Per-element scale: the absmax of the element's block."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    blocks = jnp.pad(flat, (0, pad)).reshape(-1, block)
    s = jnp.max(jnp.abs(blocks), axis=1)
    return jnp.repeat(s, block)[: flat.shape[0]].reshape(x.shape)


def _pick(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    hit = idx[..., None] == jnp.arange(table.shape[0])
    return jnp.sum(jnp.where(hit, table, 0.0), axis=-1)


def round_nearest(n: jnp.ndarray, table) -> jnp.ndarray:
    """Round ``n`` to the nearest table point (a tie goes to the lower)."""
    t = jnp.asarray(table, jnp.float32)
    mids = (t[1:] + t[:-1]) / 2
    return _pick(t, jnp.sum(n[..., None] > mids, axis=-1))


def quantize_b128_de(x: jnp.ndarray) -> jnp.ndarray:
    """B128/DE round-to-nearest, read back as values (4-bit served weights)."""
    s = blockwise_scale(x)
    return round_nearest(x / jnp.where(s > 0, s, 1.0), DE_SIGNED_4) * s
