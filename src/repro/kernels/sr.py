"""Counter-based randomness for in-kernel stochastic rounding.

The fused AdamW kernel requantizes moments *inside* the Pallas kernel, so the
stochastic-rounding noise must be generated in-kernel too — materializing an
fp32 uniform tensor in HBM would forfeit the memory saving the fusion exists
for.  ``pltpu.prng_*`` has no interpret-mode lowering, so the kernel instead
runs Threefry-2x32 (the same PRNG family JAX's keys use) expressed in plain
uint32 jnp ops: add/xor/shift lower both in compiled TPU Pallas and in
interpret mode, and — crucially — produce bit-identical streams in the kernel
and in the pure-jnp reference oracle, so the SR path is testable bit-for-bit,
not just statistically.

Stream derivation (see docs/kernels.md):

    per-leaf key   = fold_in(step key, leaf index)        (compressed())
    per-slice key  = fold_in(leaf key, slice index)       (ops.py, one 2-d
                                                           slice per leading
                                                           dim of the leaf)
    per-element    = threefry2x32(key_words(slice key),
                     random bits    counter0 = row * C + col,
                                    counter1 = stream id (0 = m, 1 = v))

Because the counter is the *global element index within the slice*, the bits
an element sees are independent of the kernel tiling and of the mesh layout —
retiling or resharding replays the identical noise.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "threefry2x32",
    "uniform_from_bits",
    "key_words",
    "key_rows",
    "tensor_uniforms",
    "STREAM_M",
    "STREAM_V",
    "STREAM_GRAD",
    "STREAM_SAMPLE",
]

# Stream ids separating the two moments' noise within one (key, element) pair.
STREAM_M = 0
STREAM_V = 1
# Gradient-transport quantization (repro.comms) — its own counter stream so
# the wire noise never collides with either moment's even under a shared key.
STREAM_GRAD = 2
# Token sampling in the serving engine (repro.serve.sampling): per-request
# Gumbel noise, counter = generated-token index, so a request's sampled
# stream is independent of which cache slot it lands in.
STREAM_SAMPLE = 3

_PARITY = np.uint32(0x1BD11BDA)  # Threefry key-schedule parity constant
_ROT = (13, 15, 26, 6, 17, 29, 16, 24)


def _rotl(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return jax.lax.shift_left(x, jnp.uint32(r)) | jax.lax.shift_right_logical(
        x, jnp.uint32(32 - r)
    )


def threefry2x32(k0, k1, c0, c1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Threefry-2x32 (20 rounds, Random123/JAX-compatible).

    ``k0/k1`` are uint32 key words, ``c0/c1`` uint32 counters (arrays or
    scalars; standard broadcasting).  Returns the two output words.  Matches
    ``jax.extend.random.threefry_2x32`` bit-for-bit (test-enforced), and uses
    only uint32 add/xor/shift — safe inside Pallas TPU kernels and in
    interpret mode.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = jnp.asarray(c0, jnp.uint32) + k0
    x1 = jnp.asarray(c1, jnp.uint32) + k1
    for group in range(5):
        rots = _ROT[0:4] if group % 2 == 0 else _ROT[4:8]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(group + 1) % 3]
        x1 = x1 + ks[(group + 2) % 3] + jnp.uint32(group + 1)
    return x0, x1


def uniform_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """uint32 -> fp32 uniform in [0, 1) using the top 24 bits (exact in fp32)."""
    return jax.lax.shift_right_logical(bits, jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) * (
        1.0 / (1 << 24)
    )


def key_words(key: jax.Array) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The two uint32 words of a JAX PRNG key (typed or raw uint32 layout)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = key
    data = data.astype(jnp.uint32).reshape(-1)
    return data[-2], data[-1]


def key_rows(keys: jax.Array) -> jnp.ndarray:
    """(L,)-batched PRNG keys -> (L, 2) uint32 seed rows.

    The batched twin of ``key_words`` (same last-two-words layout per key), in
    the shape the fused kernel consumes: row ``l`` seeds slice ``l``.
    Accepts typed keys (e.g. from a vmapped ``fold_in``) or raw uint32
    ``(L, 2)`` layouts.
    """
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(keys)
    else:
        data = keys
    data = data.astype(jnp.uint32)
    return data.reshape(data.shape[0], -1)[:, -2:]


def element_uniforms(
    k0, k1, shape: Tuple[int, int], stream: int
) -> jnp.ndarray:
    """Per-element uniforms for a 2-d (R, C) slice, counter = r * C + c.

    The pure-jnp twin of the kernel's in-tile derivation (same bits for the
    same key/stream/element — bit-exact kernel-vs-reference SR).
    """
    R, C = shape
    linear = jnp.arange(R * C, dtype=jnp.uint32).reshape(R, C)
    bits, _ = threefry2x32(k0, k1, linear, jnp.uint32(stream))
    return uniform_from_bits(bits)


def tensor_uniforms(key: jax.Array, shape: Tuple[int, ...], stream: int) -> jnp.ndarray:
    """Per-element uniforms for an arbitrary-rank tensor, counter = the
    flattened global element index.

    The any-ndim sibling of ``element_uniforms`` taking a PRNG key directly.
    Unlike ``jax.random.uniform`` under the default (non-partitionable)
    Threefry lowering — whose draws depend on how the output is sharded —
    the counter-based derivation yields the same bits for the same
    (key, element) on every mesh layout, which is what lets quantized
    gradient transport (``repro.comms``) promise bit-identical results
    across elastic mesh restarts.
    """
    k0, k1 = key_words(key)
    n = 1
    for d in shape:
        n *= int(d)
    linear = jnp.arange(n, dtype=jnp.uint32).reshape(shape)
    bits, _ = threefry2x32(k0, k1, linear, jnp.uint32(stream))
    return uniform_from_bits(bits)
