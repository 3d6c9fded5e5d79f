"""Quantization mappings T: code -> [0,1] (or [-1,1] signed), as a registry.

A *mapping* is materialized as a sorted fp32 table of length <= 2^b.  Encoding
is round-to-nearest via midpoint comparison (branchless, TPU friendly) with an
optional stochastic-rounding variant (App. E.3).

Every map enters the system through ``register_mapping(name, table_fn)`` —
including the paper's three (App. E.2), registered at the bottom of this
module.  ``QuantConfig`` validates its ``mapping`` string against
``registered()`` at construction, so the registry is the single source of
truth for what maps exist; there is no parallel hardcoded list.

Registered maps:

* ``linear``   — T(i) = (i+1)/2^b, zero EXCLUDED by construction (used for the
  second moment; smallest representable value at 4 bits is 1/16 = 0.0625).
* ``de``       — dynamic exponent mapping [Dettmers 2015] with the bitsandbytes
  corner cases: unsigned code 0 -> 0.0, unsigned code 1 -> 1.0; in the signed
  case the (sign=1, magnitude=0) pattern is repurposed as +1.0, so -1.0 is not
  representable and the map is asymmetric (App. E.2).
* ``de0``      — ``de`` with the zero code removed (the paper's DE-0), leaving
  2^b - 1 quantization points; fixes the second-moment zero-point problem at
  the cost of one wasted code.
* ``dynamic``  — bitsandbytes' symmetric dynamic map: a sign bit plus
  dynamic-exponent magnitudes (with 0.0 and 1.0 representable on BOTH sides),
  the create_dynamic_map construction.  Unlike ``de`` it is exactly odd
  symmetric — the natural choice for Shampoo's Kronecker factors, whose
  off-diagonal entries carry meaningful signs in both directions.  (The
  unsigned table coincides with ``de``: with no sign bit the constructions
  agree.)
* ``quantile`` — static quantile map: code points at equally spaced quantiles
  of N(0,1) (clipped at the 99.5th percentile, normalized to max 1), the
  static analogue of bitsandbytes' quantile quantization / NF4.  Unsigned is
  the half-normal version — strictly positive (zero-excluding), a
  quantile-spaced alternative second-moment map.
* ``log-ema``  — SOLO-style logarithmic map for EMA statistics: code points
  log-uniform over ``bits`` decades ending at 1.0, so after absmax
  normalization the relative quantization error is constant across magnitudes
  — tuned for EMA accumulators whose entries span orders of magnitude.
  Unsigned excludes zero; signed is symmetric with a zero code.
"""

from __future__ import annotations

import dataclasses
import difflib
import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "MappingSpec",
    "register_mapping",
    "registered",
    "get_spec",
    "mapping_table",
    "encode",
    "decode",
    "lookup",
    "encode_stochastic",
    "encode_stochastic_uniform",
]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MappingSpec:
    """A registered quantization map.

    ``table_fn(bits, signed)`` returns the sorted, unique table of
    quantization points as a float numpy array of length <= 2^bits.
    ``symmetric_signed`` declares that the signed table is exactly odd
    symmetric (``de``/``de0`` are famously not — their +1.0 code has no
    negative twin); the registry contract tests enforce the declaration.
    Remaining fields are documentation surfaced by ``QuantConfig.name`` and
    the docs/optimizers.md map table.
    """

    name: str
    table_fn: Callable[[int, bool], np.ndarray]
    display: str
    statistic: str = ""
    zero_code: str = ""
    symmetric_signed: bool = True
    reference: str = ""


_REGISTRY: Dict[str, MappingSpec] = {}


def register_mapping(
    name: str,
    table_fn: Callable[[int, bool], np.ndarray],
    *,
    display: str = "",
    statistic: str = "",
    zero_code: str = "",
    symmetric_signed: bool = True,
    reference: str = "",
) -> MappingSpec:
    """Register a quantization map — the ONLY way a map becomes usable in a
    ``QuantConfig`` (and hence anywhere a config flows: optimizer moments,
    gradient transport, q4 serving weights)."""
    if not isinstance(name, str) or not name:
        raise ValueError(f"mapping name must be a non-empty string, got {name!r}")
    if name in _REGISTRY:
        raise ValueError(f"mapping {name!r} is already registered")
    spec = MappingSpec(
        name=name,
        table_fn=table_fn,
        display=display or name,
        statistic=statistic,
        zero_code=zero_code,
        symmetric_signed=symmetric_signed,
        reference=reference,
    )
    _REGISTRY[name] = spec
    return spec


def registered() -> Tuple[str, ...]:
    """Names of all registered maps, in registration order."""
    return tuple(_REGISTRY)


def get_spec(name: str) -> MappingSpec:
    """Resolve a mapping name, with a did-you-mean on typos."""
    spec = _REGISTRY.get(name)
    if spec is None:
        hint = ""
        close = difflib.get_close_matches(str(name), _REGISTRY, n=1)
        if close:
            hint = f" — did you mean {close[0]!r}?"
        raise ValueError(
            f"unknown mapping {name!r}; registered mappings: {registered()}"
            f"{hint} (add new maps with repro.core.mappings.register_mapping)"
        )
    return spec


# ---------------------------------------------------------------------------
# table builders
# ---------------------------------------------------------------------------


def _de_fraction_levels(F: int) -> np.ndarray:
    """Midpoint fraction levels for F fraction bits, distributed in (0.1, 1)."""
    j = np.arange(2**F + 1, dtype=np.float64)
    p = (1.0 - 0.1) / (2**F) * j + 0.1
    return (p[:-1] + p[1:]) / 2.0


def _de_unsigned_values(width: int, special_one: bool = True) -> np.ndarray:
    """All dynamic-exponent values for ``width``-bit unsigned codes.

    Code 0 -> 0.0 and (if ``special_one``) code 1 -> 1.0, the bitsandbytes
    corner cases; otherwise the code's binary representation is
    [E leading zeros | 1 | F fraction bits] and the value is
    10^-E * fraction[F]. In the signed case only the all-zeros pattern is
    special (App. E.2), so magnitudes are built with ``special_one=False``.
    """
    values = np.zeros(2**width, dtype=np.float64)
    values[0] = 0.0
    start = 1
    if special_one:
        values[1] = 1.0
        start = 2
    for code in range(start, 2**width):
        bits = format(code, f"0{width}b")
        E = len(bits) - len(bits.lstrip("0"))  # leading zeros
        frac_bits = bits[E + 1 :]
        F = len(frac_bits)
        k = int(frac_bits, 2) if F > 0 else 0
        frac = _de_fraction_levels(F)[k]
        values[code] = (10.0**-E) * frac
    return values


def _linear_table(bits: int, signed: bool) -> np.ndarray:
    if signed:
        # Symmetric signed linear map excluding zero: +/- (i+1)/2^(b-1).
        half = (np.arange(2 ** (bits - 1), dtype=np.float64) + 1) / 2 ** (bits - 1)
        return np.concatenate([-half[::-1], half])
    return (np.arange(2**bits, dtype=np.float64) + 1) / 2**bits


def _de_table(bits: int, signed: bool) -> np.ndarray:
    if signed:
        mag = _de_unsigned_values(bits - 1, special_one=False)
        # sign=0 patterns: +mag (pattern 0 -> 0.0). sign=1 patterns: -mag,
        # except magnitude-pattern 0 which is repurposed as +1.0, so -1.0 is
        # not representable (the map is asymmetric, App. E.2).
        vals = np.concatenate([mag, np.array([1.0]), -mag[1:]])
    else:
        vals = _de_unsigned_values(bits)
    return np.sort(np.unique(vals))


def _de0_table(bits: int, signed: bool) -> np.ndarray:
    vals = _de_table(bits, signed)
    return vals[vals != 0.0]


def _dynamic_table(bits: int, signed: bool) -> np.ndarray:
    if signed:
        # Sign bit + (bits-1)-bit dynamic-exponent magnitude with BOTH corner
        # cases (0.0 and 1.0 representable) on both sides; +0/-0 collapse, so
        # the table has 2^bits - 1 entries and is exactly odd symmetric.
        mag = _de_unsigned_values(bits - 1, special_one=True)
        return np.sort(np.unique(np.concatenate([-mag, mag])))
    return np.sort(np.unique(_de_unsigned_values(bits)))


def _quantile_table(bits: int, signed: bool) -> np.ndarray:
    from statistics import NormalDist

    inv_cdf = NormalDist().inv_cdf
    P = 0.995  # clip the unbounded normal tails at the 99.5th percentile
    if signed:
        K = 2 ** (bits - 1) - 1
        pos = np.array(
            [inv_cdf(0.5 + 0.5 * P * (i + 1) / K) for i in range(K)], np.float64
        )
        pos /= pos[-1]
        return np.concatenate([-pos[::-1], [0.0], pos])
    K = 2**bits
    vals = np.array(
        [inv_cdf(0.5 + 0.5 * P * (i + 1) / K) for i in range(K)], np.float64
    )
    return vals / vals[-1]


def _log_ema_table(bits: int, signed: bool) -> np.ndarray:
    # Log-uniform code points over `bits` decades ending at 1.0: constant
    # RELATIVE quantization error across magnitudes, the regime that matters
    # for EMA accumulators whose entries span orders of magnitude (SOLO).
    decades = float(bits)
    if signed:
        K = 2 ** (bits - 1) - 1
        pos = 10.0 ** (-decades * (1.0 - (np.arange(K, dtype=np.float64) + 1.0) / K))
        return np.concatenate([-pos[::-1], [0.0], pos])
    K = 2**bits
    return 10.0 ** (-decades * (1.0 - (np.arange(K, dtype=np.float64) + 1.0) / K))


# ---------------------------------------------------------------------------
# table materialization + codecs
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _mapping_table_np(kind: str, bits: int, signed: bool) -> np.ndarray:
    """Sorted numpy table of quantization points for (kind, bits, signed).

    Looks the map up in the registry and enforces the table contract
    (sorted, unique, finite, length <= 2^bits) on whatever the builder
    returns — a misbehaving ``register_mapping`` fails here, not downstream
    in a kernel.
    """
    spec = get_spec(kind)
    if bits < 2 or bits > 8:
        raise ValueError(f"bits must be in [2, 8], got {bits}")
    vals = np.asarray(spec.table_fn(bits, signed), dtype=np.float64).astype(np.float32)
    if vals.ndim != 1 or vals.size == 0 or vals.size > 2**bits:
        raise ValueError(
            f"mapping {kind!r}: table must be 1-d with 1..2^{bits} entries, "
            f"got shape {vals.shape}"
        )
    if not np.all(np.isfinite(vals)):
        raise ValueError(f"mapping {kind!r}: table contains non-finite values")
    if not np.all(np.diff(vals) > 0):
        raise ValueError(f"mapping {kind!r}: table must be strictly increasing")
    return vals


def mapping_table(kind: str, bits: int, signed: bool) -> jnp.ndarray:
    """Return the sorted fp32 quantization-point table as a jnp array."""
    return jnp.asarray(_mapping_table_np(kind, bits, signed))


def _midpoints(table: jnp.ndarray) -> jnp.ndarray:
    return (table[1:] + table[:-1]) / 2.0


def encode(n: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Round-to-nearest code indices into ``table`` (branchless).

    idx = sum_k [n > midpoint_k]; exact round-to-nearest for a sorted table
    (ties go to the lower code, matching argmin-first behaviour).
    """
    mids = _midpoints(table)
    # (..., 1) > (K-1,) -> (..., K-1); sum over the last axis.
    idx = jnp.sum(n[..., None] > mids, axis=-1)
    return idx.astype(jnp.uint8)


def lookup(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for integer ``idx`` in range.

    A table of up to 16 entries (every 4-bit map) is read by comparing
    ``idx`` with every code and summing the one entry that matches: XLA
    lowers a gather from so small a table badly on TPU — its cost analysis
    of the fused kernel's prepass, a vmapped ``jnp.take`` over an
    (8, 2048, 8192) leaf, counts 561 GB accessed against 4.2 GB this way.
    Larger tables keep the gather.
    """
    idx = idx.astype(jnp.int32)
    if table.shape[0] > 16:
        return jnp.take(table, idx, axis=0)
    hit = idx[..., None] == jnp.arange(table.shape[0], dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, table, 0.0), axis=-1)


def decode(codes: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Dequantize code indices back to fp32 quantization points."""
    return lookup(table, codes)


def encode_stochastic(
    n: jnp.ndarray, table: jnp.ndarray, key: jax.Array
) -> jnp.ndarray:
    """Stochastic rounding (App. E.3): round to the bracketing codes with
    probability proportional to proximity; values outside the table clamp."""
    return encode_stochastic_uniform(n, table, jax.random.uniform(key, n.shape))


def encode_stochastic_uniform(
    n: jnp.ndarray, table: jnp.ndarray, u: jnp.ndarray
) -> jnp.ndarray:
    """``encode_stochastic`` consuming precomputed uniforms ``u`` in [0, 1).

    Callers that need mesh-invariant noise (gradient transport in
    ``repro.comms``) derive ``u`` with the counter-based Threefry of
    ``repro.kernels.sr`` instead of ``jax.random.uniform``, whose draws
    depend on the output sharding under the default non-partitionable
    lowering.
    """
    k = table.shape[0]
    # Lower bracket: largest code with T(code) <= n (clamped to [0, K-2]).
    lo = jnp.clip(jnp.sum(n[..., None] >= table, axis=-1) - 1, 0, k - 2)
    t_lo = lookup(table, lo)
    t_hi = lookup(table, lo + 1)
    span = jnp.maximum(t_hi - t_lo, 1e-12)
    p_hi = jnp.clip((n - t_lo) / span, 0.0, 1.0)
    idx = lo + (u < p_hi).astype(lo.dtype)
    return idx.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# the built-in maps — registered like any third-party map would be
# ---------------------------------------------------------------------------

register_mapping(
    "linear",
    _linear_table,
    display="Linear",
    statistic="second moment (EMA of squared grads)",
    zero_code="zero excluded by construction (both signednesses)",
    symmetric_signed=True,
    reference="4-bit Optimizers App. E.2",
)
register_mapping(
    "de",
    _de_table,
    display="DE",
    statistic="first moment / signed zero-clustered tensors",
    zero_code="unsigned has 0.0; signed repurposes -0 as +1.0 (asymmetric)",
    symmetric_signed=False,
    reference="Dettmers 2015; 4-bit Optimizers App. E.2",
)
register_mapping(
    "de0",
    _de0_table,
    display="DE-0",
    statistic="second moment (zero-point fix)",
    zero_code="zero code removed from DE (2^b - 1 points)",
    symmetric_signed=False,
    reference="4-bit Optimizers App. E.2 (DE-0)",
)
register_mapping(
    "dynamic",
    _dynamic_table,
    display="Dyn",
    statistic="signed matrix factors (Shampoo Kronecker blocks)",
    zero_code="zero representable; signed exactly odd symmetric with ±1.0",
    symmetric_signed=True,
    reference="bitsandbytes create_dynamic_map; 4-bit Shampoo",
)
register_mapping(
    "quantile",
    _quantile_table,
    display="Qtl",
    statistic="normally distributed moments / weights",
    zero_code="signed has a zero code; unsigned strictly positive",
    symmetric_signed=True,
    reference="bitsandbytes quantile quantization; QLoRA NF4",
)
register_mapping(
    "log-ema",
    _log_ema_table,
    display="LogEMA",
    statistic="EMA statistics spanning decades (second moment)",
    zero_code="unsigned zero-excluding; signed symmetric with a zero code",
    symmetric_signed=True,
    reference="SOLO (logarithmic quantization for EMA dynamics)",
)
