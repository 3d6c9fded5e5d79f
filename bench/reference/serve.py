"""The reference's logits for served requests.

A request's prompt and the tokens served for it run through the plain
float32 model (``bench.reference.model``) in one causal pass; the logits
at each position are what the next served token was picked from.  The
weights are the seed's, in the format the cell serves: ``bf16`` rounds
each matrix to bfloat16, ``q4`` to B128/DE codes (round to nearest); norm
scales and other leaves of 4096 elements or fewer, or of rank one, stay
float32.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from bench.reference import q4
from bench.reference.model import Rounder, block, init_params, layer_params, rmsnorm

POSITION_CHUNK = 512
THRESHOLD = 4096


def served_format(P: Dict[str, jnp.ndarray], weights: str) -> Dict[str, jnp.ndarray]:
    def fmt(x):
        if x.ndim < 2 or x.size <= THRESHOLD:
            return x
        if weights == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        if weights == "q4":
            return q4.quantize_b128_de(x)
        raise ValueError(f"unknown weights format {weights!r}")

    return {k: jax.jit(fmt)(v) for k, v in P.items()}


class ServeReference:
    """Logits of the seed's model, served as ``weights``, over token runs;
    ``fmt`` names the lower float of the control (``None``: float32)."""

    def __init__(self, c: dict, key, weights: str, fmt: Optional[str] = None):
        self.c = c
        self.P = served_format(jax.jit(functools.partial(init_params, c=c))(key), weights)
        r = Rounder(fmt)

        @jax.jit
        def layer(P, l, x):
            return block(x, layer_params(P, l), c, r)

        @jax.jit
        def head(P, x):
            h = rmsnorm(x, P["final_norm"], c["rms_norm_eps"])
            return r.mm("nd,dv->nv", h, P["head"])

        self._layer, self._head = layer, head

    def logits(self, tokens: Sequence[int]) -> List[jnp.ndarray]:
        """Logits at every position of ``tokens``, in chunks of
        ``POSITION_CHUNK`` positions (the run is padded at its end, which a
        causal pass never looks at)."""
        n = len(tokens)
        padded = list(tokens) + [0] * (-n % POSITION_CHUNK)
        x = self.P["embed"][jnp.asarray(padded, jnp.int32)][None]
        for l in range(self.c["num_layers"]):
            x = self._layer(self.P, l, x)
        x = x[0]
        return [self._head(self.P, x[i:i + POSITION_CHUNK])
                for i in range(0, x.shape[0], POSITION_CHUNK)]

    def close(self):
        self.P = None


@jax.jit
def _gaps(ref, picked):
    """Per position: the reference's best logit less its logit of ``picked``."""
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]


def _by_chunk(picks: Dict[int, int], n_chunks: int):
    """{position: token} -> per chunk (token per row, rows that count)."""
    import numpy as np

    tok = np.zeros((n_chunks, POSITION_CHUNK), np.int32)
    use = np.zeros((n_chunks, POSITION_CHUNK), bool)
    for pos, t in picks.items():
        tok[pos // POSITION_CHUNK, pos % POSITION_CHUNK] = t
        use[pos // POSITION_CHUNK, pos % POSITION_CHUNK] = True
    return tok, use


def widest_gap(ref_chunks, picked_chunks) -> float:
    """Widest gap over the positions that count: ``picked_chunks`` holds per
    chunk (tokens, rows that count)."""
    worst = 0.0
    for ref, (tok, use) in zip(ref_chunks, picked_chunks):
        if use.any():
            g = jax.device_get(_gaps(ref, jnp.asarray(tok)))
            worst = max(worst, float(g[use].max()))
    return worst


def served_picks(served: Sequence[int], prompt_len: int, n_chunks: int):
    """``served[j]`` was picked at position ``prompt_len - 1 + j``."""
    tok, use = _by_chunk({prompt_len - 1 + j: t for j, t in enumerate(served)}, n_chunks)
    return list(zip(tok, use))


@jax.jit
def _argmax(x):
    return jnp.argmax(x, axis=-1).astype(jnp.int32)


def control_picks(ctl_chunks, prompt_len: int, n: int):
    """The token the control puts first at each served position."""
    out = []
    for i, ctl in enumerate(ctl_chunks):
        tok = jax.device_get(_argmax(ctl))
        pos = i * POSITION_CHUNK + jnp.arange(POSITION_CHUNK)
        use = jax.device_get((pos >= prompt_len - 1) & (pos < prompt_len - 1 + n))
        out.append((tok, use))
    return out
