"""Blockwise (flash-style) attention with GQA, windows, softcap, qk-norm.

Two entry points:

* ``train_attention`` — self/cross attention over full sequences. Runs an
  online-softmax scan over *static* (q-chunk, k-chunk) block pairs; for
  causal/windowed layouts the pair list is pruned at trace time, so no FLOPs
  are spent on fully-masked blocks and the S×S logit matrix never
  materializes (required for the 32k shapes).
* ``decode_attention`` — one query step against a (possibly circular) KV
  cache, scanning k chunks with dynamic position masks.  On the serving
  path the cache is a scan unit's whole stack, read and written in place
  at a layer index (``cache_update`` scatters only the new token).

GQA: q heads are grouped per kv head; kv heads are never replicated in
memory — the grouping happens in the einsum index structure.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["train_attention", "decode_attention", "KVCache", "cache_prefill"]

NEG_INF = -1e30


class KVCache(NamedTuple):
    """Circular KV cache. ``pos`` holds the absolute position stored in each
    slot (-1 = empty). Windowed layers allocate only ``window`` slots."""

    k: jnp.ndarray    # (B, Smax, Hkv, D), or (L, B, Smax, Hkv, D) stacked
    v: jnp.ndarray    # (B, Smax, Hkv, D), or (L, B, Smax, Hkv, D) stacked
    pos: jnp.ndarray  # (B, Smax) or (L, B, Smax) int32, absolute positions, -1 empty


def _split_heads(q, n_kv):
    """(B, S, Hq, D) -> (B, S, Hkv, G, D)."""
    B, S, Hq, D = q.shape
    return q.reshape(B, S, n_kv, Hq // n_kv, D)


def _block_pairs(nq: int, nk: int, qc: int, kc: int, causal: bool, window: int):
    """Static list of (iq, jk) chunk pairs that can contain unmasked entries
    (assumes positions are 0..S-1 in order — training layout)."""
    pairs = []
    for iq in range(nq):
        q_lo, q_hi = iq * qc, (iq + 1) * qc - 1
        for jk in range(nk):
            k_lo, k_hi = jk * kc, (jk + 1) * kc - 1
            if causal and k_lo > q_hi:
                continue  # entirely in the future
            if window > 0 and k_hi < q_lo - window + 1:
                continue  # entirely behind the window
            pairs.append((iq, jk))
    return pairs


def train_attention(
    q: jnp.ndarray,   # (B, Sq, Hq, D)
    k: jnp.ndarray,   # (B, Sk, Hkv, D)
    v: jnp.ndarray,   # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,          # static; 0 = unbounded
    softcap_val: float = 0.0,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    unroll: bool = False,     # python loop (roofline probes: honest op counts)
) -> jnp.ndarray:
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)

    qc = min(q_chunk, Sq)
    kc = min(k_chunk, Sk)
    pad_q = (-Sq) % qc
    pad_k = (-Sk) % kc
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    nq, nk = qp.shape[1] // qc, kp.shape[1] // kc

    qg = _split_heads(qp, Hkv)  # (B, Sq', Hkv, G, D)
    pairs = _block_pairs(nq, nk, qc, kc, causal, window)

    def make_body(iq: int, qs):
        """Online-softmax step for a fixed q chunk: carry is CHUNK-LOCAL
        (B, qc, Hkv, G[, D]) — the flash-attention structure. Keeping the
        carry chunk-local (not full-sequence) bounds the backward residuals
        (see EXPERIMENTS.md §Perf, chatglm3 hillclimb)."""

        def body(carry, jk):
            m, l, acc = carry
            ks = jax.lax.dynamic_slice_in_dim(kp, jk * kc, kc, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vp, jk * kc, kc, axis=1)

            s = jnp.einsum(
                "bqhgd,bkhd->bqhgk", qs.astype(jnp.float32), ks.astype(jnp.float32)
            ) * scale
            if softcap_val > 0:
                s = softcap_val * jnp.tanh(s / softcap_val)

            q_pos = iq * qc + jnp.arange(qc)
            k_pos = jk * kc + jnp.arange(kc)
            ok = jnp.ones((qc, kc), bool)
            if causal:
                ok &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                ok &= k_pos[None, :] > q_pos[:, None] - window
            ok &= (k_pos < Sk)[None, :]  # padding mask
            s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)

            s_max = jnp.max(s, axis=-1)  # (B, qc, Hkv, G)
            m_new = jnp.maximum(m, s_max)
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bqhgk,bkhd->bqhgd", p, vs.astype(jnp.float32))
            a_new = acc * corr[..., None] + pv
            return (m_new, l_new, a_new), None

        return body

    chunk_outs = []
    for iq in range(nq):
        jks = [jk for (i, jk) in pairs if i == iq]
        if not jks:
            chunk_outs.append(jnp.zeros((B, qc, Hkv, G, D), jnp.float32))
            continue
        qs = jax.lax.slice_in_dim(qg, iq * qc, (iq + 1) * qc, axis=1)
        m0 = jnp.full((B, qc, Hkv, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, qc, Hkv, G), jnp.float32)
        a0 = jnp.zeros((B, qc, Hkv, G, D), jnp.float32)
        body = make_body(iq, qs)
        if unroll:
            carry = (m0, l0, a0)
            for jk in jks:
                carry, _ = body(carry, jnp.int32(jk))
            m, l, acc = carry
        else:
            # flash-attention backward: block probs recomputed, never saved
            (m, l, acc), _ = jax.lax.scan(
                jax.checkpoint(body), (m0, l0, a0), jnp.asarray(jks, jnp.int32)
            )
        chunk_outs.append(acc / jnp.maximum(l, 1e-30)[..., None])

    out = jnp.concatenate(chunk_outs, axis=1)
    out = out.reshape(B, qp.shape[1], Hq, D)[:, :Sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,        # (B, 1, Hq, D)
    cache: KVCache,
    cur_pos: jnp.ndarray,  # (B,) absolute position of the query token
    *,
    layer=None,            # index into a stacked (L, B, Smax, ...) cache
    window: int = 0,
    softcap_val: float = 0.0,
    k_chunk: int = 1024,
    unroll: bool = False,
) -> jnp.ndarray:
    """Attend one query step over a layer's cache.

    With ``layer`` given, ``cache`` is the whole stack of a scan unit and
    each k chunk is sliced straight out of it at ``(layer, :, chunk)``: the
    read fuses into the attention and no copy of the layer is ever made.
    Without it, ``cache`` is one layer's.
    """
    if layer is None:
        cache, layer = _stacked(cache), 0
    B, _, Hq, D = q.shape
    Smax, Hkv = cache.k.shape[2], cache.k.shape[3]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    kc = min(k_chunk, Smax)
    assert Smax % kc == 0, (Smax, kc)
    nk = Smax // kc

    qg = _split_heads(q, Hkv)[:, 0]  # (B, Hkv, G, D)

    def chunk(a, jk):
        start = (layer, 0, jk * kc) + (0,) * (a.ndim - 3)
        return jax.lax.dynamic_slice(a, start, (1, B, kc) + a.shape[3:])[0]

    def body(carry, jk):
        m, l, acc = carry
        ks, vs, ps = chunk(cache.k, jk), chunk(cache.v, jk), chunk(cache.pos, jk)

        s = jnp.einsum(
            "bhgd,bkhd->bhgk", qg.astype(jnp.float32), ks.astype(jnp.float32)
        ) * scale
        if softcap_val > 0:
            s = softcap_val * jnp.tanh(s / softcap_val)

        ok = (ps >= 0) & (ps <= cur_pos[:, None])
        if window > 0:
            ok &= ps > (cur_pos[:, None] - window)
        s = jnp.where(ok[:, None, None, :], s, NEG_INF)

        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgk,bkhd->bhgd", p, vs.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, D), jnp.float32)
    if unroll:
        carry = (m0, l0, a0)
        for jk in range(nk):
            carry, _ = body(carry, jnp.int32(jk))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def cache_prefill(
    cache: KVCache,
    k_new: jnp.ndarray,    # (B, S, Hkv, D) — positions 0..S-1 in order
    v_new: jnp.ndarray,    # (B, S, Hkv, D)
    lengths: jnp.ndarray,  # (B,) int32 — real prompt length per row (rest pad)
) -> KVCache:
    """Write a whole (right-padded) prompt into the circular cache at once.

    Expressed as a gather, not a scatter: for each slot ``s`` the entry that
    a token-at-a-time prefill would leave behind is the *largest* position
    ``p < len`` with ``p % Smax == s`` (circular overwrite keeps the latest).
    Solving for it directly sidesteps duplicate-index scatter hazards and
    handles every per-row case uniformly — short prompts leave trailing
    slots untouched (pos stays -1 on a fresh cache), prompts longer than the
    slot count keep exactly their trailing ``Smax`` positions (what a
    windowed layer's circular cache retains anyway).
    """
    B, S = k_new.shape[:2]
    Smax = cache.k.shape[1]
    s = jnp.arange(Smax, dtype=jnp.int32)[None, :]          # (1, Smax)
    len_b = lengths.astype(jnp.int32)[:, None]              # (B, 1)
    # Largest p in [0, len) with p ≡ s (mod Smax); negative ⇒ slot unused.
    p_star = s + jnp.floor_divide(len_b - 1 - s, Smax) * Smax  # (B, Smax)
    valid = p_star >= 0
    pidx = jnp.clip(p_star, 0, S - 1)
    b_idx = jnp.arange(B)[:, None]
    k_sel = k_new[b_idx, pidx].astype(cache.k.dtype)
    v_sel = v_new[b_idx, pidx].astype(cache.v.dtype)
    k = jnp.where(valid[..., None, None], k_sel, cache.k)
    v = jnp.where(valid[..., None, None], v_sel, cache.v)
    p = jnp.where(valid, p_star, cache.pos)
    return KVCache(k, v, p)


def cache_update(cache: KVCache, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 pos: jnp.ndarray, layer=None) -> KVCache:
    """Write one decode step into the circular cache. pos: (B,).

    With ``layer`` given, ``cache`` is the whole stack of a scan unit and
    only the new token's K, V and position are scattered, at
    ``(layer, b, pos % Smax)``: the stack is updated in place.  Without it,
    ``cache`` is one layer's.
    """
    if layer is None:
        return _unstacked(cache_update(_stacked(cache), k_new, v_new, pos, 0))
    B, Smax = cache.k.shape[1], cache.k.shape[2]
    slot = (pos % Smax).astype(jnp.int32)  # (B,)
    b_idx = jnp.arange(B)
    k = cache.k.at[layer, b_idx, slot].set(k_new[:, 0].astype(cache.k.dtype))
    v = cache.v.at[layer, b_idx, slot].set(v_new[:, 0].astype(cache.v.dtype))
    p = cache.pos.at[layer, b_idx, slot].set(pos.astype(jnp.int32))
    return KVCache(k, v, p)


def _stacked(cache: KVCache) -> KVCache:
    """One layer's cache as a stack of one."""
    return KVCache(*(a[None] for a in cache))


def _unstacked(cache: KVCache) -> KVCache:
    return KVCache(*(a[0] for a in cache))


def make_cache(batch: int, s_max: int, n_kv: int, head_dim: int, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
        pos=jnp.full((batch, s_max), -1, jnp.int32),
    )
