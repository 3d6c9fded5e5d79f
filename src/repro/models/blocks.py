"""Transformer blocks: dense, MoE, mLSTM, sLSTM, hymba (parallel attn+SSM),
whisper encoder/decoder. One init/apply pair per kind, dispatched by
``LayerSpec.kind``; every init returns (params, axes) for the sharding rules.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import gla as gla_lib
from repro.models.attention import KVCache
from repro.models.gla import GLAState, SLSTMState
from repro.models.layers import (
    COMPUTE_DTYPE,
    INIT_STD,
    init_layernorm,
    init_rmsnorm,
    layernorm,
    mrope,
    rmsnorm,
    rope,
    rope_half,
)
from repro.models.moe import init_moe, moe_apply

__all__ = ["LayerSpec", "init_block", "apply_block", "init_block_cache"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str = "dense"   # dense | moe | mlstm | slstm | hymba | enc | dec
    window: int = 0       # 0 = full attention; >0 = sliding window


def _norm_init(cfg):
    if cfg.norm_type == "layernorm":
        return init_layernorm(cfg.d_model)
    return init_rmsnorm(cfg.d_model)


def _norm_apply(cfg, x, p):
    if cfg.norm_type == "layernorm":
        return layernorm(x, p)
    return rmsnorm(x, p)


def _rope_apply(cfg, x, positions):
    if cfg.rope_variant == "none":
        return x
    if cfg.rope_variant == "rope2d":
        return rope_half(x, positions, cfg.rope_theta)
    if cfg.rope_variant == "mrope":
        return mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return rope(x, positions, cfg.rope_theta)


# ---------------------------------------------------------------------------
# attention sub-module
# ---------------------------------------------------------------------------


def init_attention(key, cfg, cross: bool = False):
    D, Hq, Hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    params = {
        "wq": jax.random.normal(ks[0], (D, Hq, dh), jnp.float32) * INIT_STD,
        "wk": jax.random.normal(ks[1], (D, Hkv, dh), jnp.float32) * INIT_STD,
        "wv": jax.random.normal(ks[2], (D, Hkv, dh), jnp.float32) * INIT_STD,
        "wo": jax.random.normal(ks[3], (Hq, dh, D), jnp.float32) * INIT_STD,
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qk_norm and not cross:
        params["q_norm"] = jnp.ones((dh,), jnp.float32)
        params["k_norm"] = jnp.ones((dh,), jnp.float32)
        axes["q_norm"] = ("head_dim",)
        axes["k_norm"] = ("head_dim",)
    return params, axes


def _qk_normalize(x, scale):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def apply_attention(
    p,
    x: jnp.ndarray,
    cfg,
    *,
    window: int = 0,
    causal: bool = True,
    positions=None,           # (B, S) or (3, B, S) for mrope; None = no rope
    kv_source: Optional[jnp.ndarray] = None,  # cross-attention source
    cache: Optional[KVCache] = None,
    cur_pos: Optional[jnp.ndarray] = None,    # (B,) decode position
    kv_lengths: Optional[jnp.ndarray] = None,  # (B,) prefill prompt lengths
    layer=None,               # decode: ``cache`` is a stack, this its layer
):
    """Returns (out, new_cache).

    Three cache regimes: ``cache + cur_pos`` with a single-token input is a
    decode step (circular write + position-masked attention; with ``layer``
    the cache is the scan unit's whole stack and is returned updated in
    place at that layer);
    ``cache + kv_lengths`` with a full sequence is a one-shot prefill (the
    forward runs as train attention and the whole K/V sequence is written
    into the cache in one gather); cache-less calls are plain training.
    """
    cd = COMPUTE_DTYPE
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bsd,dhe->bshe", x.astype(cd), p["wq"].astype(cd))
    if "q_norm" in p:
        q = _qk_normalize(q, p["q_norm"])

    decode = cache is not None and cur_pos is not None and x.shape[1] == 1
    if kv_source is None or not decode:
        k = jnp.einsum("bsd,dhe->bshe", src.astype(cd), p["wk"].astype(cd))
        v = jnp.einsum("bsd,dhe->bshe", src.astype(cd), p["wv"].astype(cd))
        if "k_norm" in p:
            k = _qk_normalize(k, p["k_norm"])
    else:
        k = v = None  # cross-attention decode uses the cached projections

    if positions is not None and kv_source is None:
        q = _rope_apply(cfg, q, positions)
        k = _rope_apply(cfg, k, positions)
    elif positions is not None and kv_source is not None:
        q = _rope_apply(cfg, q, positions)

    new_cache = cache
    if decode:
        if kv_source is None:
            new_cache = attn_lib.cache_update(cache, k, v, cur_pos, layer)
            out = attn_lib.decode_attention(
                q, new_cache, cur_pos, layer=layer, window=window,
                softcap_val=cfg.attn_softcap, k_chunk=cfg.decode_k_chunk,
                unroll=cfg.unroll_scans,
            )
        else:
            # cross-attention: cache holds the full encoder K/V (always valid)
            out = attn_lib.decode_attention(
                q, cache, jnp.full_like(cur_pos, 2**30), layer=layer, window=0,
                softcap_val=cfg.attn_softcap, k_chunk=cfg.decode_k_chunk,
                unroll=cfg.unroll_scans,
            )
    else:
        out = attn_lib.train_attention(
            q, k, v, causal=causal, window=window,
            softcap_val=cfg.attn_softcap,
            q_chunk=cfg.attn_q_chunk, k_chunk=cfg.attn_k_chunk,
            unroll=cfg.unroll_scans,
        )
        if cache is not None and kv_source is None and kv_lengths is not None:
            # one-shot prefill: park the whole (post-rope) K/V sequence in
            # the decode cache; right-padded tails stay unwritten (pos -1)
            new_cache = attn_lib.cache_prefill(cache, k, v, kv_lengths)
    y = jnp.einsum("bshe,hed->bsd", out.astype(cd), p["wo"].astype(cd))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP sub-module
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, gated: bool = True):
    ks = jax.random.split(key, 3)
    params = {
        "w1": jax.random.normal(ks[0], (d_model, d_ff), jnp.float32) * INIT_STD,
        "w2": jax.random.normal(ks[1], (d_ff, d_model), jnp.float32) * INIT_STD,
    }
    axes = {"w1": ("embed", "mlp"), "w2": ("mlp", "embed")}
    if gated:
        params["w3"] = jax.random.normal(ks[2], (d_model, d_ff), jnp.float32) * INIT_STD
        axes["w3"] = ("embed", "mlp")
    return params, axes


def apply_mlp(p, x, act: str = "silu"):
    cd = COMPUTE_DTYPE
    h = jnp.einsum("bsd,df->bsf", x.astype(cd), p["w1"].astype(cd))
    a = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
    if "w3" in p:
        a = a * jnp.einsum("bsd,df->bsf", x.astype(cd), p["w3"].astype(cd))
    return jnp.einsum("bsf,fd->bsd", a, p["w2"].astype(cd))


# ---------------------------------------------------------------------------
# block kinds
# ---------------------------------------------------------------------------


def _init_dense(key, cfg):
    k1, k2 = jax.random.split(key)
    attn_p, attn_a = init_attention(k1, cfg)
    mlp_p, mlp_a = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.gated_mlp)
    n1, n1a = _norm_init(cfg)
    n2, n2a = _norm_init(cfg)
    params = {"attn": attn_p, "mlp": mlp_p, "norm1": n1, "norm2": n2}
    axes = {"attn": attn_a, "mlp": mlp_a, "norm1": n1a, "norm2": n2a}
    if cfg.sandwich_norm:
        for name in ("post1", "post2"):
            p_, a_ = _norm_init(cfg)
            params[name] = p_
            axes[name] = a_
    return params, axes


def _apply_dense(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
                 kv_lengths=None, layer=None):
    h, new_cache = apply_attention(
        p["attn"], _norm_apply(cfg, x, p["norm1"]), cfg,
        window=spec.window, positions=positions, cache=cache, cur_pos=cur_pos,
        kv_lengths=kv_lengths, layer=layer,
    )
    if cfg.sandwich_norm:
        h = _norm_apply(cfg, h, p["post1"])
    x = x + h
    h2 = apply_mlp(p["mlp"], _norm_apply(cfg, x, p["norm2"]), cfg.act)
    if cfg.sandwich_norm:
        h2 = _norm_apply(cfg, h2, p["post2"])
    return x + h2, new_cache, jnp.float32(0.0)


def _init_moe(key, cfg):
    k1, k2 = jax.random.split(key)
    attn_p, attn_a = init_attention(k1, cfg)
    moe_p, moe_a = init_moe(k2, cfg.d_model, cfg.d_ff, cfg.num_experts)
    n1, n1a = _norm_init(cfg)
    n2, n2a = _norm_init(cfg)
    return (
        {"attn": attn_p, "moe": moe_p, "norm1": n1, "norm2": n2},
        {"attn": attn_a, "moe": moe_a, "norm1": n1a, "norm2": n2a},
    )


def _apply_moe(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
               kv_lengths=None, layer=None):
    h, new_cache = apply_attention(
        p["attn"], _norm_apply(cfg, x, p["norm1"]), cfg,
        window=spec.window, positions=positions, cache=cache, cur_pos=cur_pos,
        kv_lengths=kv_lengths, layer=layer,
    )
    x = x + h
    out, aux = moe_apply(
        p["moe"], _norm_apply(cfg, x, p["norm2"]),
        top_k=cfg.top_k, group_size=cfg.moe_group_size,
    )
    return x + out, new_cache, aux


def _init_mlstm(key, cfg):
    D = cfg.d_model
    Di = D  # inner dim (projection factor folded into q/k/v dims)
    H, dh = cfg.num_heads, D // cfg.num_heads
    ks = jax.random.split(key, 8)
    params = {
        "w_in": jax.random.normal(ks[0], (D, 2 * Di), jnp.float32) * INIT_STD,
        "wq": jax.random.normal(ks[1], (Di, H, dh), jnp.float32) * INIT_STD,
        "wk": jax.random.normal(ks[2], (Di, H, dh), jnp.float32) * INIT_STD,
        "wv": jax.random.normal(ks[3], (Di, H, dh), jnp.float32) * INIT_STD,
        "w_if": jax.random.normal(ks[4], (Di, 2 * H), jnp.float32) * INIT_STD,
        "b_if": jnp.concatenate(
            [jnp.zeros((H,)), jnp.full((H,), 3.0)]  # forget-gate bias ~ keep
        ).astype(jnp.float32),
        "w_out": jax.random.normal(ks[5], (Di, D), jnp.float32) * INIT_STD,
    }
    n1, n1a = _norm_init(cfg)
    params["norm"] = n1
    axes = {
        "w_in": ("embed", "mlp"),
        "wq": ("mlp", "heads", "head_dim"),
        "wk": ("mlp", "heads", "head_dim"),
        "wv": ("mlp", "heads", "head_dim"),
        "w_if": ("mlp", "heads"),
        "b_if": ("heads",),
        "w_out": ("mlp", "embed"),
        "norm": n1a,
    }
    return params, axes


def _apply_mlstm(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
                 kv_lengths=None, layer=None):
    cd = COMPUTE_DTYPE
    D = cfg.d_model
    H, dh = cfg.num_heads, D // cfg.num_heads
    h = _norm_apply(cfg, x, p["norm"])
    up = jnp.einsum("bsd,de->bse", h.astype(cd), p["w_in"].astype(cd))
    xm, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bse,ehd->bshd", xm, p["wq"].astype(cd))
    k = jnp.einsum("bse,ehd->bshd", xm, p["wk"].astype(cd)) / jnp.sqrt(float(dh))
    v = jnp.einsum("bse,ehd->bshd", xm, p["wv"].astype(cd))
    gates = jnp.einsum("bse,eh->bsh", xm, p["w_if"].astype(cd)).astype(jnp.float32)
    gates = gates + p["b_if"]
    i_gate, f_gate = jnp.split(gates, 2, axis=-1)
    log_a = jax.nn.log_sigmoid(f_gate)           # (B, S, H)
    k = k * jax.nn.sigmoid(i_gate)[..., None]    # fold input gate into k

    if kv_lengths is not None and x.shape[1] > 1:
        # right-padded prefill: an identity recurrence step is a_t = 1,
        # k_t = 0, so padded steps carry S/n through exactly
        step_ok = jnp.arange(x.shape[1])[None, :] < kv_lengths[:, None]
        log_a = jnp.where(step_ok[..., None], log_a, 0.0)
        k = jnp.where(step_ok[..., None, None], k, 0.0)

    if cache is not None and x.shape[1] == 1:
        y, new_state = gla_lib.gla_decode_step(q, k, v, log_a, cache)
    else:
        y, new_state = gla_lib.gla_chunked(
            q, k, v, log_a, chunk=cfg.gla_chunk, init_state=cache,
            unroll=cfg.unroll_scans,
        )
    y = y.reshape(*y.shape[:2], -1)              # (B, S, Di)
    out = jnp.einsum(
        "bse,ed->bsd", (y * jax.nn.silu(z)).astype(cd), p["w_out"].astype(cd)
    )
    return x + out, new_state, jnp.float32(0.0)


def _init_slstm(key, cfg):
    D = cfg.d_model
    H = cfg.num_heads
    dh = D // H
    ks = jax.random.split(key, 4)
    f_ff = int(round(4 * D / 3 / 128)) * 128
    mlp_p, mlp_a = init_mlp(ks[2], D, max(f_ff, 128), gated=True)
    n1, n1a = _norm_init(cfg)
    n2, n2a = _norm_init(cfg)
    params = {
        "w_gates": jax.random.normal(ks[0], (D, 4, D), jnp.float32) * INIT_STD,
        "r_gates": jax.random.normal(ks[1], (H, 4, dh, dh), jnp.float32) * INIT_STD,
        "w_out": jax.random.normal(ks[3], (D, D), jnp.float32) * INIT_STD,
        "mlp": mlp_p,
        "norm1": n1,
        "norm2": n2,
    }
    axes = {
        "w_gates": ("embed", "gates", "mlp"),
        "r_gates": ("heads", "gates", "head_dim", "head_dim"),
        "w_out": ("mlp", "embed"),
        "mlp": mlp_a,
        "norm1": n1a,
        "norm2": n2a,
    }
    return params, axes


def _apply_slstm(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
                 kv_lengths=None, layer=None):
    cd = COMPUTE_DTYPE
    h = _norm_apply(cfg, x, p["norm1"])
    gates_x = jnp.einsum("bsd,dge->bsge", h.astype(cd), p["w_gates"].astype(cd))
    step_mask = None
    if kv_lengths is not None and x.shape[1] > 1:
        step_mask = jnp.arange(x.shape[1])[None, :] < kv_lengths[:, None]
    hs, new_state = gla_lib.slstm_scan(
        gates_x, p["r_gates"], cfg.num_heads, init_state=cache,
        step_mask=step_mask,
    )
    out = jnp.einsum("bsd,de->bse", hs.astype(cd), p["w_out"].astype(cd))
    x = x + out
    x = x + apply_mlp(p["mlp"], _norm_apply(cfg, x, p["norm2"]), cfg.act)
    return x, new_state, jnp.float32(0.0)


def _init_hymba(key, cfg):
    D = cfg.d_model
    H = cfg.num_heads
    dh = D // H
    st = cfg.ssm_state
    ks = jax.random.split(key, 10)
    attn_p, attn_a = init_attention(ks[0], cfg)
    mlp_p, mlp_a = init_mlp(ks[1], D, cfg.d_ff, cfg.gated_mlp)
    n1, n1a = _norm_init(cfg)
    n2, n2a = _norm_init(cfg)
    params = {
        "attn": attn_p,
        "mlp": mlp_p,
        "norm1": n1,
        "norm2": n2,
        "ssm_in": jax.random.normal(ks[2], (D, 2 * D), jnp.float32) * INIT_STD,
        "ssm_dt": jax.random.normal(ks[3], (D, H), jnp.float32) * INIT_STD,
        "ssm_dt_bias": jnp.full((H,), -2.0, jnp.float32),
        "ssm_B": jax.random.normal(ks[4], (D, H, st), jnp.float32) * INIT_STD,
        "ssm_C": jax.random.normal(ks[5], (D, H, st), jnp.float32) * INIT_STD,
        "ssm_A_log": jnp.zeros((H,), jnp.float32),
        "ssm_D": jnp.ones((H,), jnp.float32),
        "ssm_out": jax.random.normal(ks[6], (D, D), jnp.float32) * INIT_STD,
        "scale_attn": jnp.ones((D,), jnp.float32),
        "scale_ssm": jnp.ones((D,), jnp.float32),
    }
    axes = {
        "attn": attn_a,
        "mlp": mlp_a,
        "norm1": n1a,
        "norm2": n2a,
        "ssm_in": ("embed", "mlp"),
        "ssm_dt": ("embed", "heads"),
        "ssm_dt_bias": ("heads",),
        "ssm_B": ("embed", "heads", "state"),
        "ssm_C": ("embed", "heads", "state"),
        "ssm_A_log": ("heads",),
        "ssm_D": ("heads",),
        "ssm_out": ("mlp", "embed"),
        "scale_attn": ("embed",),
        "scale_ssm": ("embed",),
    }
    return params, axes


def _apply_hymba(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
                 kv_lengths=None, layer=None):
    """Parallel attention + Mamba/SSD heads, outputs averaged (Hymba)."""
    cd = COMPUTE_DTYPE
    D, H = cfg.d_model, cfg.num_heads
    dh = D // H
    h = _norm_apply(cfg, x, p["norm1"])
    cache = cache or {"attn": None, "ssm": None}

    a_out, new_kv = apply_attention(
        p["attn"], h, cfg, window=spec.window, positions=positions,
        cache=cache["attn"], cur_pos=cur_pos, kv_lengths=kv_lengths, layer=layer,
    )

    up = jnp.einsum("bsd,de->bse", h.astype(cd), p["ssm_in"].astype(cd))
    xm, z = jnp.split(up, 2, axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", xm, p["ssm_dt"].astype(cd)).astype(jnp.float32)
        + p["ssm_dt_bias"]
    )                                             # (B, S, H)
    log_a = -dt * jnp.exp(p["ssm_A_log"])         # <= 0
    k = jnp.einsum("bsd,dhn->bshn", xm, p["ssm_B"].astype(cd))
    q = jnp.einsum("bsd,dhn->bshn", xm, p["ssm_C"].astype(cd))
    v = xm.reshape(*xm.shape[:2], H, dh) * dt[..., None].astype(cd)

    if kv_lengths is not None and x.shape[1] > 1:
        # padded prefill: a_t = 1, k_t = 0 makes the step an exact identity
        step_ok = jnp.arange(x.shape[1])[None, :] < kv_lengths[:, None]
        log_a = jnp.where(step_ok[..., None], log_a, 0.0)
        k = jnp.where(step_ok[..., None, None], k, 0.0)

    if cache["ssm"] is not None and x.shape[1] == 1:
        y, new_ssm = gla_lib.gla_decode_step(q, k, v, log_a, cache["ssm"], normalize=False)
    else:
        y, new_ssm = gla_lib.gla_chunked(
            q, k, v, log_a, chunk=cfg.gla_chunk, normalize=False,
            init_state=cache["ssm"], unroll=cfg.unroll_scans,
        )
    y = y + p["ssm_D"][None, None, :, None].astype(y.dtype) * v
    y = (y.reshape(*y.shape[:2], -1) * jax.nn.silu(z)).astype(cd)
    s_out = jnp.einsum("bse,ed->bsd", y, p["ssm_out"].astype(cd))

    combined = 0.5 * (
        a_out * p["scale_attn"].astype(cd) + s_out * p["scale_ssm"].astype(cd)
    )
    x = x + combined
    x = x + apply_mlp(p["mlp"], _norm_apply(cfg, x, p["norm2"]), cfg.act)
    return x, {"attn": new_kv, "ssm": new_ssm}, jnp.float32(0.0)


def _init_enc(key, cfg):
    k1, k2 = jax.random.split(key)
    attn_p, attn_a = init_attention(k1, cfg)
    mlp_p, mlp_a = init_mlp(k2, cfg.d_model, cfg.d_ff, gated=False)
    n1, n1a = _norm_init(cfg)
    n2, n2a = _norm_init(cfg)
    return (
        {"attn": attn_p, "mlp": mlp_p, "norm1": n1, "norm2": n2},
        {"attn": attn_a, "mlp": mlp_a, "norm1": n1a, "norm2": n2a},
    )


def _apply_enc(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
               kv_lengths=None, layer=None):
    h, _ = apply_attention(
        p["attn"], _norm_apply(cfg, x, p["norm1"]), cfg,
        window=0, causal=False, positions=None,
    )
    x = x + h
    x = x + apply_mlp(p["mlp"], _norm_apply(cfg, x, p["norm2"]), act="gelu")
    return x, None, jnp.float32(0.0)


def _init_dec(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    self_p, self_a = init_attention(k1, cfg)
    cross_p, cross_a = init_attention(k2, cfg, cross=True)
    mlp_p, mlp_a = init_mlp(k3, cfg.d_model, cfg.d_ff, gated=False)
    n1, n1a = _norm_init(cfg)
    n2, n2a = _norm_init(cfg)
    n3, n3a = _norm_init(cfg)
    return (
        {"self": self_p, "cross": cross_p, "mlp": mlp_p,
         "norm1": n1, "norm2": n2, "norm3": n3},
        {"self": self_a, "cross": cross_a, "mlp": mlp_a,
         "norm1": n1a, "norm2": n2a, "norm3": n3a},
    )


def _apply_dec(p, x, spec, cfg, *, positions, cache, cur_pos, enc_out=None,
               kv_lengths=None, layer=None):
    cache = cache or {"self": None, "cross": None}
    h, new_self = apply_attention(
        p["self"], _norm_apply(cfg, x, p["norm1"]), cfg,
        window=spec.window, positions=None, cache=cache["self"], cur_pos=cur_pos,
        kv_lengths=kv_lengths, layer=layer,
    )
    x = x + h
    h, _ = apply_attention(
        p["cross"], _norm_apply(cfg, x, p["norm2"]), cfg,
        window=0, causal=False, positions=None,
        kv_source=enc_out, cache=cache["cross"], cur_pos=cur_pos, layer=layer,
    )
    x = x + h
    x = x + apply_mlp(p["mlp"], _norm_apply(cfg, x, p["norm3"]), act="gelu")
    return x, {"self": new_self, "cross": cache["cross"]}, jnp.float32(0.0)


_INIT = {
    "dense": _init_dense,
    "moe": _init_moe,
    "mlstm": _init_mlstm,
    "slstm": _init_slstm,
    "hymba": _init_hymba,
    "enc": _init_enc,
    "dec": _init_dec,
}
_APPLY = {
    "dense": _apply_dense,
    "moe": _apply_moe,
    "mlstm": _apply_mlstm,
    "slstm": _apply_slstm,
    "hymba": _apply_hymba,
    "enc": _apply_enc,
    "dec": _apply_dec,
}


def init_block(key, cfg, kind: str):
    return _INIT[kind](key, cfg)


def apply_block(params, x, spec: LayerSpec, cfg, **kw):
    return _APPLY[spec.kind](params, x, spec, cfg, **kw)


def init_block_cache(cfg, spec: LayerSpec, batch: int, s_max: int):
    """Decode-time cache for one block. Windowed layers allocate only
    ``window`` slots (what bounds the long_500k memory for SWA archs)."""
    D, H = cfg.d_model, cfg.num_heads
    dh_model = D // H

    def kv():
        slots = min(s_max, spec.window) if spec.window > 0 else s_max
        # decode_attention scans in chunks of 1024; keep slot count aligned
        slots = max(256, slots)
        if slots % 256:
            slots += 256 - slots % 256
        return attn_lib.make_cache(batch, slots, cfg.num_kv_heads, cfg.head_dim)

    if spec.kind in ("dense", "moe"):
        return kv()
    if spec.kind == "mlstm":
        return GLAState(
            S=jnp.zeros((batch, H, dh_model, dh_model), jnp.float32),
            n=jnp.zeros((batch, H, dh_model), jnp.float32),
        )
    if spec.kind == "slstm":
        z = jnp.zeros((batch, D), jnp.float32)
        return SLSTMState(z, z, z, jnp.full((batch, D), -1e30, jnp.float32))
    if spec.kind == "hymba":
        return {
            "attn": kv(),
            "ssm": GLAState(
                S=jnp.zeros((batch, H, cfg.ssm_state, dh_model), jnp.float32),
                n=jnp.zeros((batch, H, cfg.ssm_state), jnp.float32),
            ),
        }
    if spec.kind == "dec":
        return {"self": kv(), "cross": None}  # cross filled at prefill
    return None
