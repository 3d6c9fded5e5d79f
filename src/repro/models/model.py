"""Model assembly: config, scan-unit grouping, init, train/prefill/decode.

A single composable stack covers all ten assigned architectures. Layers are
grouped into *scan units*: if the layer pattern has a small period p (gemma2
local/global: p=2; xLSTM m/m/m/s: p=4) the whole stack is one `lax.scan` over
stacked parameter pytrees — the production trick that keeps HLO size and
compile time flat in depth. Aperiodic patterns (hymba's 3 global layers) fall
back to maximal homogeneous runs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.attention import KVCache
from repro.models.blocks import (
    LayerSpec,
    apply_block,
    init_block,
    init_block_cache,
)
from repro.models.layers import (
    COMPUTE_DTYPE,
    chunked_cross_entropy,
    embed_lookup,
    init_embedding,
    init_rmsnorm,
    rmsnorm,
    init_layernorm,
    layernorm,
    sinusoidal_positions,
    softcap,
)

__all__ = [
    "ModelConfig",
    "ScanUnit",
    "init_model",
    "loss_fn",
    "prefill",
    "prefill_with_cache",
    "decode_step",
    "init_serve_cache",
]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    blocks: Tuple[LayerSpec, ...]
    encoder_blocks: Tuple[LayerSpec, ...] = ()
    num_experts: int = 0
    top_k: int = 0
    qk_norm: bool = False
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    rope_variant: str = "rope"   # rope | rope2d | mrope | none
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    norm_type: str = "rmsnorm"
    sandwich_norm: bool = False
    act: str = "silu"
    gated_mlp: bool = True
    tie_embeddings: bool = False
    ssm_state: int = 16
    gla_chunk: int = 128
    moe_group_size: int = 2048
    input_mode: str = "tokens"   # tokens | embeds (modality-stub archs)
    family: str = "decoder"      # decoder | encdec
    remat: bool = True
    # scan execution knobs (roofline probes unroll for honest op counts)
    unroll_scans: bool = False
    attn_q_chunk: int = 512
    attn_k_chunk: int = 1024
    decode_k_chunk: int = 1024
    ce_chunk: int = 512

    @property
    def sub_quadratic(self) -> bool:
        """True iff decode state is bounded (long_500k eligibility)."""
        return all(
            b.kind in ("mlstm", "slstm", "hymba") or b.window > 0
            for b in self.blocks
        )


@dataclasses.dataclass(frozen=True)
class ScanUnit:
    pattern: Tuple[LayerSpec, ...]
    repeat: int


def plan_scan_units(blocks: Tuple[LayerSpec, ...]) -> List[ScanUnit]:
    """Group layers into scan units (periodic pattern or maximal runs)."""
    L = len(blocks)
    for p in (1, 2, 3, 4):
        if L % p == 0 and L // p > 1:
            if all(blocks[i] == blocks[i % p] for i in range(L)):
                return [ScanUnit(tuple(blocks[:p]), L // p)]
    units: List[ScanUnit] = []
    i = 0
    while i < L:
        j = i
        while j < L and blocks[j] == blocks[i]:
            j += 1
        units.append(ScanUnit((blocks[i],), j - i))
        i = j
    return units


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _stack_init(key, cfg: ModelConfig, unit: ScanUnit):
    """Init one scan unit: per sub-pattern, params stacked over `repeat`."""
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}
    keys = jax.random.split(key, len(unit.pattern))
    for si, spec in enumerate(unit.pattern):
        layer_keys = jax.random.split(keys[si], unit.repeat)
        stacked = jax.vmap(lambda k: init_block(k, cfg, spec.kind)[0])(layer_keys)
        _, sub_axes = init_block(jax.random.PRNGKey(0), cfg, spec.kind)
        params[f"sub{si}"] = stacked
        axes[f"sub{si}"] = jax.tree_util.tree_map(
            lambda a: ("layers",) + a,
            sub_axes,
            is_leaf=lambda a: isinstance(a, tuple) and all(isinstance(s, str) for s in a),
        )
    return params, axes


def init_model(key, cfg: ModelConfig):
    """Returns (params, axes). Params are fp32 masters."""
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    emb, emb_axes = init_embedding(keys[0], cfg.vocab_size, cfg.d_model)
    params["embed"] = emb
    axes["embed"] = emb_axes

    units = plan_scan_units(cfg.blocks)
    unit_params, unit_axes = [], []
    for ui, unit in enumerate(units):
        p, a = _stack_init(jax.random.fold_in(keys[1], ui), cfg, unit)
        unit_params.append(p)
        unit_axes.append(a)
    params["decoder"] = unit_params
    axes["decoder"] = unit_axes

    if cfg.family == "encdec":
        enc_units = plan_scan_units(cfg.encoder_blocks)
        ep, ea = [], []
        for ui, unit in enumerate(enc_units):
            p, a = _stack_init(jax.random.fold_in(keys[2], ui), cfg, unit)
            ep.append(p)
            ea.append(a)
        params["encoder"] = ep
        axes["encoder"] = ea
        if cfg.norm_type == "layernorm":
            n, na = init_layernorm(cfg.d_model)
        else:
            n, na = init_rmsnorm(cfg.d_model)
        params["enc_norm"] = n
        axes["enc_norm"] = na

    if cfg.norm_type == "layernorm":
        n, na = init_layernorm(cfg.d_model)
    else:
        n, na = init_rmsnorm(cfg.d_model)
    params["final_norm"] = n
    axes["final_norm"] = na

    if not cfg.tie_embeddings:
        head = jax.random.normal(keys[3], (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02
        params["head"] = head
        axes["head"] = ("embed", "vocab")
    return params, axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _final_norm(cfg, x, p):
    if cfg.norm_type == "layernorm":
        return layernorm(x, p)
    return rmsnorm(x, p)


def _head_weight(cfg, params):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def _layer_view(c_unit, li):
    """Layer ``li``'s decode cache in a unit's stacked cache: KV caches stay
    whole (attention reads and writes them at ``li``); recurrent states,
    small per layer, are sliced out."""
    return jax.tree_util.tree_map(
        lambda c: c if isinstance(c, KVCache) else jax.lax.dynamic_index_in_dim(
            c, li, keepdims=False),
        c_unit, is_leaf=lambda c: isinstance(c, KVCache),
    )


def _layer_write(c_unit, new, li):
    """Inverse of ``_layer_view``: KV stacks come back already updated;
    recurrent states are written into the stack at ``li``."""
    return jax.tree_util.tree_map(
        lambda c, n: n if isinstance(c, KVCache) else jax.lax.dynamic_update_index_in_dim(
            c, n, li, 0),
        c_unit, new, is_leaf=lambda c: isinstance(c, KVCache),
    )


def _run_units(
    cfg: ModelConfig,
    units: List[ScanUnit],
    unit_params: List[Any],
    x: jnp.ndarray,
    *,
    positions,
    enc_out=None,
    caches: Optional[List[Any]] = None,
    cur_pos=None,
    kv_lengths=None,
    unit_axes: Optional[List[Any]] = None,
):
    """Run all scan units. Returns (x, new_caches, aux_sum).

    A decode call (``caches`` and ``cur_pos``) carries each unit's stacked
    cache through the layer scan with the layer index, so every layer
    writes its one new token into the stack in place; training and prefill
    pass caches as the scan's ``xs``/``ys``.
    """
    from repro.sharding.context import constrain_activation, constrain_layer_params

    aux_total = jnp.float32(0.0)
    new_caches: List[Any] = []
    decode = caches is not None and cur_pos is not None

    for ui, unit in enumerate(units):
        p_unit = unit_params[ui]
        cache_unit = caches[ui] if caches is not None else None
        a_unit = unit_axes[ui] if unit_axes is not None else None

        def apply_layer(h, aux, p_l, c_l, li, _unit=unit, _axes=a_unit):
            new_c = {}
            for si, spec in enumerate(_unit.pattern):
                sub_cache = c_l[f"sub{si}"] if c_l is not None else None
                p_sub = p_l[f"sub{si}"]
                if _axes is not None:
                    # in-body layout pin: keeps the backward grad accumulator
                    # in the ZeRO layout (see repro.sharding.context)
                    p_sub = constrain_layer_params(p_sub, _axes[f"sub{si}"])
                h = constrain_activation(h)
                h, nc, a = apply_block(
                    p_sub, h, spec, cfg,
                    positions=positions, cache=sub_cache, cur_pos=cur_pos,
                    enc_out=enc_out, kv_lengths=kv_lengths, layer=li,
                )
                new_c[f"sub{si}"] = nc
                aux = aux + a
            return h, aux, new_c

        if decode:
            def body(carry, xs):
                h, aux, c_unit, li = carry
                h, aux, new_c = apply_layer(h, aux, xs["params"], _layer_view(c_unit, li), li)
                return (h, aux, _layer_write(c_unit, new_c, li), li + 1), None
        else:
            def body(carry, xs):
                h, aux = carry
                c_l = xs.get("cache")
                h, aux, new_c = apply_layer(h, aux, xs["params"], c_l, None)
                return (h, aux), (new_c if c_l is not None else None)

        if cfg.remat:
            body = jax.checkpoint(body)

        xs = {"params": p_unit}
        if decode:
            (x, aux_total, cache_out, _), _ = jax.lax.scan(
                body, (x, aux_total, cache_unit, jnp.int32(0)), xs
            )
        else:
            if cache_unit is not None:
                xs["cache"] = cache_unit
            (x, aux_total), cache_out = jax.lax.scan(body, (x, aux_total), xs)
        new_caches.append(cache_out)
    return x, new_caches, aux_total


def forward_hidden(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Token/embed inputs -> final hidden states (train/prefill path)."""
    units = plan_scan_units(cfg.blocks)

    if cfg.input_mode == "embeds":
        x = batch["embeds"].astype(COMPUTE_DTYPE)
    else:
        x = embed_lookup(params["embed"], batch["tokens"])
    B, S = x.shape[0], x.shape[1]

    if cfg.rope_variant == "mrope":
        positions = batch.get("positions")
        if positions is None:
            pos1 = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            positions = jnp.stack([pos1] * 3)
    elif cfg.rope_variant == "none":
        positions = None
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    from repro.sharding.context import ctx_axes

    enc_out = None
    if cfg.family == "encdec":
        frames = batch["frames"].astype(COMPUTE_DTYPE)
        Se = frames.shape[1]
        e = frames + sinusoidal_positions(Se, cfg.d_model)[None].astype(frames.dtype)
        enc_units = plan_scan_units(cfg.encoder_blocks)
        e, _, _ = _run_units(cfg, enc_units, params["encoder"], e, positions=None,
                             unit_axes=ctx_axes("encoder"))
        enc_out = _final_norm(cfg, e, params["enc_norm"])

    x, _, aux = _run_units(
        cfg, units, params["decoder"], x, positions=positions, enc_out=enc_out,
        unit_axes=ctx_axes("decoder"),
    )
    x = _final_norm(cfg, x, params["final_norm"])
    return x, aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Causal LM loss (chunked CE) + MoE aux. Returns (loss, metrics)."""
    x, aux = forward_hidden(params, cfg, batch)
    loss = chunked_cross_entropy(
        x, _head_weight(cfg, params), batch["labels"],
        logit_cap=cfg.final_softcap, chunk=cfg.ce_chunk,
        unroll=cfg.unroll_scans,
    )
    total = loss + 0.01 * aux
    return total, {"ce_loss": loss, "aux_loss": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def init_serve_cache(cfg: ModelConfig, batch: int, s_max: int):
    """Cache pytree for decode, stacked per scan unit: each leaf leads with
    the unit's ``repeat`` layers (decode updates the stacks in place)."""
    units = plan_scan_units(cfg.blocks)
    caches = []
    for unit in units:
        unit_cache = {}
        for si, spec in enumerate(unit.pattern):
            # dec blocks recompute cross K/V from enc_out each step ("cross"
            # stays None); only self-attention KV is cached.
            one = init_block_cache(cfg, spec, batch, s_max)
            stacked = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (unit.repeat,) + a.shape), one
            )
            unit_cache[f"sub{si}"] = stacked
        caches.append(unit_cache)
    return caches


def decode_step(
    params,
    cfg: ModelConfig,
    caches: List[Any],
    tokens: jnp.ndarray,    # (B,) int32
    pos: jnp.ndarray,       # (B,) int32 absolute position
    enc_out: Optional[jnp.ndarray] = None,
):
    """One serving step: next-token logits + updated caches."""
    units = plan_scan_units(cfg.blocks)
    x = embed_lookup(params["embed"], tokens[:, None])  # (B, 1, D)
    B = x.shape[0]

    if cfg.rope_variant == "mrope":
        positions = jnp.stack([pos[None, :, None]] * 3)[:, 0]  # (3, B, 1)
    elif cfg.rope_variant == "none":
        positions = None
        from repro.models.layers import sinusoidal_at

        x = x + sinusoidal_at(pos, cfg.d_model)[:, None].astype(x.dtype)
    else:
        positions = pos[:, None]  # (B, 1)

    from repro.sharding.context import ctx_axes

    x, new_caches, _ = _run_units(
        cfg, units, params["decoder"], x,
        positions=positions, enc_out=enc_out, caches=caches, cur_pos=pos,
        unit_axes=ctx_axes("decoder"),
    )
    x = _final_norm(cfg, x, params["final_norm"])
    logits = jnp.einsum(
        "bsd,dv->bsv", x.astype(COMPUTE_DTYPE),
        _head_weight(cfg, params).astype(COMPUTE_DTYPE),
    )[:, 0].astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches


def prefill(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]):
    """Prefill pass: final hidden states + last-position logits.

    (The dry-run's prefill_32k cell lowers this; cache materialization for
    chat-style serving goes through ``prefill_with_cache``.)
    """
    x, _ = forward_hidden(params, cfg, batch)
    last = x[:, -1]
    logits = jnp.einsum(
        "bd,dv->bv", last.astype(COMPUTE_DTYPE),
        _head_weight(cfg, params).astype(COMPUTE_DTYPE),
    ).astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = softcap(logits, cfg.final_softcap)
    return logits


def prefill_with_cache(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,   # (B, S) int32, right-padded prompts
    lengths: jnp.ndarray,  # (B,) int32 real prompt length per row
    caches: List[Any],
):
    """One-shot prompt consumption for serving (token-decoder archs only).

    Runs the full-sequence forward once over the right-padded prompt batch,
    writing K/V (attention) and carried recurrent states (mLSTM/sLSTM/SSM)
    into the decode caches, and returns the logits at each row's *last real
    token* — the distribution the first generated token is sampled from.
    Padding is inert by construction: causal attention never looks forward
    to padded keys, padded cache slots keep pos = -1, and recurrent paths
    run identity steps (a = 1, k = 0 / state freeze) on padded positions.

    Returns ``(logits (B, V) fp32, new_caches)``.
    """
    if cfg.family != "decoder" or cfg.input_mode != "tokens":
        raise ValueError("prefill_with_cache serves token-decoder archs only")
    units = plan_scan_units(cfg.blocks)
    x = embed_lookup(params["embed"], tokens)
    B, S = tokens.shape

    if cfg.rope_variant == "mrope":
        pos1 = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        positions = jnp.stack([pos1] * 3)
    elif cfg.rope_variant == "none":
        positions = None
        x = x + sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    from repro.sharding.context import ctx_axes

    x, new_caches, _ = _run_units(
        cfg, units, params["decoder"], x, positions=positions,
        caches=caches, kv_lengths=lengths, unit_axes=ctx_axes("decoder"),
    )
    x = _final_norm(cfg, x, params["final_norm"])
    last = x[jnp.arange(B), jnp.maximum(lengths - 1, 0)]  # (B, D)
    logits = jnp.einsum(
        "bd,dv->bv", last.astype(COMPUTE_DTYPE),
        _head_weight(cfg, params).astype(COMPUTE_DTYPE),
    ).astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches
