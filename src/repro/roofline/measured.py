"""Roofline measurement by decomposed compilation.

XLA's ``cost_analysis`` counts while-loop bodies ONCE (verified in
tests/test_roofline.py), so a full-module count under-reports every scanned
model by the trip counts. This module derives honest per-device roofline
terms from compiled artifacts anyway, by compiling each *scan-unit body*
separately — with inner scans (attention block-pairs, GLA chunks, CE chunks)
unrolled so the compiled module contains every op — and multiplying by the
known trip counts:

    total = Σ_unit  cost(unit body) × repeat
          + cost(embed / head+loss tails)
          + cost(optimizer update)                    (train only)

Remat is accounted explicitly: with remat on, the executed schedule is
forward + (forward recompute + backward), so a train unit contributes
cost(grad probe) + cost(fwd probe).

Sequence-linear units (SSM/GLA/sliding-window) are probed at
S_probe = min(S, 4096) and scaled by S/S_probe (their compute and activation
traffic are linear in S; weight traffic is slightly over-scaled — noted in
EXPERIMENTS.md). Quadratic units are probed at full length. The strictly
sequential sLSTM cell cannot be unrolled at 4k; its (small) recurrent matmul
cost is added analytically.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.core.optimizers import make_optimizer
from repro.launch.specs import decode_cache_len
from repro.models import ModelConfig, init_model, plan_scan_units
from repro.models.blocks import apply_block, init_block, init_block_cache
from repro.models.layers import chunked_cross_entropy, embed_lookup
from repro.models.model import _final_norm, _head_weight, ScanUnit
from repro.roofline.analysis import (
    HW,
    V5E,
    collective_bytes_from_hlo,
    model_flops,
    roofline_terms,
)
from repro.sharding.rules import dp_axes, dp_size, spec_for, with_zero
from repro.sharding.specs import opt_state_shardings, param_shardings, replicated

SDS = jax.ShapeDtypeStruct

_IS_AXES_LEAF = lambda a: isinstance(a, tuple) and all(isinstance(s, str) for s in a)

LINEAR_KINDS = ("mlstm", "slstm")


def _unit_is_linear(unit: ScanUnit) -> bool:
    """Compute/memory linear in S? (bounded window or recurrent state)"""
    for spec in unit.pattern:
        if spec.kind in LINEAR_KINDS:
            continue
        if spec.kind in ("dense", "moe", "hymba") and spec.window > 0:
            continue
        return False
    return True


def _probe_cfg(cfg: ModelConfig, S: int) -> ModelConfig:
    return dataclasses.replace(
        cfg,
        unroll_scans=True,
        remat=False,
        attn_q_chunk=2048 if S >= 16384 else 512,
        attn_k_chunk=2048 if S >= 16384 else 1024,
        decode_k_chunk=8192 if S >= 131072 else 2048,
        ce_chunk=2048 if S >= 16384 else 512,
        gla_chunk=1024 if S >= 16384 else cfg.gla_chunk,
    )


def _dp_sharding(mesh: Mesh, shape: Tuple[int, ...], batch_dim: int = 0):
    n_dp = dp_size(mesh)
    if n_dp > 1 and shape[batch_dim] % n_dp == 0:
        dps = dp_axes(mesh)
        entry = dps if len(dps) > 1 else dps[0]
        e = [None] * len(shape)
        e[batch_dim] = entry
        return NamedSharding(mesh, P(*e))
    return replicated(mesh)


def _layer_param_shardings(params_single, axes_single, mesh: Mesh):
    def one(x, a):
        a = a[1:] if a and a[0] == "layers" else a
        spec = spec_for(tuple(x.shape), a, mesh)
        spec = with_zero(tuple(x.shape), spec, mesh, axes=a)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(
        one, params_single, axes_single, is_leaf=lambda x: hasattr(x, "shape") and not isinstance(x, dict)
    )


def _compile_cost(fn, args, in_shardings, mesh: Mesh, out_shardings=None):
    with mesh:
        lowered = jax.jit(
            fn, in_shardings=in_shardings, out_shardings=out_shardings
        ).lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        hlo,
    )


def _slstm_correction(cfg: ModelConfig, B: int, S: int, backward: bool, n_layers: int):
    """Analytic flops for the sequential sLSTM recurrence (R h matmul):
    per step 2·4·D·dh MACs -> 4 gates × D × dh × 2 flops; ×3 with backward."""
    dh = cfg.d_model // cfg.num_heads
    per_step = 2.0 * 4 * cfg.d_model * dh
    mult = 3.0 if backward else 1.0
    return per_step * B * S * mult * n_layers  # global; caller divides by dp


@dataclasses.dataclass
class CellMeasurement:
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: float = 0.0
    pieces: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def add(self, name, flops, bytes_accessed, hlo, multiplier=1.0):
        coll = collective_bytes_from_hlo(hlo, multiplier=multiplier)
        self.flops += flops * multiplier
        self.bytes_accessed += bytes_accessed * multiplier
        self.collective_bytes += coll["total"]
        self.pieces.append(
            {
                "name": name,
                "multiplier": multiplier,
                "flops": flops,
                "bytes": bytes_accessed,
                "collective_bytes": coll["total"],
                "collective_ops": coll.get("ops", 0),
            }
        )

    def add_analytic(self, name, flops):
        self.flops += flops
        self.pieces.append({"name": name, "multiplier": 1, "flops": flops,
                            "bytes": 0.0, "collective_bytes": 0.0, "analytic": True})


def measure_cell(
    arch: str,
    shape_name: str,
    mesh: Mesh,
    hw: HW = V5E,
    optimizer: str = "adamw4bit",
) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B = shape.global_batch
    S = shape.seq_len
    kind = shape.kind
    n_chips = mesh.devices.size

    meas = CellMeasurement()

    # real axes (python metadata) + param shapes
    closure = {}

    def capture():
        p, a = init_model(jax.random.PRNGKey(0), cfg)
        closure["axes"] = a
        return p

    params_s = jax.eval_shape(capture)
    axes = closure["axes"]

    D = cfg.d_model
    bf = jnp.bfloat16

    sections = [("decoder", cfg.blocks, axes.get("decoder"))]
    enc_out_sds = None
    S_dec = S
    if cfg.family == "encdec":
        S_dec = S // 2
        sections = [
            ("encoder", cfg.encoder_blocks, axes.get("encoder")),
            ("decoder", cfg.blocks, axes.get("decoder")),
        ]
        enc_out_sds = SDS((B, S_dec, D), bf)

    backward = kind == "train"

    for sec_name, blocks, sec_axes in sections:
        units = plan_scan_units(blocks)
        sec_S = S_dec if cfg.family == "encdec" else S
        for ui, unit in enumerate(units):
            linear = _unit_is_linear(unit) and kind != "decode"
            S_probe = min(sec_S, 4096) if linear else sec_S
            scale = sec_S / S_probe
            pcfg = _probe_cfg(cfg, S_probe)

            # single-layer params + axes
            p_single = {}
            a_single = {}
            for si, spec2 in enumerate(unit.pattern):
                ps = jax.eval_shape(
                    lambda sp=spec2: init_block(jax.random.PRNGKey(0), cfg, sp.kind)[0]
                )
                _, asx = init_block(jax.random.PRNGKey(0), cfg, spec2.kind)
                p_single[f"sub{si}"] = ps
                a_single[f"sub{si}"] = asx
            p_sh = {
                k: _layer_param_shardings(p_single[k], a_single[k], mesh)
                for k in p_single
            }

            if cfg.rope_variant == "mrope":
                positions = jnp.stack(
                    [jnp.broadcast_to(jnp.arange(S_probe)[None], (1, S_probe))] * 3
                )  # broadcast over batch at trace time is fine
                positions = None  # simplify: per-arch probes use default ids
            positions = None
            if kind != "decode" and cfg.rope_variant != "none" and unit.pattern[0].kind not in ("mlstm", "slstm", "enc"):
                positions = "arange"

            if kind == "decode":
                # one-token decode probe with single-layer cache
                s_max = decode_cache_len(cfg, shape)
                pdcfg = _probe_cfg(cfg, s_max)

                def mk_probe(unit=unit, pdcfg=pdcfg, s_max=s_max):
                    def probe(p_l, x, caches, pos):
                        h = x
                        new_c = {}
                        for si, sp in enumerate(unit.pattern):
                            pos_arg = pos[:, None] if pdcfg.rope_variant not in ("none",) else None
                            if pdcfg.rope_variant == "mrope":
                                pos_arg = jnp.stack([pos[:, None]] * 3)
                            h, nc, _ = apply_block(
                                p_l[f"sub{si}"], h, sp, pdcfg,
                                positions=pos_arg, cache=caches[f"sub{si}"],
                                cur_pos=pos, enc_out=None,
                            )
                            new_c[f"sub{si}"] = nc
                        return h, new_c
                    return probe

                caches_s = {
                    f"sub{si}": jax.eval_shape(
                        lambda sp=sp2: init_block_cache(cfg, sp, B, s_max)
                    )
                    for si, sp2 in enumerate(unit.pattern)
                }
                cache_sh = jax.tree_util.tree_map(
                    lambda leaf: _dp_sharding(mesh, leaf.shape, 0)
                    if leaf.shape and leaf.shape[0] % dp_size(mesh) == 0
                    else (
                        _dp_sharding(mesh, leaf.shape, 1)
                        if len(leaf.shape) > 1 and "data" in mesh.axis_names
                        and leaf.shape[1] % dict(zip(mesh.axis_names, mesh.devices.shape))["data"] == 0
                        and leaf.shape[1] >= 256
                        else replicated(mesh)
                    ),
                    caches_s,
                )
                x_s = SDS((B, 1, D), bf)
                pos_s = SDS((B,), jnp.int32)
                fl, by, hlo = _compile_cost(
                    mk_probe(),
                    (p_single, x_s, caches_s, pos_s),
                    (p_sh, _dp_sharding(mesh, (B, 1, D)), cache_sh, _dp_sharding(mesh, (B,))),
                    mesh,
                    out_shardings=(_dp_sharding(mesh, (B, 1, D)), cache_sh),
                )
                meas.add(f"{sec_name}/unit{ui}/decode", fl, by, hlo, unit.repeat)
                continue

            # train / prefill probes
            enc_arg = enc_out_sds if unit.pattern[0].kind == "dec" else None

            def mk_fwd(unit=unit, pcfg=pcfg, positions=positions, S_probe=S_probe, enc_arg=enc_arg):
                def fwd(p_l, x, enc=None):
                    h = x
                    pos = None
                    if positions == "arange":
                        pos = jnp.broadcast_to(jnp.arange(S_probe)[None], (x.shape[0], S_probe))
                        if pcfg.rope_variant == "mrope":
                            pos = jnp.stack([pos] * 3)
                    aux = jnp.float32(0)
                    for si, sp in enumerate(unit.pattern):
                        h, _, a = apply_block(
                            p_l[f"sub{si}"], h, sp, pcfg,
                            positions=pos, cache=None, cur_pos=None, enc_out=enc,
                        )
                        aux = aux + a
                    return h, aux
                return fwd

            x_s = SDS((B, S_probe, D), bf)
            x_sh = _dp_sharding(mesh, (B, S_probe, D))
            fwd = mk_fwd()

            if backward:
                def probe_grad(p_l, x, cot, enc=None):
                    def scalar(p_l, x):
                        h, aux = fwd(p_l, x, enc)
                        return jnp.sum(h.astype(jnp.float32) * cot) + aux
                    g = jax.grad(scalar, argnums=(0, 1))(p_l, x)
                    return g

                cot_s = SDS((B, S_probe, D), jnp.float32)
                args = (p_single, x_s, cot_s) + ((enc_arg,) if enc_arg is not None else ())
                shard = (p_sh, x_sh, x_sh) + ((_dp_sharding(mesh, enc_arg.shape),) if enc_arg is not None else ())
                fl, by, hlo = _compile_cost(probe_grad, args, shard, mesh,
                                            out_shardings=(p_sh, x_sh))
                meas.add(f"{sec_name}/unit{ui}/grad", fl, by, hlo, unit.repeat * scale)
                if cfg.remat:
                    fl2, by2, hlo2 = _compile_cost(
                        lambda p_l, x, enc=None: fwd(p_l, x, enc)[0],
                        (p_single, x_s) + ((enc_arg,) if enc_arg is not None else ()),
                        (p_sh, x_sh) + ((_dp_sharding(mesh, enc_arg.shape),) if enc_arg is not None else ()),
                        mesh,
                        out_shardings=x_sh,
                    )
                    meas.add(f"{sec_name}/unit{ui}/remat_fwd", fl2, by2, hlo2, unit.repeat * scale)
            else:
                args = (p_single, x_s) + ((enc_arg,) if enc_arg is not None else ())
                shard = (p_sh, x_sh) + ((_dp_sharding(mesh, enc_arg.shape),) if enc_arg is not None else ())
                fl, by, hlo = _compile_cost(
                    lambda p_l, x, enc=None: fwd(p_l, x, enc)[0], args, shard, mesh,
                    out_shardings=x_sh,
                )
                meas.add(f"{sec_name}/unit{ui}/fwd", fl, by, hlo, unit.repeat * scale)

            n_slstm = sum(1 for sp in unit.pattern if sp.kind == "slstm")
            if n_slstm:
                # per-device share: the recurrence is batch-parallel over dp
                meas.add_analytic(
                    f"{sec_name}/unit{ui}/slstm_recurrence",
                    _slstm_correction(cfg, B, S_probe, backward, n_slstm)
                    * unit.repeat * scale / max(1, dp_size(mesh)),
                )

    # ---- tails -----------------------------------------------------------
    pcfg_tail = _probe_cfg(cfg, S_dec)
    head_shape = (
        params_s["embed"].shape if cfg.tie_embeddings else params_s["head"].shape
    )
    fn_s = params_s["final_norm"]
    x_s = SDS((B, S_dec, D), bf)
    x_sh = _dp_sharding(mesh, (B, S_dec, D))
    head_sds = SDS(head_shape, jnp.float32)
    head_axes = ("vocab", "embed") if cfg.tie_embeddings else ("embed", "vocab")
    head_sh = NamedSharding(
        mesh, with_zero(head_shape, spec_for(head_shape, head_axes, mesh), mesh)
    )

    if kind == "train":
        labels_s = SDS((B, S_dec), jnp.int32)

        def tail(head_w, norm_p, x, labels):
            xf = _final_norm(cfg, x, norm_p)
            hw_mat = head_w.T if cfg.tie_embeddings else head_w
            return chunked_cross_entropy(
                xf, hw_mat, labels, logit_cap=cfg.final_softcap,
                chunk=pcfg_tail.ce_chunk, unroll=True,
            )

        def tail_grad(head_w, norm_p, x, labels):
            return jax.grad(tail, argnums=(0, 1, 2))(head_w, norm_p, x, labels)

        fl, by, hlo = _compile_cost(
            tail_grad,
            (head_sds, fn_s, x_s, labels_s),
            (head_sh, None, x_sh, _dp_sharding(mesh, (B, S_dec))),
            mesh,
            out_shardings=(head_sh, None, x_sh),
        )
        meas.add("tail/loss_grad", fl, by, hlo)

        if cfg.input_mode == "tokens":
            emb_sds = SDS(params_s["embed"].shape, jnp.float32)
            emb_sh = NamedSharding(
                mesh,
                with_zero(
                    params_s["embed"].shape,
                    spec_for(params_s["embed"].shape, ("vocab", "embed"), mesh),
                    mesh,
                ),
            )
            tok_s = SDS((B, S_dec), jnp.int32)

            def emb_probe(emb, toks, cot):
                return jnp.sum(embed_lookup(emb, toks).astype(jnp.float32) * cot)

            fl, by, hlo = _compile_cost(
                lambda e, t, c: jax.grad(emb_probe)(e, t, c),
                (emb_sds, tok_s, SDS((B, S_dec, D), jnp.float32)),
                (emb_sh, _dp_sharding(mesh, (B, S_dec)), x_sh),
                mesh,
                out_shardings=emb_sh,
            )
            meas.add("tail/embed_grad", fl, by, hlo)

        # optimizer update over the full parameter set (elementwise, no scans)
        opt = make_optimizer(optimizer, 1e-4)
        params_zeros = lambda: jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), params_s
        )
        state_s = jax.eval_shape(lambda: opt.init(params_zeros()))
        grads_s = jax.tree_util.tree_map(lambda s: SDS(s.shape, jnp.float32), params_s)
        p_shard = param_shardings(params_s, axes, mesh, zero=True)
        s_shard = opt_state_shardings(state_s, params_s, axes, mesh, zero=True)
        g_shard = jax.tree_util.tree_map(
            lambda sh: sh, p_shard
        )  # grads in ZeRO layout too

        def opt_probe(grads, state, params):
            new_p, new_s = opt.update(grads, state, params)
            return new_p, new_s

        fl, by, hlo = _compile_cost(
            opt_probe, (grads_s, state_s, params_s), (g_shard, s_shard, p_shard),
            mesh, out_shardings=(p_shard, s_shard),
        )
        meas.add("tail/optimizer_update", fl, by, hlo)
    else:
        # prefill/decode logits tail: one position (decode) or last (prefill)
        def logits_tail(head_w, norm_p, x):
            xf = _final_norm(cfg, x[:, -1:], norm_p)
            hw_mat = head_w.T if cfg.tie_embeddings else head_w
            return jnp.einsum("bsd,dv->bsv", xf.astype(bf), hw_mat.astype(bf))

        n_pos = 1 if kind == "decode" else S_dec
        fl, by, hlo = _compile_cost(
            logits_tail,
            (SDS(head_shape, bf), fn_s, SDS((B, n_pos, D), bf)),
            (head_sh, None, _dp_sharding(mesh, (B, n_pos, D))),
            mesh,
            out_shardings=_dp_sharding(mesh, (B, n_pos, 8)),
        )
        meas.add("tail/logits", fl, by, hlo)

    tokens = B * (S if kind != "decode" else 1)
    mflops = model_flops(cfg, params_s, axes, kind, tokens)
    terms = roofline_terms(
        {"flops": meas.flops, "bytes accessed": meas.bytes_accessed},
        meas.collective_bytes,
        n_chips,
        mflops,
        hw,
    )
    return {
        "arch": arch,
        "shape": shape_name,
        "n_chips": n_chips,
        "method": "decomposed-compile (per-unit bodies x trip counts)",
        "roofline": terms.as_dict(),
        "pieces": meas.pieces,
    }
