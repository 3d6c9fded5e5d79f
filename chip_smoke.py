#!/usr/bin/env python3
"""Smoke test of the 4-bit optimizer system on TPU chips, in one process.

    python chip_smoke.py              # one chip: phases 1-4 below
    python chip_smoke.py --chips 4    # four chips: the sharded path only

Model: internlm2-1.8b at its published widths (d_model 2048, 16 heads with
8 KV heads, head_dim 128, d_ff 8192, vocab 92544) with random weights drawn
from ``--seed``.  On one chip the depth is cut from 24 layers to 8: the
fp32 embedding and head with fp32 Adam state take about 6.1 GB, and each
layer with fp32 param and gradient plus 4-bit state about 0.57 GB, so 8
layers at batch 4 x 1024 tokens compile to about 14.1 GB of the chip's 16 GB
(``memory_analysis()`` of the compiled step).  The four-chip path runs all
24 layers, ZeRO-sharded over a (data=2, model=2) mesh.

One chip, in order; any failure exits non-zero:

1. device — JAX must find a TPU, the kernel backend must be the compiled
   one, and ``REPRO_KERNEL_BACKEND`` must be unset.
2. kernel parity — the compiled ``fused_adamw4`` against the jnp oracle
   run through XLA on the same chip, for one MLP leaf (2048, 8192) and one
   stacked (8, 2048, 8192) leaf, round-to-nearest and stochastic rounding.
   Mosaic and XLA may round division and square root differently, so codes
   may differ by one step in under 1e-4 of the elements; scales agree
   within 1e-6 relative, element by element, and params within 1e-6 of the
   largest |param| (an update is ~1e-3 of a param, so a wrong one shows).
3. training — 10 ``production4bit`` steps with stochastic rounding through
   the functions ``launch/train.py`` runs; the compiled step must contain
   the kernel and the loss must stay finite and fall.
4. serving — ``ServeEngine`` with bf16 and q4 weights, greedy and sampled
   requests through the function ``launch/serve.py`` runs; every request
   must finish.

Four chips: training only, a few steps with the fp32 gradient collective
and the same steps with the int4 one; the two losses must agree within
0.08 nats.

Times printed here are single host-clock readings, not benchmark numbers.
The last line is the JSON result: {"ok": true, "device": {...}}.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

LAYERS_ONE_CHIP = 8        # of 24: what fits in 16 GB (see above)
BATCH, SEQ = 4, 1024
TRAIN_STEPS = 10
LR = 1e-3
MESH_STEPS = 3             # per collective mode on four chips
CODE_FLIP_FRACTION = 1e-4  # phase 2 limits
FLOAT_RTOL = 1e-6
COMMS_LOSS_TOL = 0.08      # nats; the comms tolerance of benchmarks/drift.py


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------


def check_device(chips: int):
    if os.environ.get("REPRO_KERNEL_BACKEND"):
        raise SystemExit(
            "phase 1: REPRO_KERNEL_BACKEND is set "
            f"({os.environ['REPRO_KERNEL_BACKEND']!r}); the smoke test runs "
            "only the platform's own kernel route"
        )
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"phase 1: no TPU — JAX found {len(devices)} "
            f"{devices[0].platform} device(s)"
        )
    if len(devices) != chips:
        raise SystemExit(f"phase 1: want {chips} chip(s), JAX found {len(devices)}")

    from repro.kernels.ops import kernel_backend

    if kernel_backend() != "tpu":
        raise SystemExit(f"phase 1: kernel backend is {kernel_backend()!r}, not 'tpu'")
    log(f"phase 1 device: {devices[0].device_kind} x{len(devices)} "
        f"(platform {devices[0].platform}, kernel backend tpu)")
    return devices


# ---------------------------------------------------------------------------
# phase 2: kernel parity at real width
# ---------------------------------------------------------------------------


def _parity_leaf(shape, sr, seed):
    import jax
    import jax.numpy as jnp

    from repro.core.optimizers.adamw import M_4BIT, V_4BIT
    from repro.core.quantizer import quantize

    m_cfg = dataclasses.replace(M_4BIT, stochastic_rounding=sr)
    v_cfg = dataclasses.replace(V_4BIT, stochastic_rounding=sr)
    k = jax.random.split(jax.random.PRNGKey(seed), 4)

    @jax.jit
    def make():
        p = 0.02 * jax.random.normal(k[0], shape)
        g = 1e-3 * jax.random.normal(k[1], shape)
        m = quantize(1e-3 * jax.random.normal(k[2], shape), m_cfg)
        v = quantize(1e-6 * jnp.square(jax.random.normal(k[3], shape)) + 1e-12, v_cfg)
        return p, g, m, v

    return make()


def _timed(fn, *args, reps=3):
    import jax

    out = jax.block_until_ready(fn(*args))  # compile + first run
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return out, (time.perf_counter() - t0) / reps


def kernel_parity(cfg, seed):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    D, F = cfg.d_model, cfg.d_ff
    t = 10  # bias corrections of step 10
    lr, b1, b2, eps, wd = jnp.float32(LR), 0.9, 0.999, 1e-8, 0.01
    bc1, bc2 = jnp.float32(1 - b1**t), jnp.float32(1 - b2**t)

    def route(backend):
        return jax.jit(
            lambda p, g, m, v, key: ops.fused_adamw4_leaf(
                p, g, m, v, lr, b1, b2, eps, wd, bc1, bc2, key=key,
                backend=backend,
            )
        )

    kernel, oracle = route("tpu"), route("ref")

    @jax.jit
    def compare(out_k, out_r):
        (wk, mk, vk), (wr, mr, vr) = out_k, out_r
        res = {}
        for name, a, b in (("m", mk, mr), ("v", vk, vr)):
            ca = ref.unpack_codes(a.codes.reshape(-1, a.codes.shape[-1]))
            cb = ref.unpack_codes(b.codes.reshape(-1, b.codes.shape[-1]))
            d = jnp.abs(ca.astype(jnp.int32) - cb.astype(jnp.int32))
            res[name + "_flips"] = jnp.sum(d != 0)
            res[name + "_max_step"] = jnp.max(d)
            res[name + "_scale_rel"] = jnp.max(jnp.stack([
                jnp.max(jnp.abs(x - y) / jnp.abs(y)) for x, y in zip(a.scales, b.scales)
            ]))
        res["param_rel"] = jnp.max(jnp.abs(wk - wr)) / jnp.max(jnp.abs(wr))
        return res

    for shape in ((D, F), (8, D, F)):
        n = math.prod(shape)
        for sr in (False, True):
            p, g, m, v = _parity_leaf(shape, sr, seed)
            key = jax.random.PRNGKey(seed + 1) if sr else None
            out_k, t_k = _timed(kernel, p, g, m, v, key)
            out_r, t_r = _timed(oracle, p, g, m, v, key)
            res = {k: float(x) for k, x in compare(out_k, out_r).items()}
            del p, g, m, v, out_k, out_r
            mode = "sr" if sr else "rtn"
            log(f"phase 2 parity {shape} {mode}: code flips m {int(res['m_flips'])} "
                f"v {int(res['v_flips'])} of {n} (max step m "
                f"{int(res['m_max_step'])} v {int(res['v_max_step'])}); max rel "
                f"m scale {res['m_scale_rel']:.3g} v scale {res['v_scale_rel']:.3g} "
                f"param {res['param_rel']:.3g}; wall kernel {t_k * 1e3:.2f} ms "
                f"oracle {t_r * 1e3:.2f} ms")
            for name in ("m", "v"):
                if res[f"{name}_max_step"] > 1:
                    raise SystemExit(f"phase 2: {name} codes differ by more than one step")
                if res[f"{name}_flips"] > CODE_FLIP_FRACTION * n:
                    raise SystemExit(f"phase 2: {name} codes flipped in >= 1e-4 of elements")
            for key_ in ("m_scale_rel", "v_scale_rel", "param_rel"):
                if not res[key_] <= FLOAT_RTOL:
                    raise SystemExit(f"phase 2: {key_} {res[key_]} above {FLOAT_RTOL}")


# ---------------------------------------------------------------------------
# phase 3: training (and the four-chip path)
# ---------------------------------------------------------------------------


def _route_split(opt, state):
    """(fused, 4-bit jnp, fp32 jnp) as (leaves, params) pairs, read from the
    traced optimizer update: each fused leaf is one pallas_call."""
    import jax

    from repro.core.quantizer import QuantizedTensor
    from repro.kernels import ops

    grads = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
    jaxpr = jax.make_jaxpr(
        lambda g, s, p, k: opt.update(g, s, p, key=k)
    )(grads, state.opt_state, state.params, state.key)
    fused = [e.outvars[0].aval.size for e in ops.pallas_calls(jaxpr)]
    four_bit = [
        math.prod(q.shape)
        for q in jax.tree_util.tree_leaves(
            state.opt_state, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )
        if isinstance(q, QuantizedTensor) and q.config.normalization == "blockwise"
    ]
    params = [x.size for x in jax.tree_util.tree_leaves(state.params)]
    return (
        (len(fused), sum(fused)),
        (len(four_bit) - len(fused), sum(four_bit) - sum(fused)),
        (len(params) - len(four_bit), sum(params) - sum(four_bit)),
    )


def train(cfg, seed, *, steps, batch, mesh=None, comms=None, report=True):
    """``steps`` production4bit SR steps; returns the losses."""
    import jax
    import jax.numpy as jnp

    from repro.core.optimizers import linear_warmup_linear_decay, make_optimizer
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.train import compile_step, init_or_restore, run_steps

    opt = make_optimizer(
        "production4bit", linear_warmup_linear_decay(LR, 1, steps)
    )
    state, axes, start = init_or_restore(
        cfg, opt, key=jax.random.PRNGKey(seed), mesh=mesh
    )
    data = SyntheticLM(DataConfig(cfg.vocab_size, SEQ, batch, seed=seed))
    sample = {k: jnp.asarray(v) for k, v in data.batch_at(start).items()}
    t0 = time.perf_counter()
    step = compile_step(cfg, opt, state, axes, sample, mesh=mesh, comms=comms)
    log(f"  compiled the train step in {time.perf_counter() - t0:.1f} s")
    n_kernels = step.as_text().count('custom_call_target="tpu_custom_call"')
    if n_kernels == 0:
        raise SystemExit("phase 3: no tpu_custom_call in the compiled train step")
    ma = step.memory_analysis()
    log(f"  compiled step: {n_kernels} tpu_custom_call; memory_analysis "
        f"argument {ma.argument_size_in_bytes} temp {ma.temp_size_in_bytes} "
        f"output {ma.output_size_in_bytes} alias {ma.alias_size_in_bytes} bytes")
    if report:
        (fk, fp), (jk, jp), (ok, op) = _route_split(opt, state)
        log(f"  optimizer route: fused kernel {fk} leaves / {fp} params; "
            f"jnp 4-bit {jk} leaves / {jp} params; jnp fp32 {ok} leaves / {op} params")

    state, history = run_steps(step, state, data, start, steps, log_every=0)
    del state, step
    gc.collect()
    for t, loss, dt in history:
        log(f"  step {t} loss {loss:.4f} wall {dt * 1e3:.1f} ms")
    after = sorted(dt for _, _, dt in history[1:])
    log(f"  median step wall after the first: {after[len(after) // 2] * 1e3:.1f} ms "
        "(host clock to block_until_ready; uncalibrated)")
    losses = [loss for _, loss, _ in history]
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"phase 3: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise SystemExit(f"phase 3: loss did not fall ({losses[0]} -> {losses[-1]})")
    return losses


def peak_bytes(devices):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


# ---------------------------------------------------------------------------
# phase 4: serving
# ---------------------------------------------------------------------------


def serving(cfg, seed):
    import jax

    from repro.launch.serve import make_requests, serve
    from repro.models import init_model

    params = jax.jit(lambda: init_model(jax.random.PRNGKey(seed), cfg)[0])()
    new_tokens = 16
    for weights in ("bf16", "q4"):
        reqs = make_requests(
            2, cfg.vocab_size, prompt_len=16, max_new_tokens=new_tokens, seed=seed
        ) + make_requests(
            2, cfg.vocab_size, prompt_len=16, max_new_tokens=new_tokens,
            temperature=0.8, top_k=40, seed=seed + 1, rid0=2,
        )
        eng, wall = serve(cfg, params, reqs, max_batch=4, weights=weights, seed=seed)
        served = sum(len(r.output) for r in reqs)
        for r in reqs:
            ended = (r.eos_id is not None and r.output[-1:] == [r.eos_id]) or len(
                r.output
            ) == r.max_new_tokens
            if not (r.done and ended):
                raise SystemExit(f"phase 4: {weights} request {r.rid} did not finish")
        log(f"phase 4 serving {weights}: {len(reqs)} requests (2 greedy, 2 sampled) "
            f"finished, {served} tokens in {wall:.2f} s wall incl. compile; "
            f"weights {eng.weight_bytes()['total_serve_bytes']} bytes")
        del eng
        gc.collect()


# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = check_device(args.chips)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config("internlm2-1.8b")

    if args.chips == 1:
        kernel_parity(cfg, args.seed)
        cut = dataclasses.replace(
            cfg, num_layers=LAYERS_ONE_CHIP, blocks=cfg.blocks[:LAYERS_ONE_CHIP]
        )
        log(f"phase 3 training: {cut.name} cut to {cut.num_layers} of "
            f"{cfg.num_layers} layers, batch {BATCH} x {SEQ}, {TRAIN_STEPS} steps")
        train(cut, args.seed, steps=TRAIN_STEPS, batch=BATCH)
        peak, limit = peak_bytes(devices)[0], devices[0].memory_stats()["bytes_limit"]
        log(f"  peak_bytes_in_use {peak} of bytes_limit {limit}")
        serving(cut, args.seed)
    else:
        from repro.comms import CommsConfig
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2), ("data", "model"))
        batch = 2 * BATCH
        last = {}
        for mode in ("fp32", "int4"):
            log(f"four-chip training: {cfg.name}, all {cfg.num_layers} layers, "
                f"ZeRO on (data=2, model=2), batch {batch} x {SEQ}, "
                f"{MESH_STEPS} steps, --grad-comm {mode}")
            losses = train(
                cfg, args.seed, steps=MESH_STEPS, batch=batch, mesh=mesh,
                comms=CommsConfig.parse(mode), report=mode == "fp32",
            )
            last[mode] = losses[-1]
            log(f"  peak_bytes_in_use per device {peak_bytes(devices)}")
        gap = abs(last["int4"] - last["fp32"])
        log(f"final loss fp32 {last['fp32']:.4f} int4 {last['int4']:.4f} "
            f"gap {gap:.4f} nats")
        if not gap <= COMMS_LOSS_TOL:
            raise SystemExit(f"int4 loss is {gap} nats from fp32's")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
