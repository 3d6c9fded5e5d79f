import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede any jax import: jax locks the device count
# on first init. 512 host devices back the 2x16x16 multi-pod mesh.
if os.environ.get("REPRO_DRYRUN_DEVICES"):  # test hook (small meshes)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware: jit with explicit shardings must lower, SPMD-partition, and compile
for the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh; we record
memory_analysis (fits / doesn't), cost_analysis (FLOPs & bytes for
§Roofline), and the collective schedule parsed from the optimized HLO.

Usage:
    python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cell_is_runnable, get_config
from repro.core.optimizers import make_optimizer
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import decode_cache_len, input_specs
from repro.models import ModelConfig, decode_step, init_model, loss_fn, prefill
from repro.roofline.analysis import (
    collective_bytes_from_hlo,
    model_flops,
    roofline_terms,
)
from repro.sharding import batch_shardings, cache_shardings, param_shardings, replicated
from repro.train.train_loop import (
    TrainState,
    build_train_step,
    make_train_state,
    train_state_shardings,
)


def _param_shapes_and_axes(cfg: ModelConfig):
    params_s, axes = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    # eval_shape maps the axes tuples through too — rebuild them concretely
    _, axes = init_model_axes(cfg)
    return params_s, axes


def init_model_axes(cfg: ModelConfig):
    """Axes tree without allocating params (init under eval_shape, axes via
    a real tiny trace of the same structure)."""
    # axes are pure python metadata — build by running init at shape level
    closure = {}

    def capture():
        p, a = init_model(jax.random.PRNGKey(0), cfg)
        closure["axes"] = a
        return p

    params_s = jax.eval_shape(capture)
    return params_s, closure["axes"]


def lower_cell(arch: str, shape_name: str, mesh_kind: str, opt_name: str = "adamw4bit",
               accum_steps: int = 8):
    """Lower + compile one cell; returns the result record.

    Train cells default to 8-way gradient accumulation: at global batch 256
    x 4k tokens the per-layer remat residuals alone are ~16 GB/device on the
    single-pod mesh — microbatching is the standard way production runs fit
    v5e HBM (recorded in EXPERIMENTS.md §Dry-run)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if os.environ.get("REPRO_ACCUM"):
        accum_steps = int(os.environ["REPRO_ACCUM"])
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size

    params_s, axes = init_model_axes(cfg)
    if shape.kind != "train":
        # serving uses bf16 weights (no fp32 masters outside training)
        params_s = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), params_s
        )
    specs = input_specs(cfg, shape)

    t0 = time.time()
    if shape.kind == "train":
        opt = make_optimizer(opt_name, 1e-4)
        # Always thread an SR key: proves the stochastic-rounding production
        # path (adamw4bit+SR / production4bit) lowers and SPMD-partitions;
        # deterministic optimizers simply ignore it.
        sr_key = jax.random.PRNGKey(0)
        state_s = jax.eval_shape(
            lambda: make_train_state_from_shapes(params_s, opt, key=sr_key)
        )
        from repro.comms import CommsConfig
        # REPRO_GRAD_COMM selects the gradient-collective wire format
        # (fp32/bf16/int8/int4).
        comm_mode = os.environ.get("REPRO_GRAD_COMM", "fp32")
        step_fn = build_train_step(cfg, opt, mesh, axes, zero=True,
                                   accum_steps=accum_steps,
                                   comms=CommsConfig.parse(comm_mode))
        state_sh = train_state_shardings(state_s, axes, mesh, zero=True)
        batch_sh = batch_shardings(specs, mesh)
        with mesh:
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state_s, specs)
            compiled = lowered.compile()
    elif shape.kind == "prefill":
        p_sh = param_shardings(params_s, axes, mesh)
        batch_sh = batch_shardings(specs, mesh)

        def prefill_fn(params, batch):
            return prefill(params, cfg, batch)

        with mesh:
            lowered = jax.jit(
                prefill_fn, in_shardings=(p_sh, batch_sh)
            ).lower(params_s, specs)
            compiled = lowered.compile()
    else:  # decode
        p_sh = param_shardings(params_s, axes, mesh)
        cache_sh = cache_shardings(specs["caches"], mesh)
        tok_sh = batch_shardings(
            {"tokens": specs["tokens"], "pos": specs["pos"]}, mesh
        )
        enc_specs = specs.get("enc_out")

        if enc_specs is not None:
            enc_sh = batch_shardings({"e": enc_specs}, mesh)["e"]

            def decode_fn(params, caches, tokens, pos, enc_out):
                return decode_step(params, cfg, caches, tokens, pos, enc_out=enc_out)

            in_sh = (p_sh, cache_sh, tok_sh["tokens"], tok_sh["pos"], enc_sh)
            args = (params_s, specs["caches"], specs["tokens"], specs["pos"], enc_specs)
        else:

            def decode_fn(params, caches, tokens, pos):
                return decode_step(params, cfg, caches, tokens, pos)

            in_sh = (p_sh, cache_sh, tok_sh["tokens"], tok_sh["pos"])
            args = (params_s, specs["caches"], specs["tokens"], specs["pos"])

        with mesh:
            lowered = jax.jit(
                decode_fn,
                in_shardings=in_sh,
                out_shardings=(None, cache_sh),
                donate_argnums=(1,),
            ).lower(*args)
            compiled = lowered.compile()

    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    print(mem)  # proves it fits
    cost = compiled.cost_analysis()
    print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)

    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mflops = model_flops(cfg, params_s, axes, shape.kind, tokens)
    terms = roofline_terms(cost, coll["total"], n_chips, mflops)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "n_chips": n_chips,
        "accum_steps": accum_steps if shape.kind == "train" else None,
        "status": "ok",
        "compile_s": round(compile_s, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "collectives": coll,
        "roofline": terms.as_dict(),
    }
    return record


def make_train_state_from_shapes(params_s, opt, key=None):
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), params_s
    )
    return make_train_state(params, opt, key=key)


def run_all(out_path: str, meshes=("single", "multi"), archs=None, shapes=None):
    results = []
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}

    for arch in archs or ARCHS:
        for shape_name in shapes or SHAPES:
            runnable, reason = cell_is_runnable(arch, shape_name)
            for mesh_kind in meshes:
                key = (arch, shape_name, mesh_kind)
                if key in done:
                    continue
                if not runnable:
                    results.append({
                        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
                        "status": "skipped", "reason": reason,
                    })
                    continue
                print(f"=== {arch} x {shape_name} x {mesh_kind} ===", flush=True)
                try:
                    rec = lower_cell(arch, shape_name, mesh_kind)
                except Exception as e:  # record the failure, keep going
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                    print(rec["error"], flush=True)
                results.append(rec)
                os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
                json.dump(results, open(out_path, "w"), indent=1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--opt", default="adamw4bit",
                    help="optimizer for train cells (e.g. production4bit)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    if args.all:
        run_all(args.out)
        return

    rec = lower_cell(args.arch, args.shape, args.mesh, opt_name=args.opt)
    print(json.dumps(rec, indent=1, default=str))


if __name__ == "__main__":
    main()
