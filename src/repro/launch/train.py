"""Training CLI: --arch <id> [--reduced] --steps N.

Full configs run on TPU chips (``chip_smoke.py`` drives this module's
functions at a published width; dryrun.py compiles the production meshes);
--reduced runs the same code path at CPU scale end-to-end (data pipeline ->
sharded step -> 4-bit optimizer -> checkpoints).

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced --steps 30

Production-path flags:
  --optimizer production4bit   fp32 embeddings/norms + 4-bit SR body
  --sr-seed N                  thread a stochastic-rounding PRNG key through
                               the train step (unbiased quantization, Alg. 1)
  --grad-comm MODE             gradient-collective wire format
                               (fp32|bf16|int8|int4): int8/int4 move
                               block-quantized codes+scales through the
                               cross-device reduction instead of fp32, with
                               SR keyed off the --sr-seed stream (unbiased
                               transport, bit-reproducible across resume);
                               replaces the removed grad_dtype plumbing
  --mesh DxM                   run on a (data=D, model=M) device mesh via
                               jit_train_step with explicit shardings
  --ckpt-dir PATH              resume is elastic: the restore target is built
                               abstractly (jax.eval_shape over
                               make_train_state — no throwaway concrete init,
                               so restore never doubles device memory) and
                               re-sharded onto the current mesh.  Saves use
                               the sharded v2 format (repro.io): per-host
                               shard files written on a background thread,
                               COMMIT-marker atomicity.
  --keep-last N / --keep-every K
                               retention: keep the newest N complete steps
                               plus every K-th step; superseded dirs are
                               GC'd after each successful commit.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.comms import GRAD_COMM_MODES, CommsConfig, wire_report
from repro.configs import ARCHS, get_config, reduced_config
from repro.core.optimizers import (
    linear_warmup_linear_decay,
    make_optimizer,
    optimizer_names,
    state_nbytes,
)
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.train.checkpoint import CheckpointManager
from repro.train.train_loop import (
    build_train_step,
    jit_train_step,
    make_train_state,
    train_state_shardings,
)


def _parse_value(v: str):
    """--opt-arg value: bool words, then any Python literal (1e-8, -0.5, 3),
    falling back to the raw string."""
    import ast

    low = v.lower()
    if low in ("true", "false"):
        return low == "true"
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def _uses_stochastic_rounding(opt_state) -> bool:
    from repro.core.quantizer import QuantizedTensor

    return any(
        l.config.stochastic_rounding
        for l in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )
        if isinstance(l, QuantizedTensor)
    )


def _build_state(cfg, optimizer, key, captured):
    params, axes = init_model(jax.random.PRNGKey(0), cfg)
    captured["axes"] = axes
    return make_train_state(params, optimizer, key=key)


def abstract_train_state(cfg, optimizer, key=None):
    """(abstract TrainState, axes) without allocating a single param.

    The whole init (model params -> optimizer state -> TrainState) runs under
    ``jax.eval_shape``, so every leaf is a ShapeDtypeStruct.  This is the
    restore target: the old ``jax.eval_shape(lambda: state)`` idiom required
    a *concrete* state to already exist, which meant a resuming process
    allocated the full model twice (fresh init + restored copy) before the
    first could be dropped.
    """
    captured = {}
    state_s = jax.eval_shape(lambda: _build_state(cfg, optimizer, key, captured))
    return state_s, captured["axes"]


def init_or_restore(cfg, optimizer, *, key=None, mesh=None, mgr=None):
    """(state, axes, start step) for a run.

    Resumes from ``mgr``'s newest COMMIT-complete step (a save killed
    mid-write is skipped) when there is one, else initializes.  Either way
    the state is built straight into its final layout: on a mesh every leaf
    lands in its ZeRO shard (``train_state_shardings``), so no device ever
    holds the whole model — the restore is elastic onto the current mesh, and
    a fresh init runs as one jitted program whose outputs are sharded.
    """
    target, axes = abstract_train_state(cfg, optimizer, key=key)
    shardings = (
        train_state_shardings(target, axes, mesh) if mesh is not None else None
    )
    start = (mgr.latest_step() or 0) if mgr else 0
    if start:
        state, _ = mgr.restore(target, shardings=shardings)
    else:
        state = jax.jit(
            lambda: _build_state(cfg, optimizer, key, {}), out_shardings=shardings
        )()
    return state, axes, start


def compile_step(cfg, optimizer, state, axes, sample, *, mesh=None, comms=None):
    """The train step compiled ahead of time for ``state`` and ``sample``.

    On a mesh: ``jit_train_step`` with explicit ZeRO shardings; otherwise a
    single-device jit.  Both donate the state.  The returned executable is
    what the loop runs, so its ``as_text()`` / ``memory_analysis()`` describe
    the program that trains.
    """
    if mesh is not None:
        step = jit_train_step(
            build_train_step(cfg, optimizer, mesh, axes, zero=True, comms=comms),
            state, sample, axes, mesh,
        )
    else:
        step = jax.jit(
            build_train_step(cfg, optimizer, comms=comms), donate_argnums=(0,)
        )
    return step.lower(state, sample).compile()


def run_steps(step, state, data, start, steps, *, mgr=None, ckpt_every=25,
              log_every=5):
    """Train from ``start`` to ``steps``; returns (state, history).

    ``history`` holds one ``(step, loss, seconds)`` per step, where seconds
    is host wall time from dispatch until the new state is ready on device
    (``block_until_ready``), the batch's host-side creation excluded.
    """
    history = []
    for t in range(start, steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(t).items()}
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t0
        history.append((t, float(metrics["loss"]), dt))
        if mgr and (t + 1) % ckpt_every == 0:
            mgr.save(t + 1, state)
        if log_every and t % log_every == 0:
            print(f"step {t:4d} loss {history[-1][1]:.4f} ({dt * 1e3:.0f} ms)")
    if mgr:
        mgr.wait()
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale config of the same family")
    ap.add_argument("--optimizer", default="adamw4bit",
                    choices=list(optimizer_names()))
    ap.add_argument("--opt-arg", action="append", default=[],
                    metavar="K=V",
                    help="optimizer override, e.g. --opt-arg use_kernel=true "
                         "(validated by make_optimizer)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sr-seed", type=int, default=None,
                    help="seed for the stochastic-rounding PRNG key stream "
                         "(required for unbiased SR; omit for deterministic "
                         "round-to-nearest)")
    ap.add_argument("--grad-comm", default="fp32",
                    choices=list(GRAD_COMM_MODES),
                    help="gradient-collective wire format; int8/int4 "
                         "block-quantize the cross-device reduction "
                         "(docs/comms.md)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="host-device mesh, e.g. 2x4 (data=2, model=4); "
                         "needs D*M local devices")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--keep-last", type=int, default=3,
                    help="retention: keep the newest N complete checkpoints "
                         "(superseded step dirs are GC'd after each commit)")
    ap.add_argument("--keep-every", type=int, default=None,
                    help="retention: additionally keep every K-th step as a "
                         "periodic archival anchor")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if not args.reduced and jax.default_backend() == "cpu":
        print("note: full config on CPU — expect long compiles; "
              "use --reduced or launch/dryrun.py for the mesh path")
    if cfg.input_mode == "embeds" or cfg.family == "encdec":
        raise SystemExit(
            f"{args.arch}: modality-stub arch — use examples/ or the dry-run"
        )

    for kv in args.opt_arg:
        if "=" not in kv:
            raise SystemExit(
                f"--opt-arg {kv!r}: expected K=V (e.g. use_kernel=true)"
            )
    overrides = {k: _parse_value(v) for k, _, v in
                 (kv.partition("=") for kv in args.opt_arg)}
    opt = make_optimizer(
        args.optimizer,
        linear_warmup_linear_decay(args.lr, max(1, args.steps // 10), args.steps),
        **overrides,
    )
    sr_key = (
        jax.random.PRNGKey(args.sr_seed) if args.sr_seed is not None else None
    )

    mesh = None
    if args.mesh:
        d, _, m = args.mesh.partition("x")
        mesh = make_mesh((int(d), int(m)), ("data", "model"))

    mgr = (
        CheckpointManager(
            args.ckpt_dir, keep_last=args.keep_last, keep_every=args.keep_every
        )
        if args.ckpt_dir
        else None
    )
    state, axes, start = init_or_restore(cfg, opt, key=sr_key, mesh=mesh, mgr=mgr)
    if start:
        print(f"resumed from step {start}")
    print(f"arch={cfg.name} optimizer={opt.name} "
          f"state_bytes={state_nbytes(state.opt_state):,}")

    comms = CommsConfig.parse(args.grad_comm)
    rep = wire_report(state.params, comms)
    print(f"grad-comm={comms.name} collective_bytes/step="
          f"{rep['total_wire_bytes']:,} "
          f"({rep['ratio_vs_fp32']:.2f}x fewer than fp32, "
          f"{rep['quantized_leaves']}/{rep['n_leaves']} leaves quantized)")

    if sr_key is None and _uses_stochastic_rounding(state.opt_state):
        print("warning: optimizer is configured for stochastic rounding but "
              "no --sr-seed was given — quantization falls back to biased "
              "round-to-nearest")
    if sr_key is None and comms.quantized and comms.stochastic_rounding:
        print("warning: --grad-comm " + comms.mode + " transports gradients "
              "with stochastic rounding but no --sr-seed was given — "
              "transport falls back to biased round-to-nearest")

    data = SyntheticLM(DataConfig(cfg.vocab_size, args.seq, args.batch))
    sample = {k: jnp.asarray(v) for k, v in data.batch_at(start).items()}
    step = compile_step(cfg, opt, state, axes, sample, mesh=mesh, comms=comms)
    run_steps(step, state, data, start, args.steps, mgr=mgr,
              ckpt_every=args.ckpt_every)


if __name__ == "__main__":
    main()
