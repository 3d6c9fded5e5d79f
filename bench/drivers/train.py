"""Training cells: the program's own step, driven from the seed.

Set-up builds one object, the compiled train step (``launch.train.
compile_step``) with its state, from weights drawn from the seed, and drives
it through ``launch.train.run_steps`` for the traffic's ``checked_steps``
steps.  Its loss at each step, the first gradient as its optimizer state
holds it after one step, and each weight's change over those steps are
read on the way.  The window then keeps calling ``run_steps`` on the same
object for ``--seconds`` (``--trace 1``: for ``trace_steps`` steps under the
profiler).  After the window the program is freed and the reference
(``bench.reference``) takes the same steps from the same weights and
batches; ``bench.check`` compares the two.
"""

from __future__ import annotations

import contextlib
import gc
import math
import tempfile
import time

import jax
import jax.numpy as jnp

from bench import check, device, flops, program, trace as trace_lib
from bench.generate import TokenFeed
from bench.harness import Outcome
from bench.program import check_tree, keys, program_config, to_program
from bench.reference.model import LAYER_LEAVES, init_leaf, init_params
from bench.reference.train import Readings, Reference, batches_from, host, leaf_norms

def _leaf_name(path) -> str:
    return str(path[-1].key)


def moment_norms(opt_state, scale: float):
    """{leaf id: norm} of the first moments in the program's optimizer
    state, times ``scale``; 4-bit moments are read back through the
    program's own decompression."""
    from repro.core.quantizer import QuantizedTensor, dequantize

    parts = opt_state.states
    parts = [parts[k] for k in sorted(parts)] if isinstance(parts, dict) else [opt_state]
    out = {}
    for part in parts:
        tree = part["m"]
        flat, _ = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, QuantizedTensor)
        )
        for path, m in flat:
            name = _leaf_name(path)
            x = dequantize(m) if isinstance(m, QuantizedTensor) else m
            out.update(leaf_norms(name, x * scale, name in LAYER_LEAVES))
    return out


def change_norms(params, key, c: dict):
    """{leaf id: norm of the weight's change since the seed's weights}."""
    from bench.reference.model import leaf_shapes

    shapes = leaf_shapes(c)
    index = {n: i for i, n in enumerate(shapes)}
    out = {}
    for path, p in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = _leaf_name(path)
        p0 = init_leaf(key, index[name], name, shapes[name])
        out.update(leaf_norms(name, p - p0, name in LAYER_LEAVES))
    return out


class Feed(TokenFeed):
    """The token feed, with a host span around each batch it makes."""

    def batch_at(self, step: int):
        with jax.profiler.TraceAnnotation("bench.batch"):
            return super().batch_at(step)


def build(cell, seed: int):
    """(program config, optimizer, init fn, feed) for ``cell`` and ``seed``."""
    from repro.core.optimizers import make_optimizer
    from repro.core.optimizers.schedule import constant
    from repro.train.train_loop import make_train_state

    c, tr = cell.config, cell.traffic
    cfg = program_config(c)
    hp = tr["hyper"]
    opt = make_optimizer(
        tr["optimizer"], constant(hp["lr"]), b1=hp["b1"], b2=hp["b2"],
        eps=hp["eps"], weight_decay=hp["weight_decay"],
    )

    def init(wkey, skey):
        return make_train_state(to_program(init_params(wkey, c)), opt, key=skey)

    feed = Feed(seed, c["vocab_size"], tr["batch"], tr["seq_len"],
                tr["data"]["zipf_exponent"], tr["data"]["copy_prob"])
    # The zero moments would be folded as constants at compile time, which
    # takes minutes at these sizes; nothing is gained by folding them.
    init = jax.jit(init, compiler_options={"xla_disable_hlo_passes": "constant_folding"})
    return cfg, opt, init, feed


def checked_steps(step, state, feed, n: int, wkey, c: dict, b1: float):
    """Run the first ``n`` steps through ``run_steps``; (state, readings)."""
    from repro.launch.train import run_steps

    state, first = run_steps(step, state, feed, 0, 1, log_every=0)
    grad = host(jax.jit(lambda s: moment_norms(s, 1.0 / (1.0 - b1)))(state.opt_state))
    state, rest = run_steps(step, state, feed, 1, n, log_every=0)
    change = host(jax.jit(lambda p, k: change_norms(p, k, c))(state.params, wkey))
    return state, Readings([loss for _, loss, _ in first + rest], grad, change)


def reference_readings(cell, seed: int, feed, *, fmt=None, label_rows=None) -> Readings:
    """The reference's readings for ``seed``; ``fmt`` and ``label_rows``
    make it the control or the half-batch fault."""
    tr = cell.traffic
    wkey = keys(seed)[0]
    if tr["optimizer"] != "adamw32":
        raise ValueError(f"the reference takes float32 AdamW steps, not {tr['optimizer']!r}")
    ref = Reference(cell.config, tr["hyper"], fmt=fmt,
                    row_blocks=cell.config["reference_row_blocks"])
    n = tr["checked_steps"]
    return ref.run(wkey, batches_from(feed, n, label_rows), n)


def run(cell, seed: int, seconds: float, trace: bool, devs, t0: float) -> Outcome:
    from repro.launch.train import compile_step, run_steps

    program.setup_compile_cache()
    c, tr = cell.config, cell.traffic
    n_checked = tr["checked_steps"]
    cfg, opt, init, feed = build(cell, seed)
    wkey, skey = keys(seed)
    state = init(wkey, skey)
    check_tree(cfg, state.params)
    sample = {k: jnp.asarray(v) for k, v in feed.batch_at(0).items()}
    step = compile_step(cfg, opt, state, None, sample)
    state, readings = checked_steps(step, state, feed, n_checked, wkey, c, tr["hyper"]["b1"])

    losses = []
    t, start = n_checked, time.perf_counter()
    setup_s = start - t0
    tracer = (trace_lib.Recorder(tempfile.mkdtemp(prefix="bench-trace-"))
              if trace else contextlib.nullcontext())
    with tracer:
        while True:
            with jax.profiler.TraceAnnotation("bench.run_steps"):
                state, hist = run_steps(step, state, feed, t, t + 1, log_every=0)
            t += 1
            losses.append(hist[0][1])
            elapsed = time.perf_counter() - start
            if (t - n_checked >= tr["trace_steps"]) if trace else (elapsed >= seconds):
                break
    window_s = time.perf_counter() - start
    steps = t - n_checked
    peak = device.peak_bytes(devs)
    del state, step
    gc.collect()

    ref = reference_readings(cell, seed, feed)
    values = check.compare(ref, readings)
    correct, compared = check.judge(values, cell.limits)
    failed = sum(not math.isfinite(x) for x in losses)
    tokens = steps * tr["batch"] * tr["seq_len"]
    return Outcome(
        correct=correct and failed == 0,
        attempted=steps,
        failed=failed,
        end_to_end={
            "setup_s": setup_s,
            "train_tokens_per_s": tokens / window_s,
            "peak_hbm_gib": peak / 2**30,
        },
        compared=compared,
        memory_peak_bytes=peak,
        counts={
            "steps": steps,
            "tokens_per_step": tr["batch"] * tr["seq_len"],
            "model_flops_per_step": flops.train_flops_per_step(c, tr["batch"], tr["seq_len"]),
        },
        trace=tracer.summary() if trace else None,
    )
