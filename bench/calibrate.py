#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip:

    python3 bench/calibrate.py --workload <name> --seeds 12 [--controls 3]

Training cells, for each seed: the program's first steps (through the
cell's own compiled step and feed) against the reference's; for the first
``--controls`` seeds also the control (the reference in the program's
place with fp8 matmuls: e4m3 operands and results, e5m2 gradients) and
the half-batch fault (the reference in the program's place with the loss
over half of the rows).  Serving cells, for
each seed: the cell's closed loop on a fresh engine until ``check_requests``
greedy requests have finished, and the widest reference-logit gap of their
served tokens; for the first ``--controls`` seeds also the gap of the
tokens the control puts first, and the gap once one served token is
altered.  Prints one JSON
line per reading and writes all of them to ``--out``.  The benchmark's own
runs never run this.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_FMT = "float8_e4m3fn"


def calibrate_train(cell, args, emit):
    import jax.numpy as jnp

    from bench import check, program
    from bench.drivers import train
    from repro.launch.train import compile_step

    tr = cell.traffic
    rows = tr["batch"] // 2
    step = None

    def put(kind, seed, ref, cand, secs):
        rec = dict(check.compare(ref, cand), loss=cand.loss, ref_loss=ref.loss)
        emit(kind, seed, rec, secs)

    for i, seed in enumerate(seeds(args)):
        cfg, opt, init, feed = train.build(cell, seed)
        wkey, skey = program.keys(seed)
        state = init(wkey, skey)
        if step is None:
            sample = {k: jnp.asarray(v) for k, v in feed.batch_at(0).items()}
            step = compile_step(cfg, opt, state, None, sample)
        state, prog = train.checked_steps(
            step, state, feed, tr["checked_steps"], wkey, cell.config, tr["hyper"]["b1"]
        )
        del state
        gc.collect()
        t0 = time.perf_counter()
        ref = train.reference_readings(cell, seed, feed)
        put("program", seed, ref, prog, time.perf_counter() - t0)
        if i < args.controls:
            t0 = time.perf_counter()
            put("control", seed, ref, train.reference_readings(cell, seed, feed, fmt=CONTROL_FMT),
                time.perf_counter() - t0)
            t0 = time.perf_counter()
            put("half_batch", seed, ref, train.reference_readings(cell, seed, feed, label_rows=rows),
                time.perf_counter() - t0)


def calibrate_serve(cell, args, emit):
    import numpy as np

    from bench.drivers import serve_closed as sc
    from bench.generate import request_specs

    tr, c = cell.traffic, cell.config
    for i, seed in enumerate(seeds(args)):
        t0 = time.perf_counter()
        eng = sc.build_engine(cell, seed)
        sc.warm_up(eng, tr, c["vocab_size"])
        loop = sc.ClosedLoop(eng, request_specs(seed, tr["request_pool"], c["vocab_size"], tr),
                             tr["clients"])
        while sum(r.temperature <= 0 for r in loop.finished) < tr["check_requests"]:
            loop.tick(resend=True)
        finished = list(loop.finished)
        del loop, eng
        gc.collect()
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
        sample = sc.sample_finished(finished, tr["check_requests"], rng)
        n = sum(len(r.output) for r in sample)
        run_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        emit("program", seed, {"served_gap": sc.served_gap(cell, seed, sample),
                               "tokens": n, "run_s": run_s}, time.perf_counter() - t0)
        if i < args.controls:
            t0 = time.perf_counter()
            emit("control", seed, {"served_gap": sc.served_gap(cell, seed, sample, fmt=CONTROL_FMT),
                                   "tokens": n}, time.perf_counter() - t0)
            r = sample[0]
            j = len(r.output) // 2
            r.output[j] = (r.output[j] + 1) % c["vocab_size"]
            emit("altered_token", seed, {"served_gap": sc.served_gap(cell, seed, sample),
                                         "tokens": n}, 0.0)


def seeds(args):
    return [args.first_seed + 7919 * i for i in range(args.seeds)]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import device, harness, program

    devs = device.require(1)
    program.setup_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    records = []

    def emit(kind, seed, rec, secs):
        rec = dict(kind=kind, seed=seed, seconds=secs, **rec)
        records.append(rec)
        print(json.dumps(rec), flush=True)

    run = calibrate_serve if cell.traffic["driver"] == "serve_closed" else calibrate_train
    run(cell, args, emit)
    print(json.dumps({"peak_bytes": device.peak_bytes(devs)}))
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(records, indent=1))


if __name__ == "__main__":
    main()
