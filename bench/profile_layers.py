#!/usr/bin/env python3
"""A cell's traced run, read by the program's own scopes, spans and counters:

    python3 bench/profile_layers.py --workload <name> --seed <n> [--seed <n> ...] [--seconds 51]

For each seed the cell runs as ``bench/run.py --trace 1`` runs it (the same
driver, window and reference check), and one JSON line is printed with the
run's result and, from the same traced window (``bench.layers``):

* ``layers``: forward, backward and optimizer ms per train step; q4
  ``materialize`` and ``sample`` ms per decode call; device idle ms per
  tick under the drain and retire spans and under the admit, prefill and
  decode spans; the share of prefill positions that hold a prompt token;
* ``per_unit_ms``: the traced window per step or tick, ``device_idle``;
* ``map_share``: the share of the mapped module's op time whose ops the
  HLO map holds; ``scoped``: that time by scope, in %; ``unscoped_top``:
  its longest ops outside every scope;
* ``idle_under_spans``: the share of idle gap time under a ``repro.`` span.

The map is read from the program after the window: the train step's
compiled text, the engine's ``decode_hlo()``.  A program without scopes,
spans or that accessor reads null there.  The benchmark's own runs never
run this.
"""

import argparse
import contextlib
import glob
import json
import os
import pathlib
import shutil
import sys
import time
import weakref

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class Capture:
    def __init__(self):
        self.maps, self.layers, self.summary, self.engine = {}, None, None, None


@contextlib.contextmanager
def capturing():
    """Within it, the drivers' traced windows are also reduced by
    ``bench.layers``, and the scope map of the traced program is read."""
    import jax

    import repro.launch.train as launch_train
    import repro.serve as serve
    from bench import layers, scopes, trace

    cap = Capture()
    Recorder, compile_step, Engine = trace.Recorder, launch_train.compile_step, serve.ServeEngine

    class LayersRecorder(Recorder):
        def __exit__(self, *exc):
            window = time.perf_counter() - self._t0
            jax.profiler.stop_trace()
            try:
                if exc[0] is None:
                    self._summary = cap.summary = trace.load(self.directory, window)
                    path = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                                     recursive=True)[0]
                    cap.layers = layers.load(path)
                    eng = cap.engine() if cap.engine else None
                    if eng is not None and hasattr(eng, "decode_hlo"):
                        cap.maps[layers.DECODE_MODULE] = scopes.op_scopes(eng.decode_hlo())
            finally:
                shutil.rmtree(self.directory, ignore_errors=True)
            return False

    def mapped_compile_step(*a, **k):
        step = compile_step(*a, **k)
        cap.maps[layers.TRAIN_MODULE] = scopes.op_scopes(step.as_text())
        return step

    class TrackedEngine(Engine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            cap.engine = weakref.ref(self)

    trace.Recorder, launch_train.compile_step, serve.ServeEngine = (
        LayersRecorder, mapped_compile_step, TrackedEngine)
    try:
        yield cap
    finally:
        trace.Recorder, launch_train.compile_step, serve.ServeEngine = (
            Recorder, compile_step, Engine)


def coverage(lay, op_map, module: str, top: int = 8) -> dict:
    from bench import layers, scopes

    by = layers.scope_seconds(lay, module, op_map)
    total = sum(by.values())
    if not total:
        return {}
    unscoped = sorted(((op, s) for (m, op), s in lay.module_ops.items()
                       if m == module and op in op_map
                       and scopes.classify(op_map[op]) is None), key=lambda x: -x[1])
    return {
        "module": module,
        "map_share": 100.0 * (1 - by.get("?", 0.0) / total),
        "scoped": {str(k): 100.0 * v / total for k, v in by.items()},
        "unscoped_top": [[op, 1e3 * s, op_map[op]] for op, s in unscoped[:top]],
    }


def profile(cell, seed: int, seconds: float) -> dict:
    from bench import layers
    from bench.run import run_cell

    with capturing() as cap:
        result = run_cell(cell, seed, seconds, True, t0=time.perf_counter())
    s, lay = cap.summary, cap.layers
    tr = cell.traffic
    units = tr.get("trace_steps") or tr.get("trace_ticks")
    module = layers.TRAIN_MODULE if tr["driver"] == "train" else layers.DECODE_MODULE
    idle = sum(lay.span_idle.values())
    return {
        "workload": cell.name,
        "seed": seed,
        "correct": result["correct"],
        "failed": result["failed"],
        "compared": result["compared"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "layers": layers.readings(lay, cap.maps, s.modules),
        "per_unit_ms": 1e3 * s.window_s / units,
        "device_idle": 100.0 * (1 - s.busy_s / s.window_s),
        "modules": s.modules,
        **coverage(lay, cap.maps.get(module, {}), module),
        "idle_under_spans": 100.0 * (1 - lay.span_idle.get("no host span", 0.0) / idle) if idle else None,
        "spans": lay.spans,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    for seed in args.seed:
        print(json.dumps(profile(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
