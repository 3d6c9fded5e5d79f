#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, compile, warm-up) is timed as ``setup_s``;
then the cell's window runs for ``--seconds``.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` traces the window and reports its
per-layer metrics.  Either way the run's output is compared with the plain
reference afterwards, and the last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``) and, last, ``compared``: each number that
decides ``correct`` beside its limit.  Without the cell's chips the run
exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import device, harness  # noqa: E402


def _number(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else str(x)


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool, *,
             devices=None, t0: float = None) -> dict:
    """Run ``cell`` and return its result object.  ``devices`` defaults to
    the cell's chips, which must be TPUs."""
    t0 = T0 if t0 is None else t0
    devs = devices if devices is not None else device.require(cell.chips)
    out = harness.driver(cell).run(cell, seed, seconds, trace, devs, t0)
    dev = device.describe(devs, out.memory_peak_bytes)
    result = {
        "correct": bool(out.correct),
        "attempted": int(out.attempted),
        "failed": int(out.failed),
    }
    if trace:
        run = harness.Run(cell, out.trace, out.counts, device.peaks(dev["kind"]))
        result["metrics"] = harness.read_per_layer(cell, run)
        dev["busy_s"] = out.trace.busy_s
        dev["window_s"] = out.trace.window_s
        result["device"] = dev
        result["breakdown"] = out.trace.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
        missing = set(units) - set(out.end_to_end)
        if missing:
            raise RuntimeError(f"the driver did not measure {sorted(missing)}")
        result["metrics"] = {
            k: {"value": out.end_to_end[k], "unit": u} for k, u in units.items()
        }
        result["device"] = dev
    result["compared"] = {
        k: {"value": _number(v["value"]), "limit": v["limit"]}
        for k, v in out.compared.items()
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    cell = harness.load_cell(ROOT, args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, v in result["compared"].items():
        print(f"compared {name} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
