"""A cell added from new files alone runs end to end on the CPU, and
``correct`` comes out false when the path it times is broken underneath.

The harness's look for a chip is skipped (the devices are handed in);
everything after it runs as on the chip.  The cells and their limits are
``bench_cells.py``'s.
"""

import json

import jax
import pytest

from bench import harness
from bench.drivers import serve_closed as sc
from bench_cells import CONTROL, LIMITS, ROOT, SEED, TINY

RUN = harness.load_module(ROOT / "bench" / "run.py")


pytestmark = pytest.mark.usefixtures("no_compile_cache")


def run(root, name, seed=SEED):
    cell = harness.load_cell(root, name)
    return RUN.run_cell(cell, seed, 1.0, False, devices=jax.devices()[:1])


def test_serve_cell_from_new_files(root):
    res = run(root, "serve.tiny")
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"setup_s", "serve_tokens_per_s", "tpot_p95_ms"}
    assert res["metrics"]["tpot_p95_ms"]["value"] > 0
    assert res["attempted"] >= 4 and res["failed"] == 0


def test_serve_token_altered_where_produced_is_not_correct(root, monkeypatch):
    from repro.serve import engine

    real = engine.ServeEngine.step
    altered = set()

    def step(self):
        alive = real(self)
        for r in self.active:
            if r is not None and r.temperature <= 0 and r.rid not in altered and len(r.output) > 2:
                r.output[1] = (r.output[1] + 1) % TINY["vocab_size"]
                altered.add(r.rid)
        return alive

    monkeypatch.setattr(engine.ServeEngine, "step", step)
    res = run(root, "serve.tiny")
    assert altered and res["correct"] is False
    assert res["compared"]["served_gap"]["value"] > LIMITS["serve.tiny"]["served_gap"]


def test_serve_control_fails_the_limit(root):
    import numpy as np

    from bench.generate import request_specs

    cell = harness.load_cell(root, "serve.tiny")
    tr = cell.traffic
    eng = sc.build_engine(cell, SEED)
    loop = sc.ClosedLoop(eng, request_specs(SEED, tr["request_pool"], 512, tr), tr["clients"])
    while sum(r.temperature <= 0 for r in loop.finished) < tr["check_requests"]:
        loop.tick(resend=True)
    rng = np.random.default_rng(0)
    sample = sc.sample_finished(loop.finished, tr["check_requests"], rng)
    assert sc.served_gap(cell, SEED, sample) <= cell.limits["served_gap"]
    assert sc.served_gap(cell, SEED, sample, fmt=CONTROL) > cell.limits["served_gap"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    import os
    import subprocess
    import sys

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", spec["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
