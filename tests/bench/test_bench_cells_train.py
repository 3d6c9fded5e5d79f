"""A cell added from new files alone runs end to end on the CPU, and
``correct`` comes out false when the path it times is broken underneath.

The harness's look for a chip is skipped (the devices are handed in);
everything after it runs as on the chip.  The cells and their limits are
``bench_cells.py``'s.
"""

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.drivers import train
from bench_cells import CONTROL, LIMITS, ROOT, SEED

RUN = harness.load_module(ROOT / "bench" / "run.py")


pytestmark = pytest.mark.usefixtures("no_compile_cache")


def run(root, name, seed=SEED):
    cell = harness.load_cell(root, name)
    return RUN.run_cell(cell, seed, 1.0, False, devices=jax.devices()[:1])


def test_train_cell_from_new_files(root):
    res = run(root, "train.tiny")
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "compared"
    assert res["compared"]["change_gap"]["limit"] == LIMITS["train.tiny"]["change_gap"]


def test_train_step_that_leaves_its_state_unchanged_is_not_correct(root, monkeypatch):
    import repro.launch.train as lt

    real = lt.compile_step

    def stuck(*args, **kwargs):
        step = real(*args, **kwargs)

        def call(state, batch):
            _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch)
            return state, metrics

        return call

    monkeypatch.setattr(lt, "compile_step", stuck)
    res = run(root, "train.tiny")
    assert res["correct"] is False
    assert res["compared"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_train_step_on_half_the_batch_is_not_correct(root, monkeypatch):
    import repro.launch.train as lt

    real = lt.run_steps

    class Half:
        def __init__(self, feed):
            self.feed = feed

        def batch_at(self, t):
            b = dict(self.feed.batch_at(t))
            labels = b["labels"].copy()
            labels[labels.shape[0] // 2:] = -1
            b["labels"] = labels
            return b

    monkeypatch.setattr(lt, "run_steps", lambda step, state, data, *a, **k:
                        real(step, state, Half(data), *a, **k))
    res = run(root, "train.tiny")
    assert res["correct"] is False
    assert res["compared"]["grad_gap"]["value"] > LIMITS["train.tiny"]["grad_gap"]


def test_train_control_fails_the_limits(root):
    """The reference in the program's place, with fp8 matmuls."""
    from bench import check

    cell = harness.load_cell(root, "train.tiny")
    for seed in (SEED, SEED + 7919):
        _, _, _, feed = train.build(cell, seed)
        ref = train.reference_readings(cell, seed, feed)
        ctl = train.reference_readings(cell, seed, feed, fmt=CONTROL)
        ok, shown = check.judge(check.compare(ref, ctl), cell.limits)
        assert not ok, shown
