"""Regression tests for the training CLI's elastic-resume path.

The old restore built its target as ``jax.eval_shape(lambda: state)`` — which
requires a fully *allocated* ``state`` to close over, so a resuming process
paid for the model twice (fresh init + restored copy).  The fixed path
(``abstract_train_state``) runs the entire init under ``eval_shape``: every
target leaf is a ShapeDtypeStruct and restore allocates exactly one copy.
"""

import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.optimizers import make_optimizer
from repro.launch.train import abstract_train_state
from repro.train.checkpoint import restore_checkpoint, save_checkpoint
from repro.train.train_loop import make_train_state

jax.config.update("jax_platform_name", "cpu")


def test_abstract_train_state_allocates_nothing():
    cfg = reduced_config("internlm2-1.8b")
    opt = make_optimizer("production4bit", 1e-3)
    target, axes = abstract_train_state(cfg, opt, key=jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(target)
    assert leaves, "abstract state is empty"
    for l in leaves:
        assert isinstance(l, jax.ShapeDtypeStruct), type(l)
    assert isinstance(axes, dict) and "embed" in axes


def test_abstract_target_restores_real_checkpoint(tmp_path):
    cfg = reduced_config("internlm2-1.8b")
    opt = make_optimizer("adamw4bit", 1e-3)
    from repro.models import init_model

    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(3)
    state = make_train_state(params, opt, key=key)
    d = str(tmp_path / "c")
    save_checkpoint(d, 1, state)

    target, _ = abstract_train_state(cfg, opt, key=key)
    restored, _ = restore_checkpoint(d, target)
    for a, b in zip(
        jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_cli_train_checkpoint_resume(tmp_path):
    """The CLI end-to-end: train 4 steps with checkpoints, rerun to 8 steps
    — the second process must resume (not restart) and finish cleanly."""
    d = str(tmp_path / "ckpt")
    cmd = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "internlm2-1.8b", "--reduced",
        "--optimizer", "production4bit", "--sr-seed", "0",
        "--batch", "4", "--seq", "32", "--ckpt-dir", d, "--ckpt-every", "2",
    ]
    repo_root = str(pathlib.Path(__file__).resolve().parents[1])
    env = {"PYTHONPATH": str(pathlib.Path(repo_root) / "src"),
           "PATH": "/usr/bin:/bin:/usr/local/bin",
           "JAX_PLATFORMS": "cpu",
           # keep the CLI's compile cache out of the checkout
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    r1 = subprocess.run(cmd + ["--steps", "4"], capture_output=True, text=True,
                        env=env, cwd=repo_root, timeout=420)
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(cmd + ["--steps", "8"], capture_output=True, text=True,
                        env=env, cwd=repo_root, timeout=420)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 4" in r2.stdout, r2.stdout[-2000:]
