"""Throwaway cells for the benchmark's tests: the repository's drivers and
readers with a tiny configuration, mixes and limits of their own, written
to a temporary directory."""

import json
import pathlib
import shutil

import pytest

from bench import program

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 3_000_000_000
CONTROL = "float8_e4m3fn"

TINY = {
    "name": "tiny", "program_config": "internlm2-1.8b", "source": "https://arxiv.org/abs/2403.17297",
    "num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 2, "head_dim": 64,
    "d_ff": 512, "vocab_size": 512, "rope_variant": "rope", "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_embeddings": False, "reduced": [], "assumed": [],
    "deployment": "test", "reference_row_blocks": 2,
}
TRAIN = {
    "driver": "train", "batch": 4, "seq_len": 64, "optimizer": "adamw32",
    "hyper": {"lr": 3e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.01},
    "data": {"zipf_exponent": 1.1, "copy_prob": 0.5}, "checked_steps": 3, "trace_steps": 2,
}
SERVE = {
    "driver": "serve_closed", "weights": "q4", "clients": 4, "s_max": 256, "drain_every": 8,
    "prompt_len": [16, 64], "max_new_tokens": [24, 48], "temperature": 0.8, "top_k": 40,
    "request_pool": 256, "check_requests": 2, "trace_after_s": 0.5, "trace_ticks": 3,
}
# readings at this size (CPU, 8 seeds): train grad_gap <= 0.003, control
# >= 0.016, half batch >= 0.25; change_gap <= 0.00042, control >= 0.0017,
# a state left unchanged 1.  serve served_gap <= 0.0052, control >= 0.065,
# an altered token >= 0.67.
LIMITS = {
    "train.tiny": {"grad_gap": 0.03, "change_gap": 0.0009},
    "serve.tiny": {"served_gap": 0.02},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark of two new cells, from files written here alone."""
    root = tmp_path_factory.mktemp("bench_root")
    bench = root / "bench"
    shutil.copytree(ROOT / "bench" / "drivers", bench / "drivers")
    shutil.copytree(ROOT / "bench" / "metrics", bench / "metrics")
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir()
    (bench / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (bench / "traffic" / "tiny_train.json").write_text(json.dumps(TRAIN))
    (bench / "traffic" / "tiny_serve.json").write_text(json.dumps(SERVE))
    for name, lim in LIMITS.items():
        (bench / "limits" / f"{name}.json").write_text(json.dumps({"limits": lim}))
    e2e = lambda n, u, cells: {"name": n, "unit": u, "better": "lower", "bound": 0.05,
                               "source": "host_clock", "workloads": cells}
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"], "run_seconds": 2,
        "configs": [{"name": "tiny", "source": TINY["source"], "file": "bench/configs/tiny.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "train.tiny", "config": "tiny", "traffic": "tiny_train", "chips": 1, "why": "t"},
            {"name": "serve.tiny", "config": "tiny", "traffic": "tiny_serve", "chips": 1, "why": "t"},
        ],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25, "source": "host_clock"},
            e2e("train_tokens_per_s", "tokens/s", ["train.tiny"]),
            e2e("serve_tokens_per_s", "tokens/s", ["serve.tiny"]),
            e2e("tpot_p95_ms", "ms", ["serve.tiny"]),
        ],
        "per_layer": [],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Runs in tests leave JAX's persistent compile cache off."""
    monkeypatch.setattr(program, "setup_compile_cache", lambda: None)
