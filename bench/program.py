"""The program under test, as the benchmark sets it up: its config at the
file's sizes, and the seed's weights in its parameter tree."""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from bench.reference.model import seed_key

WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
          "vocab_size", "rope_variant", "rope_theta")


def keys(seed: int):
    """(weights, the program's random streams)."""
    base = seed_key(seed)
    return tuple(jax.random.fold_in(base, i) for i in range(2))


def program_config(c: dict):
    """The program's config of that name, run at the file's depth and sizes."""
    from repro.configs import get_config

    base = get_config(c["program_config"])
    L = c["num_layers"]
    cfg = dataclasses.replace(
        base, num_layers=L, blocks=base.blocks[:L], **{k: c[k] for k in WIDTHS}
    )
    if cfg.tie_embeddings or cfg.norm_type != "rmsnorm" or not cfg.gated_mlp:
        raise ValueError(f"{c['name']}: not the dense untied RMSNorm/SwiGLU family")
    if any(b.kind != "dense" or b.window for b in cfg.blocks):
        raise ValueError(f"{c['name']}: not every layer is a dense full-attention layer")
    return cfg


def to_program(P: Dict[str, jnp.ndarray]):
    """The reference's weights in the program's parameter tree."""
    return {
        "embed": P["embed"],
        "decoder": [{"sub0": {
            "attn": {k: P[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: P[k] for k in ("w1", "w2", "w3")},
            "norm1": P["norm1"],
            "norm2": P["norm2"],
        }}],
        "final_norm": P["final_norm"],
        "head": P["head"],
    }


def check_tree(cfg, params):
    """The weights handed over have the program's own tree and shapes."""
    from repro.models import init_model

    want = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg)[0])
    got = jax.eval_shape(lambda: params)
    same = jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got) and all(
        a.shape == b.shape
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got))
    )
    if not same:
        raise ValueError("the program's parameter tree is not the reference's")


def setup_compile_cache():
    """The program's persistent compile cache (a fixed path in the checkout,
    or ``JAX_COMPILATION_CACHE_DIR``), holding every program of a run."""
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
