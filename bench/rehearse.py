#!/usr/bin/env python3
"""Compile a cell's train step for a described TPU v5e and print what it
needs, with no chip:

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name> [--layers N]

Nothing runs: the step is lowered from shapes and compiled by the TPU
compiler for a chip that is described, not attached, so a cell's fit in the
chip's memory is checked before it costs chip time.  ``--layers`` overrides
the configuration's depth.  Prints ``memory_analysis()`` and the number of
``tpu_custom_call`` ops in the compiled step.
"""

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def describe_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def rehearse(cell, layers=None):
    import jax
    import jax.numpy as jnp

    from bench import program
    from bench.drivers import train
    from repro.train.train_loop import build_train_step

    if layers:
        cell = dataclasses.replace(cell, config=dict(cell.config, num_layers=layers))
    cfg, opt, init, feed = train.build(cell, 0)
    wkey, skey = program.keys(0)
    one_chip = describe_chip()
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    state = jax.tree_util.tree_map(on_chip, jax.eval_shape(init, wkey, skey))
    sample = {k: on_chip(jnp.asarray(v)) for k, v in feed.batch_at(0).items()}
    step = jax.jit(build_train_step(cfg, opt), donate_argnums=(0,))
    t0 = time.perf_counter()
    compiled = step.lower(state, sample).compile()
    return compiled, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_KERNEL_BACKEND"] = "tpu"  # route eligible leaves to the kernel

    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    compiled, secs = rehearse(cell, args.layers)
    ma = compiled.memory_analysis()
    gib = 2.0**30
    print(json.dumps({
        "workload": args.workload,
        "layers": args.layers or cell.config["num_layers"],
        "compile_s": secs,
        "argument_gib": ma.argument_size_in_bytes / gib,
        "temp_gib": ma.temp_size_in_bytes / gib,
        "output_gib": ma.output_size_in_bytes / gib,
        "alias_gib": ma.alias_size_in_bytes / gib,
        "tpu_custom_calls": compiled.as_text().count('custom_call_target="tpu_custom_call"'),
    }))


if __name__ == "__main__":
    main()
