"""The program's named scopes reach its compiled modules, and
``bench.scopes`` reads them back: a tiny train step compiled on the CPU
(through ``bench/profile_layers.py``'s capture, as on the chip) and a tiny
q4 engine's ``decode_hlo()``."""

import collections

import jax.numpy as jnp
import pytest

from bench import harness, layers, program, scopes
from bench.drivers import serve_closed, train
from bench.profile_layers import capturing
from bench_cells import SEED

pytestmark = pytest.mark.usefixtures("no_compile_cache")


def classes(op_map):
    return collections.Counter(scopes.classify(p) for p in op_map.values())


def test_classify_paths():
    assert scopes.classify("jit(train_step)/jvp(fwd)/while/body/dot_general") == "fwd"
    assert scopes.classify("jit(train_step)/transpose(jvp(fwd))/while/body/mul") == "bwd"
    assert scopes.classify("jit(train_step)/optimizer/jit(_uniform)/add") == "optimizer"
    assert scopes.classify("jit(_decode)/while/body/sample/sort") == "sample"
    assert scopes.classify("jit(_decode)/materialize/convert_element_type") == "materialize"
    assert scopes.classify("jit(train_step)/sqrt") is None
    assert scopes.classify("") is None
    assert scopes.classify("jit(f)/fwdx/add") is None


def test_op_scopes_reads_instruction_names_and_paths():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={(f32[2]{0})->f32[2]{0}}",
        "ENTRY %main.3 (p: f32[2]) -> f32[2] {",
        '  %p = f32[2]{0} parameter(0), metadata={op_name="p"}',
        '  %fusion.7 = f32[2]{0} fusion(%p), kind=kLoop, calls=%c, '
        'metadata={op_name="jit(step)/optimizer/sub" source_file="x.py"}',
        "  ROOT %copy.2 = f32[2]{0} copy(%fusion.7)",
        "}",
    ])
    assert scopes.op_scopes(text) == {"p": "p", "fusion.7": "jit(step)/optimizer/sub",
                                      "copy.2": ""}


def test_train_step_scopes_cover_forward_backward_and_optimizer(root):
    import repro.launch.train as launch_train

    cell = harness.load_cell(root, "train.tiny")
    cfg, opt, init, feed = train.build(cell, SEED)
    state = init(*program.keys(SEED))
    sample = {k: jnp.asarray(v) for k, v in feed.batch_at(0).items()}
    original = launch_train.compile_step
    with capturing() as cap:
        launch_train.compile_step(cfg, opt, state, None, sample)
    assert launch_train.compile_step is original
    got = classes(cap.maps[layers.TRAIN_MODULE])
    for scope in ("fwd", "bwd", "optimizer"):
        assert got[scope] > 0, got
    assert got["materialize"] == got["sample"] == 0


def test_q4_decode_hlo_holds_materialize_and_sample(root):
    import repro.serve as serve

    cell = harness.load_cell(root, "serve.tiny")
    original = serve.ServeEngine
    with capturing() as cap:
        eng = serve_closed.build_engine(cell, SEED)
    assert serve.ServeEngine is original and cap.engine() is eng
    got = classes(scopes.op_scopes(eng.decode_hlo()))
    assert got["materialize"] > 0 and got["sample"] > 0, got
    assert got["fwd"] == got["bwd"] == got["optimizer"] == 0
