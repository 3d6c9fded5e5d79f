"""Per-layer readings from the program's scopes, spans and counters
(``bench.layers``): ops split by module, span totals and the idle gaps
under each span, on synthetic traces and on the recorded v5e trace."""

import pathlib

import pytest

from bench import layers, trace
from bench.layers import ADMIT, DECODE, DRAIN, PREFILL, RETIRE, STEP, Layers
from bench.profile_layers import coverage

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"
US = 1000


def test_module_ops_sum_to_ops_on_recorded_trace():
    ops, modules, host = trace.read_planes(str(SMALL))
    s = trace.reduce(ops, modules, host, window_s=0.0124)
    got = layers.module_ops(ops, modules)
    assert sum(got.values()) == pytest.approx(sum(s.ops.values()), rel=1e-9)
    for (mod, op), sec in got.items():
        assert mod in s.modules and sec <= s.ops[op] * (1 + 1e-9)
    per_module = {m: sum(v for (k, _), v in got.items() if k == m) for m in s.modules}
    for m, (_, sec) in s.modules.items():
        assert per_module.get(m, 0.0) <= sec * (1 + 1e-9)
    assert got[("jit__lambda", "fused_adamw4.1")] == pytest.approx(s.ops["fused_adamw4.1"])


def test_recorded_trace_without_program_spans():
    ops, modules, host = trace.read_planes(str(SMALL))
    s = trace.reduce(ops, modules, host, window_s=0.0124)
    lay = layers.load(str(SMALL))
    assert lay.spans == {}
    assert list(lay.span_idle) == ["no host span"]
    assert lay.span_idle["no host span"] == pytest.approx(sum(g for _, g in s.gaps))
    assert layers.readings(lay, {}, s.modules) == {}


def test_same_named_ops_of_two_modules_stay_apart():
    ops = [[("%fusion.1 = f32[] add()", 0, 10 * US), ("%fusion.1 = f32[] add()", 20 * US, 25 * US),
            ("%copy.2 = f32[] copy()", 30 * US, 31 * US)]]
    modules = [[("jit__prefill(1)", 0, 12 * US), ("jit__decode(2)", 18 * US, 26 * US)]]
    got = layers.module_ops(ops, modules)
    assert got == {("jit__prefill", "fusion.1"): pytest.approx(10e-6),
                   ("jit__decode", "fusion.1"): pytest.approx(5e-6),
                   ("", "copy.2"): pytest.approx(1e-6)}
    assert trace.reduce(ops, modules, [], 1.0).ops["fusion.1"] == pytest.approx(15e-6)


def test_span_totals_and_idle_by_innermost_span():
    spans = [(STEP, 0, 100 * US, {}),
             (PREFILL, 5 * US, 40 * US, {"admitted": 2, "prompt_tokens": 150, "positions": 1024}),
             (DRAIN, 60 * US, 90 * US, {}),
             (STEP, 100 * US, 200 * US, {}),
             (RETIRE, 150 * US, 160 * US, {"delivered": 8, "discarded": 3, "retired": 1, "x": "s"})]
    ops = [[("%a.1 = f32[] add()", 0, 10 * US), ("%a.2 = f32[] add()", 30 * US, 70 * US),
            ("%a.3 = f32[] add()", 80 * US, 150 * US), ("%a.4 = f32[] add()", 160 * US, 170 * US),
            ("%a.5 = f32[] add()", 300 * US, 310 * US)]]
    lay = layers.reduce(ops, [[]], spans)
    assert lay.spans[STEP]["count"] == 2
    assert lay.spans[STEP]["seconds"] == pytest.approx(200e-6)
    assert lay.spans[PREFILL]["counters"] == {"admitted": 2, "prompt_tokens": 150, "positions": 1024}
    assert lay.spans[RETIRE]["counters"] == {"delivered": 8, "discarded": 3, "retired": 1}
    assert lay.span_idle == {PREFILL: pytest.approx(20e-6), DRAIN: pytest.approx(10e-6),
                             RETIRE: pytest.approx(10e-6), "no host span": pytest.approx(130e-6)}


def _layers(**kw):
    base = dict(module_ops={}, spans={}, span_idle={})
    base.update(kw)
    return Layers(**base)


TRAIN_MAP = {"f.1": "jit(train_step)/jvp(fwd)/dot", "f.2": "jit(train_step)/transpose(jvp(fwd))/dot",
             "f.3": "jit(train_step)/optimizer/sub", "f.4": "jit(train_step)/sqrt"}


def test_train_readings_per_step():
    lay = _layers(module_ops={("jit_train_step", "f.1"): 0.08, ("jit_train_step", "f.2"): 0.2,
                              ("jit_train_step", "f.3"): 0.06, ("jit_train_step", "f.4"): 0.01,
                              ("jit_train_step", "f.9"): 0.001, ("jit_other", "f.1"): 5.0})
    got = layers.readings(lay, {"jit_train_step": TRAIN_MAP}, {"jit_train_step": [2.0, 0.4]})
    assert got == {"fwd_ms.train": pytest.approx(40.0), "bwd_ms.train": pytest.approx(100.0),
                   "optimizer_ms.train": pytest.approx(30.0)}
    cov = coverage(lay, TRAIN_MAP, "jit_train_step")
    assert cov["map_share"] == pytest.approx(100 * (1 - 0.001 / 0.351))
    assert cov["scoped"]["None"] == pytest.approx(100 * 0.01 / 0.351)
    assert cov["unscoped_top"] == [["f.4", pytest.approx(10.0), "jit(train_step)/sqrt"]]


def test_train_readings_without_map_or_steps_are_none():
    lay = _layers(module_ops={("jit_train_step", "f.1"): 0.08})
    assert layers.readings(lay, {}, {"jit_train_step": [2.0, 0.4]}) == {
        "fwd_ms.train": None, "bwd_ms.train": None, "optimizer_ms.train": None}
    assert layers.readings(lay, {"jit_train_step": TRAIN_MAP}, {}) == {}
    assert coverage(lay, {}, "jit_decode") == {}


def test_serve_readings_per_call_and_tick():
    dmap = {"s.1": "jit(_decode)/while/body/sample/sort", "m.1": "jit(_decode)/materialize/mul",
            "c.1": "jit(_decode)/while/body/dot"}
    lay = _layers(
        module_ops={("jit__decode", "s.1"): 0.09, ("jit__decode", "m.1"): 0.2,
                    ("jit__decode", "c.1"): 0.6, ("jit__prefill", "s.1"): 1.0},
        spans={STEP: {"count": 4, "seconds": 1.0, "counters": {}},
               PREFILL: {"count": 2, "seconds": 0.2,
                         "counters": {"prompt_tokens": 300, "positions": 2 * 32 * 512}}},
        span_idle={DRAIN: 0.004, RETIRE: 0.002, ADMIT: 0.001, PREFILL: 0.003, DECODE: 0.004,
                   STEP: 0.5, "no host span": 1.0})
    got = layers.readings(lay, {"jit__decode": dmap}, {"jit__decode": [3.0, 0.9]})
    assert got == {"materialize_ms.serve": pytest.approx(200 / 3), "sample_ms.serve": pytest.approx(30.0),
                   "drain_idle_ms.serve": pytest.approx(1.5), "dispatch_idle_ms.serve": pytest.approx(2.0),
                   "prefill_useful.serve": pytest.approx(100 * 300 / 32768)}


def test_serve_readings_none_cases():
    dmap = {"c.1": "jit(_decode)/while/body/dot"}
    lay = _layers(module_ops={("jit__decode", "c.1"): 0.6})
    got = layers.readings(lay, {"jit__decode": dmap}, {"jit__decode": [3.0, 0.6]})
    # bf16: no materialize op reads 0.0; no spans (a program without them)
    assert got == {"materialize_ms.serve": 0.0, "sample_ms.serve": 0.0, "drain_idle_ms.serve": None,
                   "dispatch_idle_ms.serve": None, "prefill_useful.serve": None}
    assert layers.readings(lay, {}, {"jit__decode": [3.0, 0.6]})["materialize_ms.serve"] is None
    assert layers.readings(lay, {}, {"jit__prefill": [1.0, 0.1]}) == {}
