"""The serving engine's host spans and their counters, as a profiler
session records them, on a tiny engine on the CPU."""

import contextlib
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.models import LayerSpec, ModelConfig, init_model
from repro.serve import Request, ServeEngine

TINY = ModelConfig(
    name="obs-test", num_layers=1, d_model=32, num_heads=2, num_kv_heads=1,
    head_dim=16, d_ff=64, vocab_size=64, blocks=(LayerSpec("dense", 0),), remat=False,
)
MAX_BATCH, DRAIN, NEW = 4, 4, 6
PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8], [9] * 17, [10, 11]]


def _spans(directory):
    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                        for ev in line.events if ev.name.startswith("repro.")]
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Four requests served to their end under a profiler session: one
    admission, two decode chunks, then an idle tick."""
    params, _ = init_model(jax.random.PRNGKey(0), TINY)
    eng = ServeEngine(TINY, params, max_batch=MAX_BATCH, s_max=64, drain_every=DRAIN)
    eng.submit(Request(rid=99, prompt=[1] * 17, max_new_tokens=2))
    eng.run()  # compile outside the session
    reqs = [Request(rid=i, prompt=p, max_new_tokens=NEW) for i, p in enumerate(PROMPTS)]
    d = tmp_path_factory.mktemp("obs_trace")
    with jax.profiler.trace(str(d)):
        for r in reqs:
            eng.submit(r)
        eng.run()
    return _spans(d), reqs


def _by_name(spans):
    out = {}
    for name, s, e, stats in spans:
        out.setdefault(name, []).append((s, e, stats))
    return out


def test_every_phase_has_its_span(traced):
    spans, reqs = traced
    got = _by_name(spans)
    assert set(got) == {obs.STEP, obs.ADMIT, obs.PREFILL, obs.DECODE, obs.DRAIN, obs.RETIRE}
    assert len(got[obs.STEP]) == 3 and len(got[obs.ADMIT]) == 3
    assert len(got[obs.PREFILL]) == 1
    assert len(got[obs.DECODE]) == len(got[obs.DRAIN]) == len(got[obs.RETIRE]) == 2
    assert all(r.done and len(r.output) == NEW for r in reqs)


def test_phases_nest_in_their_tick(traced):
    got = _by_name(traced[0])
    ticks = got[obs.STEP]
    for name in (obs.ADMIT, obs.PREFILL, obs.DECODE, obs.DRAIN, obs.RETIRE):
        for s, e, _ in got[name]:
            assert any(ts <= s and e <= te for ts, te, _ in ticks), name


def test_prefill_counts_prompt_tokens_and_positions(traced):
    (_, _, c), = _by_name(traced[0])[obs.PREFILL]
    bucket = 32  # the power of two (>= 16) over the longest prompt, 17
    assert c["admitted"] == len(PROMPTS)
    assert c["prompt_tokens"] == sum(len(p) for p in PROMPTS)
    assert c["positions"] == MAX_BATCH * bucket
    admits = [st["admitted"] for _, _, st in _by_name(traced[0])[obs.ADMIT]]
    assert admits == [len(PROMPTS), 0, 0]


def test_retire_counts_delivered_discarded_retired(traced):
    spans, reqs = traced
    retire = [st for _, _, st in _by_name(spans)[obs.RETIRE]]
    # one token from the prefill, then chunks of DRAIN: 4 delivered, then
    # 1 more and 3 decoded past each request's end
    assert [st["delivered"] for st in retire] == [4 * len(reqs), 1 * len(reqs)]
    assert [st["discarded"] for st in retire] == [0, (DRAIN - 1) * len(reqs)]
    assert [st["retired"] for st in retire] == [0, len(reqs)]
    assert sum(st["delivered"] for st in retire) + len(reqs) == sum(len(r.output) for r in reqs)


def test_tokens_are_the_same_with_and_without_a_session(tmp_path):
    params, _ = init_model(jax.random.PRNGKey(0), TINY)
    outs = []
    for session in (jax.profiler.trace(str(tmp_path)), contextlib.nullcontext()):
        eng = ServeEngine(TINY, params, max_batch=2, s_max=64, drain_every=DRAIN, seed=3)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=5, temperature=0.7)
                for i, p in enumerate(PROMPTS[:2])]
        with session:
            for r in reqs:
                eng.submit(r)
            eng.run()
        outs.append(np.array([r.output for r in reqs]))
    np.testing.assert_array_equal(outs[0], outs[1])
