"""Serving engine correctness: prefill parity, sampling reproducibility,
q4 weight tolerance, and retire/backfill isolation.

Fast tier runs everything on a 2-layer tiny dense LM; the cross-arch prefill
parity cases (GQA + softcap, xLSTM recurrence, hybrid SSM) are compile-heavy
and carry the ``slow`` marker like the other decode-parity suites.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.models import (
    LayerSpec,
    ModelConfig,
    decode_step,
    init_model,
    init_serve_cache,
    plan_scan_units,
    prefill_with_cache,
)
from repro.models.attention import cache_prefill, cache_update, make_cache
from repro.serve import (
    Request,
    ServeEngine,
    materialize,
    prepare_params,
    request_key_words,
    sample_tokens,
    weight_report,
)

TINY = ModelConfig(
    name="serve-test",
    num_layers=2,
    d_model=64,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    d_ff=256,
    vocab_size=256,
    blocks=(LayerSpec("dense", 0),) * 2,
    remat=False,
)


@pytest.fixture(scope="module")
def tiny_params():
    params, _ = init_model(jax.random.PRNGKey(0), TINY)
    return params


def _oracle_prefill(params, cfg, prompts, s_max=64):
    """Token-at-a-time reference: feed each prompt through decode_step."""
    B = len(prompts)
    caches = init_serve_cache(cfg, B, s_max)
    S = max(len(p) for p in prompts)
    last = np.zeros((B, cfg.vocab_size), np.float32)
    for t in range(S):
        toks = jnp.array([p[min(t, len(p) - 1)] for p in prompts], jnp.int32)
        pos = jnp.full((B,), t, jnp.int32)
        logits, caches = decode_step(params, cfg, caches, toks, pos)
        logits = np.asarray(logits)
        for b, p in enumerate(prompts):
            if t == len(p) - 1:
                last[b] = logits[b]
    return last, caches


def _batched_prefill(params, cfg, prompts, s_max=64):
    B = len(prompts)
    S = max(len(p) for p in prompts)
    toks = np.zeros((B, S), np.int32)
    for b, p in enumerate(prompts):
        toks[b, : len(p)] = p
    lens = jnp.array([len(p) for p in prompts], jnp.int32)
    caches = init_serve_cache(cfg, B, s_max)
    logits, caches = prefill_with_cache(
        params, cfg, jnp.asarray(toks), lens, caches
    )
    return np.asarray(logits), caches


# ---------------------------------------------------------------------------
# one-shot prefill vs token-at-a-time oracle
# ---------------------------------------------------------------------------


def test_prefill_matches_decode_oracle_tiny(tiny_params):
    prompts = [[5, 6, 7, 8, 9], [10, 11, 12], [13]]
    l_oracle, c_oracle = _oracle_prefill(tiny_params, TINY, prompts)
    l_batch, c_batch = _batched_prefill(tiny_params, TINY, prompts)
    np.testing.assert_allclose(l_batch, l_oracle, atol=2e-2, rtol=0)

    # The caches must be behaviorally identical too: continue greedy decode
    # from both and compare every step's logits.
    pos = np.array([len(p) for p in prompts], np.int32)
    tok_a = jnp.asarray(np.argmax(l_oracle, -1).astype(np.int32))
    tok_b = jnp.asarray(np.argmax(l_batch, -1).astype(np.int32))
    for t in range(4):
        la, c_oracle = decode_step(
            tiny_params, TINY, c_oracle, tok_a, jnp.asarray(pos + t)
        )
        lb, c_batch = decode_step(
            tiny_params, TINY, c_batch, tok_b, jnp.asarray(pos + t)
        )
        np.testing.assert_allclose(
            np.asarray(lb), np.asarray(la), atol=2e-2, rtol=0
        )
        tok_a = jnp.argmax(la, -1).astype(jnp.int32)
        tok_b = jnp.argmax(lb, -1).astype(jnp.int32)
        assert np.array_equal(np.asarray(tok_a), np.asarray(tok_b))


@pytest.mark.slow
@pytest.mark.parametrize(
    "arch", ["gemma2-2b", "xlstm-125m", "hymba-1.5b"]
)
def test_prefill_matches_decode_oracle_archs(arch):
    # GQA + logit softcap / mLSTM + sLSTM recurrence / attention + SSM
    # hybrid: padding must be inert in every cache regime.
    cfg = reduced_config(arch)
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7, 8], [9, 10]]
    l_oracle, _ = _oracle_prefill(params, cfg, prompts, s_max=256)
    l_batch, _ = _batched_prefill(params, cfg, prompts, s_max=256)
    # recurrent paths accumulate slightly different rounding than the
    # step-by-step oracle (bf16 matmuls in one S-length einsum vs S rank-1
    # updates); attention archs are bit-exact.
    np.testing.assert_allclose(l_batch, l_oracle, atol=5e-2, rtol=0)


def test_cache_prefill_matches_sequential_writes():
    # Gather-formulated bulk write == sequential circular writes, including
    # rows longer than the cache (windowed layers) and empty tails.
    B, S, Smax, H, D = 3, 10, 4, 2, 8
    key = jax.random.PRNGKey(1)
    k_new = jax.random.normal(key, (B, S, H, D))
    v_new = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, D))
    lengths = jnp.array([10, 3, 1], jnp.int32)

    bulk = cache_prefill(make_cache(B, Smax, H, D), k_new, v_new, lengths)

    seq = make_cache(B, Smax, H, D)
    for t in range(S):
        # sequential oracle writes row b only while t < lengths[b]; emulate
        # by re-writing the previous value for finished rows
        pos = jnp.minimum(t, lengths - 1)
        kt = k_new[jnp.arange(B), pos][:, None]
        vt = v_new[jnp.arange(B), pos][:, None]
        seq = cache_update(seq, kt, vt, pos)

    np.testing.assert_array_equal(np.asarray(bulk.pos), np.asarray(seq.pos))
    np.testing.assert_allclose(
        np.asarray(bulk.k, np.float32), np.asarray(seq.k, np.float32)
    )
    np.testing.assert_allclose(
        np.asarray(bulk.v, np.float32), np.asarray(seq.v, np.float32)
    )


# ---------------------------------------------------------------------------
# in-place decode vs a per-layer reference
# ---------------------------------------------------------------------------


def _per_layer_decode(params, cfg, caches, tokens, pos):
    """Reference decode step with the per-layer data flow: every layer's
    cache is sliced out of its unit's stack, run through its block as a
    single layer's cache, and stacked back (rope archs)."""
    from repro.models.blocks import apply_block
    from repro.models.layers import embed_lookup, softcap
    from repro.models.model import _final_norm, _head_weight

    assert cfg.rope_variant == "rope"
    h = embed_lookup(params["embed"], tokens[:, None])
    new_caches = []
    for unit, p_unit, c_unit in zip(plan_scan_units(cfg.blocks), params["decoder"], caches):
        per_layer = []
        for li in range(unit.repeat):
            p_l = jax.tree_util.tree_map(lambda a: a[li], p_unit)
            c_l = jax.tree_util.tree_map(lambda a: a[li], c_unit)
            new_l = {}
            for si, spec in enumerate(unit.pattern):
                h, new_l[f"sub{si}"], _ = apply_block(
                    p_l[f"sub{si}"], h, spec, cfg, positions=pos[:, None],
                    cache=c_l[f"sub{si}"], cur_pos=pos,
                )
            per_layer.append(new_l)
        new_caches.append(jax.tree_util.tree_map(lambda *a: jnp.stack(a), *per_layer))
    h = _final_norm(cfg, h, params["final_norm"])
    logits = jnp.einsum(
        "bsd,dv->bsv", h.astype(jnp.bfloat16),
        _head_weight(cfg, params).astype(jnp.bfloat16),
    )[:, 0].astype(jnp.float32)
    if cfg.final_softcap > 0:
        logits = softcap(logits, cfg.final_softcap)
    return logits, new_caches


def _assert_caches_close(got, want, rel=2e-2):
    """Same tree, shapes and dtypes; positions equal; every float leaf within
    ``rel`` of its own largest magnitude (recurrent states are small)."""
    g_leaves, g_tree = jax.tree_util.tree_flatten(got)
    w_leaves, w_tree = jax.tree_util.tree_flatten(want)
    assert g_tree == w_tree
    for g, w in zip(g_leaves, w_leaves):
        assert g.shape == w.shape and g.dtype == w.dtype
        exact = jnp.issubdtype(g.dtype, jnp.integer)
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if exact or not np.any(w):
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(g, w, atol=rel * np.abs(w).max(), rtol=0)


@pytest.mark.parametrize(
    "arch", ["tiny", "gemma2-2b", "xlstm-125m", "hymba-1.5b"]
)
def test_inplace_decode_matches_per_layer_reference(arch, tiny_params):
    # decode_step writes each step's token into the stacked caches in place;
    # from a prefilled state, its logits and every layer's K/V/pos (and
    # recurrent states) must match the per-layer data flow step by step,
    # for full, windowed, recurrent and hybrid caches.
    if arch == "tiny":
        cfg, params, atol = TINY, tiny_params, 2e-2
    else:
        cfg = reduced_config(arch)
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        atol = 5e-2
    prompts = [[5, 6, 7, 8, 9], [10, 11, 12], [13]]
    _, caches = _batched_prefill(params, cfg, prompts, s_max=256)
    ref_caches = caches
    pos = jnp.array([len(p) for p in prompts], jnp.int32)
    tok = jnp.array([3, 4, 5], jnp.int32)
    for t in range(4):
        logits, caches = decode_step(params, cfg, caches, tok, pos + t)
        ref_logits, ref_caches = _per_layer_decode(params, cfg, ref_caches, tok, pos + t)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits), atol=atol, rtol=0
        )
        _assert_caches_close(caches, ref_caches)
        tok = jnp.argmax(ref_logits, -1).astype(jnp.int32)


def test_engine_donates_caches_and_keeps_greedy_streams(tiny_params):
    # The decode chunk takes the engine's caches as donated buffers (they
    # are dead after the call), and greedy streams equal prefill followed
    # by the per-layer reference decode.
    prompts = [[5, 6, 7, 8, 9], [10, 11, 12], [13]]
    n_new = 9
    eng = ServeEngine(TINY, tiny_params, max_batch=3, s_max=64, drain_every=4)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n_new) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    assert eng.step()  # admit + prefill + one chunk
    passed = jax.tree_util.tree_leaves(eng.caches)
    assert passed and not any(a.is_deleted() for a in passed)
    assert eng.step()  # one more chunk, nothing to admit
    assert all(a.is_deleted() for a in passed)
    eng.run()
    assert all(r.done for r in reqs)

    params = materialize(prepare_params(tiny_params, "bf16"))
    logits, caches = _batched_prefill(params, TINY, prompts, s_max=64)
    tok = jnp.asarray(np.argmax(logits, -1).astype(np.int32))
    pos = jnp.array([len(p) for p in prompts], jnp.int32)
    want = [tok]
    for t in range(n_new - 1):
        logits, caches = _per_layer_decode(params, TINY, caches, tok, pos + t)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        want.append(tok)
    want = np.stack([np.asarray(w) for w in want], axis=1)
    for b, r in enumerate(reqs):
        assert r.output == want[b].tolist(), f"rid={b}"


# ---------------------------------------------------------------------------
# on-device sampling
# ---------------------------------------------------------------------------


def _rand_logits(key, B, V=64):
    return jax.random.normal(key, (B, V)) * 3.0


def test_sampling_greedy_at_zero_temperature():
    logits = _rand_logits(jax.random.PRNGKey(0), 4)
    kw = jnp.stack(request_key_words(0, np.arange(4)), axis=-1)
    out = sample_tokens(
        logits, kw, jnp.zeros(4, jnp.uint32), jnp.zeros(4), jnp.zeros(4, jnp.int32)
    )
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.argmax(logits, -1))
    )


def test_sampling_respects_top_k():
    B, V = 8, 64
    logits = _rand_logits(jax.random.PRNGKey(1), B, V)
    kw = jnp.stack(request_key_words(0, np.arange(B)), axis=-1)
    top_k = jnp.array([1, 2, 4, 8, 16, 1, 2, 4], jnp.int32)
    for gen in range(16):
        out = np.asarray(
            sample_tokens(
                logits, kw, jnp.full((B,), gen, jnp.uint32),
                jnp.full((B,), 0.9), top_k,
            )
        )
        ranks = np.asarray(jnp.argsort(-logits, axis=-1))
        for b in range(B):
            assert out[b] in ranks[b, : int(top_k[b])]


def test_sampling_stream_is_slot_invariant():
    # A request's stream depends on (seed, rid, gen_idx) only — not on which
    # batch row it occupies or who its neighbors are.
    V = 64
    logits_r7 = _rand_logits(jax.random.PRNGKey(7), 1, V)[0]
    for layout, row in ((np.array([7, 3]), 0), (np.array([9, 7, 1]), 1)):
        B = len(layout)
        logits = jnp.tile(logits_r7[None], (B, 1))
        kw = jnp.stack(request_key_words(0, layout), axis=-1)
        out = np.asarray(
            sample_tokens(
                logits, kw, jnp.full((B,), 5, jnp.uint32),
                jnp.full((B,), 0.8), jnp.full((B,), 10, jnp.int32),
            )
        )
        if row == 0:
            first = out[row]
        else:
            assert out[row] == first


def test_engine_sampled_streams_reproducible(tiny_params):
    # Same (seed, rid) => same stream, under slot reshuffle (reversed submit
    # order, different max_batch) and full engine restart.
    def serve(order, max_batch):
        eng = ServeEngine(TINY, tiny_params, max_batch=max_batch, s_max=64)
        reqs = {
            i: Request(
                rid=i, prompt=[1 + i, 2 + i, 3 + i], max_new_tokens=6,
                temperature=0.8, top_k=10,
            )
            for i in order
        }
        for i in order:
            eng.submit(reqs[i])
        eng.run()
        return {i: r.output for i, r in reqs.items()}

    a = serve([0, 1, 2, 3, 4], 2)
    b = serve([4, 3, 2, 1, 0], 3)  # reshuffled + different slot count
    c = serve([0, 1, 2, 3, 4], 2)  # restart
    assert a == b == c
    assert len({tuple(v) for v in a.values()}) > 1  # streams differ by rid


# ---------------------------------------------------------------------------
# q4 serving weights
# ---------------------------------------------------------------------------


def test_q4_within_logit_tolerance_of_bf16(tiny_params):
    prompts = [[5, 6, 7, 8], [9, 10]]
    l_bf, _ = _batched_prefill(
        materialize(prepare_params(tiny_params, "bf16")), TINY, prompts
    )
    l_q4, _ = _batched_prefill(
        materialize(prepare_params(tiny_params, "q4")), TINY, prompts
    )
    # bf16 serving == fp32 masters (casting to the compute dtype is a no-op
    # change); q4 adds bounded block-quantization noise, far below the O(1)
    # errors a broken scale/mapping layout produces.
    l_fp, _ = _batched_prefill(tiny_params, TINY, prompts)
    np.testing.assert_allclose(l_bf, l_fp, atol=1e-5, rtol=0)
    assert float(np.abs(l_q4 - l_bf).max()) < 0.3


def test_q4_weight_bytes_ratio(tiny_params):
    eng = ServeEngine(TINY, tiny_params, max_batch=2, s_max=64, weights="q4")
    rep = eng.weight_bytes()
    assert rep["quantized_leaves"] > 0
    assert rep["total_serve_bytes"] < rep["total_bf16_bytes"]
    # the acceptance floor holds on the GPT-2-M-shaped tree
    from benchmarks.tables import _gpt2m_like_params

    big = weight_report(_gpt2m_like_params(), "q4")
    assert big["ratio_vs_bf16"] >= 3.5


def test_q4_engine_decodes(tiny_params):
    eng = ServeEngine(TINY, tiny_params, max_batch=2, s_max=64, weights="q4")
    r = Request(rid=0, prompt=[3, 4, 5], max_new_tokens=5)
    eng.submit(r)
    eng.run()
    assert r.done and len(r.output) == 5
    assert all(0 <= t < TINY.vocab_size for t in r.output)


# ---------------------------------------------------------------------------
# retire / backfill isolation
# ---------------------------------------------------------------------------


def test_retire_backfill_no_kv_leak(tiny_params):
    # 6 requests through 2 slots (3 waves of retire + backfill), ragged
    # prompt lengths so buckets and cache occupancy differ per wave.  Every
    # stream must equal its solo single-slot run — any KV or sampler state
    # leaking across a backfill would diverge the later waves.
    prompts = [
        [5, 6, 7, 8, 9, 10, 11],
        [12, 13],
        [14, 15, 16],
        [17],
        [18, 19, 20, 21, 22],
        [23, 24, 25],
    ]

    def solo(i):
        eng = ServeEngine(TINY, tiny_params, max_batch=1, s_max=64)
        r = Request(rid=i, prompt=prompts[i], max_new_tokens=6)
        eng.submit(r)
        eng.run()
        return r.output

    eng = ServeEngine(TINY, tiny_params, max_batch=2, s_max=64)
    reqs = [
        Request(rid=i, prompt=prompts[i], max_new_tokens=6)
        for i in range(len(prompts))
    ]
    for r in reqs:
        eng.submit(r)
    eng.run()
    for i, r in enumerate(reqs):
        assert r.done
        assert r.output == solo(i), f"rid={i} diverged after backfill"


def test_eos_retires_early(tiny_params):
    # Find the greedy second token, then declare it EOS: output must stop
    # there and the freed slot must serve the next request correctly.
    eng = ServeEngine(TINY, tiny_params, max_batch=1, s_max=64)
    probe = Request(rid=0, prompt=[7, 8, 9], max_new_tokens=4)
    eng.submit(probe)
    eng.run()
    eos = probe.output[1]

    eng = ServeEngine(TINY, tiny_params, max_batch=1, s_max=64)
    r0 = Request(rid=0, prompt=[7, 8, 9], max_new_tokens=4, eos_id=eos)
    r1 = Request(rid=1, prompt=[10, 11], max_new_tokens=3)
    eng.submit(r0)
    eng.submit(r1)
    eng.run()
    assert r0.done and r0.output == probe.output[:2]
    solo = ServeEngine(TINY, tiny_params, max_batch=1, s_max=64)
    ref = Request(rid=1, prompt=[10, 11], max_new_tokens=3)
    solo.submit(ref)
    solo.run()
    assert r1.output == ref.output
