"""Traffic is drawn from the seed: the same seed gives the same inputs,
another seed other inputs of the same sizes."""

import numpy as np

from bench.generate import TokenFeed, request_specs

MIX = {"prompt_len": [64, 256], "max_new_tokens": [1024, 3072],
       "temperature": 0.8, "top_k": 40}


def feed(seed):
    return TokenFeed(seed, vocab_size=1000, batch=4, seq_len=64,
                     zipf_exponent=1.1, copy_prob=0.5)


def test_token_feed_repeats_for_a_seed():
    a, b = feed(3_000_000_017).batch_at(5), feed(3_000_000_017).batch_at(5)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert np.array_equal(a["labels"], b["labels"])


def test_token_feed_differs_across_seeds_steps_and_rows():
    f = feed(3_000_000_017)
    a, b = f.batch_at(0), f.batch_at(1)
    c = feed(3_000_000_018).batch_at(0)
    assert not np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    rows = {r.tobytes() for r in a["tokens"]}
    assert len(rows) == a["tokens"].shape[0]


def test_token_feed_labels_are_next_tokens():
    b = feed(7).batch_at(2)
    assert b["tokens"].shape == (4, 64) and b["tokens"].dtype == np.int32
    assert np.array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])
    assert (b["labels"][:, -1] == -1).all()
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 1000


def test_request_specs_repeat_for_a_seed():
    assert request_specs(3_000_000_017, 64, 1000, MIX) == request_specs(3_000_000_017, 64, 1000, MIX)


def test_request_specs_same_sizes_other_tokens_across_seeds():
    a = request_specs(3_000_000_017, 64, 1000, MIX)
    b = request_specs(3_000_000_018, 64, 1000, MIX)
    sizes = lambda specs: [(len(p), n, t, k) for p, n, t, k in specs]
    assert sizes(a) == sizes(b)
    assert [s[0] for s in a] != [s[0] for s in b]
    for prompt, new, temp, top_k in a:
        assert 64 <= len(prompt) <= 256 and 1024 <= new <= 3072
        assert (temp, top_k) in ((0.0, 0), (0.8, 40))
        assert min(prompt) >= 1 and max(prompt) < 1000
    assert sum(t == 0.0 for _, _, t, _ in a) == 32


def test_request_specs_lengths_are_log_uniform():
    mix = dict(MIX, max_new_tokens=[64, 1024])
    new = np.array([n for _, n, _, _ in request_specs(3_000_000_017, 4096, 1000, mix)])
    assert new.min() >= 64 and new.max() <= 1024
    # log-uniform in [64, 1024]: mean (1024 - 64) / ln 16 = 346, median 256
    assert abs(new.mean() - 346) < 15 and abs(np.median(new) - 256) < 20
