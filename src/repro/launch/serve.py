"""Serving CLI: the throughput engine on a token-decoder config.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \
        --requests 8 --weights q4 --temperature 0.8 --top-k 40

``--reduced`` serves the CPU-scale config of the same family; without it the
published config is served (a TPU-sized job).  Weights are random, drawn
from ``--seed``.
"""

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config, reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_model
from repro.serve import Request, ServeEngine, format_weight_table


def make_requests(n, vocab_size, *, prompt_len=8, max_new_tokens=8,
                  temperature=0.0, top_k=0, seed=0, rid0=0):
    """``n`` requests with seeded random prompts of ``prompt_len`` tokens."""
    rng = np.random.default_rng(seed)
    return [
        Request(rid=rid0 + i,
                prompt=[int(t) for t in rng.integers(1, vocab_size, prompt_len)],
                max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k)
        for i in range(n)
    ]


def serve(cfg, params, requests, *, max_batch=2, s_max=256, weights="bf16",
          drain_every=8, seed=0):
    """Run ``requests`` to completion; returns (engine, wall seconds).

    The wall time includes the first compile of each prefill bucket and of
    the decode chunk.
    """
    eng = ServeEngine(
        cfg, params, max_batch=max_batch, s_max=s_max,
        weights=weights, drain_every=drain_every, seed=seed,
    )
    for r in requests:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run()
    return eng, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale config of the same family")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=2)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--weights", default="bf16", choices=("bf16", "q4"),
                    help="serving weight format (q4 = 4-bit block-quantized)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples on device")
    ap.add_argument("--top-k", type=int, default=0, help="0 = full vocab")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and sampling stream")
    ap.add_argument("--drain-every", type=int, default=8,
                    help="decode steps per host sync")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family == "encdec" or cfg.input_mode == "embeds":
        raise SystemExit(f"{args.arch}: token-decoder archs only in this CLI")
    params, _ = init_model(jax.random.PRNGKey(args.seed), cfg)
    reqs = make_requests(
        args.requests, cfg.vocab_size, prompt_len=2,
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        top_k=args.top_k, seed=args.seed,
    )
    eng, wall = serve(
        cfg, params, reqs, max_batch=args.max_batch, weights=args.weights,
        drain_every=args.drain_every, seed=args.seed,
    )

    total_tokens = sum(len(r.output) for r in reqs)
    mode = "greedy" if args.temperature <= 0 else (
        f"T={args.temperature} top_k={args.top_k}"
    )
    print(format_weight_table([eng.weight_bytes()], title="serving weights"))
    print(
        f"served {args.requests} requests / {total_tokens} tokens in "
        f"{wall:.2f}s ({mode}, drain_every={args.drain_every}, "
        f"{total_tokens / wall / args.max_batch:.1f} tok/s/slot incl. compile)"
    )


if __name__ == "__main__":
    main()
