"""Serving cells: a closed loop of clients on the program's ``ServeEngine``.

Set-up draws the weights from the seed, hands them to one ``ServeEngine``
(which prepares its serving format; the float32 masters are then dropped)
and warms every prefill bucket the mix can ask for and the decode chunk,
one request at a time.  The window then drives that engine's ``submit`` and
``step``: each of ``clients`` clients sends its next request as soon as
its last one is finished.  Every time a request's tokens reach the host,
the time since its previous tokens did (or since it was sent), over the
number of tokens, is one sample of the time per output token.

After the window the loop sends nothing more; the greedy requests it
finished are sampled (the longest among them) and their served tokens are
held against the plain reference's logits over prompt and tokens.
"""

from __future__ import annotations

import gc
import math
import tempfile
import time
from typing import Dict, List

import jax
import numpy as np

from bench import device, flops, program, trace as trace_lib
from bench.generate import request_specs
from bench.harness import Outcome
from bench.program import check_tree, keys, program_config, to_program
from bench.reference.model import init_params, leaf_shapes
from bench.reference.serve import ServeReference, control_picks, served_picks, widest_gap

WARMUP_RID = 1 << 30
DRAIN_GRACE_S = 60.0  # a finished greedy request is waited for this long


def build_engine(cell, seed: int):
    from repro.serve import ServeEngine

    c, tr = cell.config, cell.traffic
    cfg = program_config(c)
    wkey = keys(seed)[0]
    params = jax.jit(lambda k: to_program(init_params(k, c)))(wkey)
    check_tree(cfg, params)
    eng = ServeEngine(cfg, params, max_batch=tr["clients"], s_max=tr["s_max"],
                      weights=tr["weights"], drain_every=tr["drain_every"],
                      seed=seed % (1 << 31))
    del params  # the engine holds its own serving copy
    return eng


def warm_up(eng, tr, vocab: int):
    """One request per prefill bucket the mix reaches, each to its end."""
    from repro.serve import Request

    lo, hi = tr["prompt_len"]
    n, rid = lo, WARMUP_RID
    while True:
        eng.submit(Request(rid=rid, prompt=[1 + i % (vocab - 1) for i in range(n)],
                           max_new_tokens=2))
        while eng.step():
            pass
        if n >= hi:
            break
        n, rid = min(2 * n, hi), rid + 1


class ClosedLoop:
    """``clients`` clients, each sending its next request when its last one
    has finished, in the order ``request_specs`` gives."""

    def __init__(self, eng, specs, clients: int):
        self.eng, self.specs = eng, specs
        self.next = 0
        self.live: Dict[int, object] = {}
        self.seen: Dict[int, int] = {}
        self.last: Dict[int, float] = {}
        self.finished: List[object] = []
        self.tpot: List[float] = []
        self.tokens = 0
        self.prefilled = 0
        self.sent = 0
        now = time.perf_counter()
        for _ in range(clients):
            self.send(now)

    def send(self, now: float):
        from repro.serve import Request

        if self.next >= len(self.specs):
            raise RuntimeError("the request pool ran out; raise request_pool")
        prompt, new, temp, top_k = self.specs[self.next]
        req = Request(rid=self.next, prompt=prompt, max_new_tokens=new,
                      temperature=temp, top_k=top_k)
        self.next += 1
        self.sent += 1
        self.live[req.rid], self.seen[req.rid], self.last[req.rid] = req, 0, now
        self.eng.submit(req)

    def tick(self, resend: bool) -> int:
        """One engine step; returns the tokens it delivered."""
        self.eng.step()
        now = time.perf_counter()
        got = 0
        for rid, req in list(self.live.items()):
            n = len(req.output) - self.seen[rid]
            if n and not self.seen[rid]:
                self.prefilled += len(req.prompt)
            if n:
                self.tpot.append((now - self.last[rid]) / n)
                self.last[rid], self.seen[rid] = now, len(req.output)
                got += n
            if req.done:
                del self.live[rid]
                self.finished.append(req)
                if resend:
                    self.send(now)
        self.tokens += got
        return got

    def live_kv_entries(self) -> int:
        return sum(len(r.prompt) + len(r.output) for r in self.live.values())


def sample_finished(finished, count: int, rng) -> list:
    """The longest finished greedy request and ``count - 1`` others drawn."""
    greedy = [r for r in finished if r.temperature <= 0]
    if not greedy:
        return []
    longest = max(greedy, key=lambda r: len(r.output))
    rest = [r for r in greedy if r is not longest]
    k = min(len(rest), count - 1)
    return [longest] + [rest[i] for i in rng.choice(len(rest), k, replace=False)]


def served_gap(cell, seed: int, sample, fmt=None) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position, over ``sample`` (inf if empty).  With
    ``fmt`` the control stands in for the program: the token it puts first
    at each served position is judged instead."""
    if not sample:
        return math.inf
    tr = cell.traffic
    ref = ServeReference(cell.config, keys(seed)[0], tr["weights"])
    ctl = ServeReference(cell.config, keys(seed)[0], tr["weights"], fmt=fmt) if fmt else None
    worst = 0.0
    for r in sample:
        run = r.prompt + r.output[:-1]
        chunks = ref.logits(run)
        if ctl is None:
            picks = served_picks(r.output, len(r.prompt), len(chunks))
        else:
            picks = control_picks(ctl.logits(run), len(r.prompt), len(r.output))
        worst = max(worst, widest_gap(chunks, picks))
    return worst


def run(cell, seed: int, seconds: float, trace: bool, devs, t0: float) -> Outcome:
    program.setup_compile_cache()
    c, tr = cell.config, cell.traffic
    eng = build_engine(cell, seed)
    warm_up(eng, tr, c["vocab_size"])
    specs = request_specs(seed, tr["request_pool"], c["vocab_size"], tr)

    start = time.perf_counter()
    setup_s = start - t0
    loop = ClosedLoop(eng, specs, tr["clients"])
    trace_at = min(tr["trace_after_s"], seconds / 2)
    summary, counts = None, {}
    while True:
        if trace and summary is None and time.perf_counter() - start >= trace_at:
            gen0, pre0, kv = loop.tokens, loop.prefilled, 0
            recorder = trace_lib.Recorder(tempfile.mkdtemp(prefix="bench-trace-"))
            with recorder:
                for _ in range(tr["trace_ticks"]):
                    kv += loop.live_kv_entries()
                    with jax.profiler.TraceAnnotation("bench.tick"):
                        loop.tick(resend=True)
            summary = recorder.summary()
            counts = {
                "ticks": tr["trace_ticks"],
                "generated_tokens": loop.tokens - gen0,
                "prefilled_tokens": loop.prefilled - pre0,
                "kv_entries_per_tick": kv / tr["trace_ticks"],
            }
        else:
            loop.tick(resend=True)
        if time.perf_counter() - start >= seconds and (summary or not trace):
            break
    window_s = time.perf_counter() - start
    tokens = loop.tokens
    peak = device.peak_bytes(devs)

    grace = time.perf_counter()
    while not any(r.temperature <= 0 for r in loop.finished) and loop.live and \
            time.perf_counter() - grace < DRAIN_GRACE_S:
        loop.tick(resend=False)
    finished, sent = list(loop.finished), loop.sent
    tpot = np.asarray(loop.tpot)
    del loop, eng
    gc.collect()

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    value = served_gap(cell, seed, sample_finished(finished, tr["check_requests"], rng))
    limit = cell.limits.get("served_gap")
    correct = limit is not None and value <= limit
    failed = sum(len(r.output) != r.max_new_tokens for r in finished)

    shapes = list(leaf_shapes(c).values())
    counts.update({
        "weight_bytes": flops.weight_bytes_at_rest(shapes, tr["weights"]),
        "kv_bytes_per_entry": flops.kv_bytes_per_entry(c),
        "drain_every": tr["drain_every"],
        "matmul_params": flops.matmul_params(c),
    })
    return Outcome(
        correct=correct and failed == 0,
        attempted=sent,
        failed=failed,
        end_to_end={
            "setup_s": setup_s,
            "serve_tokens_per_s": tokens / window_s,
            "tpot_p95_ms": 1e3 * float(np.percentile(tpot, 95)) if tpot.size else math.inf,
            "peak_hbm_gib": peak / 2**30,
        },
        compared={"served_gap": {"value": value, "limit": limit}},
        memory_peak_bytes=peak,
        counts=counts,
        trace=summary,
    )
