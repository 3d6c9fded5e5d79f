"""Throughput-oriented continuous-batching serving engine.

The engine owns ``max_batch`` fixed cache slots and drives them through the
admit → prefill → decode → retire loop:

* **admit/prefill** — queued requests are packed into free slots and their
  prompts consumed in ONE forward pass (``prefill_with_cache``): the batch
  is right-padded to a power-of-two bucket (bounded recompiles), K/V and
  recurrent states land in a fresh cache, and the result is merged into the
  live cache only at admitted slots — so a slot's history is rebuilt from
  scratch on every backfill and stale state from the previous occupant
  cannot survive. The first token of each stream is sampled from the
  prefill logits on device.
* **decode** — a jitted ``lax.scan`` over ``drain_every`` decode steps.
  The caches are donated to it and stay in one buffer: each step writes
  only the new token's K, V and position into every layer's stack.
  Sampling (temperature / top-k, Gumbel-max) happens on device with
  counter-based Threefry streams keyed by (engine seed, request id), so the
  host syncs ONCE per ``drain_every`` tokens (one small (N, B) transfer)
  instead of every tick — the host-sync-every-N contract.
* **retire** — at each drain the host walks the freshly generated tokens,
  finishes streams on EOS / ``max_new_tokens`` (tokens a dead slot decoded
  past its end inside the chunk are discarded), frees their slots, and
  backfills from the queue on the next tick.

Weights are served in the format picked by ``weights=``: ``bf16`` casts of
the fp32 masters, or ``q4`` — 4-bit block-quantized ``QuantizedTensor``
leaves (B128/DE via ``core/quantizer.py``) that stay compressed in HBM and
are dequantized on use inside the jitted steps (``serve.weights``).

Sampled streams are reproducible and slot-order-invariant: the noise
counter is (request id, generated-token index), never the slot id or tick
(``serve.sampling``).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import (
    ModelConfig,
    decode_step,
    init_serve_cache,
    prefill_with_cache,
)
from repro.serve.sampling import request_key_words, sample_tokens
from repro.serve.weights import materialize, prepare_params, weight_report

__all__ = ["Request", "ServeEngine"]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0            # 0 = full vocab
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


def _bucket_len(n: int, lo: int = 16) -> int:
    """Next power of two >= n (>= lo): the static prefill width, so distinct
    prompt lengths share a handful of compiled prefill shapes."""
    b = lo
    while b < n:
        b *= 2
    return b


class ServeEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        max_batch: int = 4,
        s_max: int = 256,
        weights: str = "bf16",
        drain_every: int = 8,
        seed: int = 0,
    ):
        if cfg.family != "decoder" or cfg.input_mode != "tokens":
            raise ValueError("ServeEngine serves token-decoder archs only")
        self.cfg = cfg
        self.max_batch = max_batch
        self.s_max = s_max
        self.weights_mode = weights
        self.drain_every = drain_every
        self.seed = seed

        self.params = prepare_params(params, weights)
        self._master_struct = jax.eval_shape(lambda t: t, params)
        self.caches = init_serve_cache(cfg, max_batch, s_max)

        # Per-slot device-mirrored state (host copies are the authority;
        # device arrays are rebuilt from them at each dispatch).
        self.tokens = np.zeros((max_batch,), np.int32)   # last sampled token
        self.pos = np.zeros((max_batch,), np.int32)      # its absolute position
        self.kw = np.zeros((max_batch, 2), np.uint32)    # sampling key words
        self.gen_idx = np.zeros((max_batch,), np.int32)  # tokens sampled so far
        self.temp = np.zeros((max_batch,), np.float32)
        self.topk = np.zeros((max_batch,), np.int32)

        self.active: List[Optional[Request]] = [None] * max_batch
        self.queue: List[Request] = []

        B = max_batch

        def _prefill(params, caches, tokens, lengths, admit,
                     kw, temp, topk, cur_tok, cur_pos, cur_gen):
            with jax.named_scope(obs.MATERIALIZE):
                p = materialize(params)
            fresh = init_serve_cache(cfg, B, s_max)
            logits, fresh = prefill_with_cache(p, cfg, tokens, lengths, fresh)
            with jax.named_scope(obs.SAMPLE):
                first = sample_tokens(
                    logits, kw, jnp.zeros((B,), jnp.int32), temp, topk
                )

            def merge(new, old):
                mask = admit.reshape((1, B) + (1,) * (new.ndim - 2))
                return jnp.where(mask, new, old)

            caches = jax.tree_util.tree_map(merge, fresh, caches)
            tok = jnp.where(admit, first, cur_tok)
            pos = jnp.where(admit, lengths, cur_pos)
            gen = jnp.where(admit, 1, cur_gen)
            return caches, tok, pos, gen, first

        def _decode(params, caches, tokens, pos, kw, gen, temp, topk):
            with jax.named_scope(obs.MATERIALIZE):
                p = materialize(params)

            def body(carry, _):
                caches, tok, pos, gi = carry
                logits, caches = decode_step(p, cfg, caches, tok, pos)
                with jax.named_scope(obs.SAMPLE):
                    nxt = sample_tokens(logits, kw, gi, temp, topk)
                return (caches, nxt, pos + 1, gi + 1), nxt

            (caches, tok, pos, gen), toks = jax.lax.scan(
                body, (caches, tokens, pos, gen), None, length=drain_every
            )
            return caches, tok, pos, gen, toks  # toks: (N, B)

        self._prefill = jax.jit(_prefill)
        # the caches are donated: the chunk updates them in place, and the
        # arrays passed in are deleted once it is dispatched (``self.caches``
        # is always replaced by the result)
        self._decode = jax.jit(_decode, donate_argnums=(1,))

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        self.queue.append(req)

    def weight_bytes(self) -> dict:
        """Exact weight-memory accounting for the serving format
        (structural — computed from the master tree's shapes)."""
        return weight_report(self._master_struct, self.weights_mode)

    # ------------------------------------------------------------------
    def _admit_and_prefill(self) -> List[int]:
        """Fill free slots from the queue; one batched prefill for all."""
        with obs.span(obs.ADMIT) as span:
            admitted: List[int] = []
            for slot in range(self.max_batch):
                if self.active[slot] is None and self.queue:
                    req = self.queue.pop(0)
                    self.active[slot] = req
                    k0, k1 = request_key_words(self.seed, req.rid)
                    self.kw[slot] = (int(k0), int(k1))
                    self.temp[slot] = req.temperature
                    self.topk[slot] = req.top_k
                    admitted.append(slot)
            span.set_metadata(admitted=len(admitted))
            if not admitted:
                return admitted

            S = _bucket_len(max(len(self.active[s].prompt) for s in admitted))
            toks = np.zeros((self.max_batch, S), np.int32)
            lens = np.zeros((self.max_batch,), np.int32)
            admit = np.zeros((self.max_batch,), bool)
            for slot in admitted:
                p = self.active[slot].prompt
                toks[slot, : len(p)] = p
                lens[slot] = len(p)
                admit[slot] = True

        # every row is computed at the bucket's width, admitted or not
        with obs.span(obs.PREFILL, admitted=len(admitted),
                      prompt_tokens=int(lens.sum()), positions=toks.size):
            self.caches, tok, pos, gen, _ = self._prefill(
                self.params, self.caches,
                jnp.asarray(toks), jnp.asarray(lens), jnp.asarray(admit),
                jnp.asarray(self.kw), jnp.asarray(self.temp),
                jnp.asarray(self.topk), jnp.asarray(self.tokens),
                jnp.asarray(self.pos), jnp.asarray(self.gen_idx),
            )
            self.tokens = np.asarray(tok)
            self.pos = np.asarray(pos)
            self.gen_idx = np.asarray(gen)

            for slot in admitted:
                req = self.active[slot]
                req.output.append(int(self.tokens[slot]))
                self._maybe_retire(slot)
        return admitted

    def _maybe_retire(self, slot: int) -> None:
        req = self.active[slot]
        hit_eos = req.eos_id is not None and req.output and req.output[-1] == req.eos_id
        if hit_eos or len(req.output) >= req.max_new_tokens:
            req.done = True
            self.active[slot] = None  # slot backfills at the next tick

    # ------------------------------------------------------------------
    def _decode_args(self) -> tuple:
        return (
            self.params, self.caches,
            jnp.asarray(self.tokens), jnp.asarray(self.pos),
            jnp.asarray(self.kw), jnp.asarray(self.gen_idx),
            jnp.asarray(self.temp), jnp.asarray(self.topk),
        )

    def decode_hlo(self) -> str:
        """The optimized HLO text of the decode chunk, compiled for this
        engine's shapes (read back from the compile cache when it is on)."""
        return self._decode.lower(*self._decode_args()).compile().as_text()

    def step(self) -> bool:
        """One engine tick: admit+prefill, then decode ``drain_every``
        tokens on device and drain them. Returns False when idle."""
        with obs.span(obs.STEP):
            self._admit_and_prefill()
            if all(r is None for r in self.active):
                return False

            with obs.span(obs.DECODE):
                self.caches, tok, pos, gen, toks = self._decode(*self._decode_args())
            # ONE host sync per drain_every tokens: the (N, B) token block.
            with obs.span(obs.DRAIN):
                toks = np.asarray(toks)
                self.tokens = np.asarray(tok)
                self.pos = np.asarray(pos)
                self.gen_idx = np.asarray(gen)

            with obs.span(obs.RETIRE) as span:
                n_tok = toks.shape[0]
                delivered = discarded = retired = 0
                for slot in range(self.max_batch):
                    req = self.active[slot]
                    if req is None:
                        continue
                    for n in range(n_tok):
                        req.output.append(int(toks[n, slot]))
                        delivered += 1
                        self._maybe_retire(slot)
                        if self.active[slot] is None:
                            # chunk tokens past the end are discarded
                            retired += 1
                            discarded += n_tok - 1 - n
                            break
                span.set_metadata(delivered=delivered, discarded=discarded,
                                  retired=retired)
            return True

    def run(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.step() and not self.queue:
                break
