"""Batched LLM serving engine: prefill + decode with KV cache, plus a
continuous-batching-lite request queue.

The engine is the backend ``B`` that Krites fronts: every cache hit is a
skipped ``generate`` call. Works with any LMConfig (the 5 assigned archs
at full scale on TPU; smoke configs on CPU for the examples/tests).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import LMConfig
from repro.data.tokenizer import ByteTokenizer, EOS, PAD
from repro.models import transformer as tr


@dataclass
class EngineStats:
    prefills: int = 0
    decode_steps: int = 0
    generated_tokens: int = 0
    batches: int = 0
    # distinct (batch, prompt-length) prefill shapes and batch decode
    # shapes run so far: each is one compile of the whole layer stack
    compiles: int = 0


class StepTokens:
    """One step's sampled tokens, ``(rows,)`` int32, left on the device
    so that the next decode step can take them before the host has read
    them (``jnp.asarray`` gives the device array, through
    ``__jax_array__``). To a host reader they are a small numpy array:
    ``np.asarray``, indexing and ``copy`` work as on one, the first read
    copies them to the host, and a write (``tok[i] = v``) edits that
    host copy, which the next step then takes in their place."""

    __slots__ = ("_dev", "_host")

    def __init__(self, dev, host=None):
        self._dev, self._host = dev, host

    @property
    def unread(self) -> bool:
        """No host read has waited for these tokens yet."""
        return self._host is None

    def _read(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self._dev)
        return self._host

    def __jax_array__(self):
        if self._dev is None:
            self._dev = jnp.asarray(self._host)
        return self._dev

    def __array__(self, dtype=None, copy=None):
        host = self._read()
        return np.array(host, dtype) if copy else np.asarray(host, dtype)

    def __getitem__(self, i):
        return self._read()[i]

    def __setitem__(self, i, v):
        host = np.array(self._read())
        host[i] = v
        self._dev, self._host = None, host

    def copy(self) -> "StepTokens":
        # both halves are never written in place: sharing them is a copy
        return StepTokens(self._dev, self._host)


def _bucket(n: int, lo: int = 1) -> int:
    """Smallest power of two >= max(n, lo)."""
    return 1 << (max(n, lo) - 1).bit_length()


class LLMEngine:
    """Synchronous batched generate; thread-safe via internal lock.

    Batches are padded to a power-of-two row count (at least
    ``min_batch``) and prompts to a power-of-two length (at least 16
    tokens), so a stream of ragged requests compiles a bounded set of
    prefill/decode programs — ``stats.compiles`` counts them. A front
    end that never sends more than ``min_batch`` rows thus runs one
    decode program.

    Decoding is pipelined one step deep: the sampled tokens stay on
    the device and feed the next step, which is dispatched before the
    host reads them, so the device runs the steps back to back while
    the host does its bookkeeping. A batch of ``max_new_tokens`` takes
    the prefill's token and ``max_new_tokens - 1`` decode steps.

    Spans (``repro.tracing``): ``engine.batch`` per call, inside it
    ``engine.prefill`` (tokenize, prefill, first sample, the dispatch
    of decode step 1 and the read of the prefill's token) and one
    ``engine.decode`` per served decode step (the dispatch of the next
    step and the read of this step's tokens: one device step of wall
    time), each dispatch with ``engine.sample`` (the argmax, left on
    the device) as a child. Counters: ``engine.decode_steps_ahead``,
    steps dispatched before the tokens they consume were read on the
    host; ``engine.decode_steps_unserved``, steps in flight when every
    row had ended on EOS."""

    def __init__(self, cfg: LMConfig, params=None, seed: int = 0,
                 max_len: int = 256, temperature: float = 0.0,
                 min_batch: int = 1):
        self.cfg = cfg
        self.tok = ByteTokenizer()
        assert cfg.vocab_size >= self.tok.vocab_size
        self.params = params if params is not None else tr.init_params(
            cfg, jax.random.PRNGKey(seed))
        self.max_len = max_len
        self.temperature = temperature
        self.min_batch = min_batch
        self.stats = EngineStats()
        self._lock = threading.Lock()
        self._shapes: set = set()

        def prefill(params, tokens):
            return tr.prefill(cfg, params, tokens, max_len=max_len)

        def decode(params, cache, tokens):
            return tr.decode_step(cfg, params, cache, tokens)

        # named, so the profiler's programs read jit_prefill / jit_decode
        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)

    def generate_batch(self, prompts: List[str],
                       max_new_tokens: int = 32) -> List[str]:
        with self._lock, tracing.span("engine.batch", rows=len(prompts)):
            return self._generate(prompts, max_new_tokens)

    def _generate(self, prompts: List[str], max_new: int) -> List[str]:
        B = len(prompts)
        Bp = _bucket(B, self.min_batch)
        out = [[] for _ in range(B)]
        done = np.zeros(Bp, bool)
        done[B:] = True
        with tracing.span("engine.prefill", rows=B):
            in_len = _bucket(max(len(p.encode()) + 2 for p in prompts), 16)
            in_len = min(in_len, self.max_len - max_new)
            toks = np.stack([self.tok.encode(p, max_len=in_len)
                             for p in prompts])
            # pad rows repeat the first prompt; their tokens are dropped
            toks = np.concatenate([toks, np.repeat(toks[:1], Bp - B, 0)])
            self._shapes |= {("prefill", Bp, in_len), ("decode", Bp)}
            self.stats.compiles = len(self._shapes)
            logits, cache = self._prefill(self.params, jnp.asarray(toks))
            self.stats.prefills += B
            tok = self._sample(logits)
            if max_new > 1:
                cache, nxt = self._step(cache, tok)
            if max_new:
                self._take(tok, out, done)
        # token t is served from the step dispatched when token t - 1
        # was still unread: the device never waits on the host's read
        for t in range(1, max_new):
            if done.all():       # the step in flight is not served
                tracing.add("engine.decode_steps_unserved", 1)
                break
            tok = nxt
            with tracing.span("engine.decode", rows=B):
                if t + 1 < max_new:
                    cache, nxt = self._step(cache, tok)
                self._take(tok, out, done)
        self.stats.generated_tokens += sum(len(o) for o in out)
        self.stats.batches += 1
        return [self.tok.decode(o) for o in out]

    def _step(self, cache, tok):
        """Dispatch one decode step on ``tok``, normally before the host
        has read it; the step's own tokens stay on the device."""
        if isinstance(tok, StepTokens) and tok.unread:
            tracing.add("engine.decode_steps_ahead", 1)
        logits, cache = self._decode(self.params, cache, jnp.asarray(tok))
        self.stats.decode_steps += 1
        return cache, self._sample(logits)

    @staticmethod
    def _take(tok, out, done):
        """Read a step's tokens on the host and serve them to the rows
        that have not ended."""
        tok = np.asarray(tok)
        for b in range(len(out)):
            if not done[b]:
                out[b].append(int(tok[b]))
                done[b] |= int(tok[b]) == EOS

    def _sample(self, logits) -> StepTokens:
        """Dispatch the argmax; the tokens stay on the device."""
        with tracing.span("engine.sample", rows=logits.shape[0]):
            if self.temperature > 0:
                logits = logits / self.temperature + np.random.gumbel(
                    size=logits.shape)
            return StepTokens(jnp.argmax(logits, -1).astype(jnp.int32))

    def generate(self, prompt: str, max_new_tokens: int = 32) -> str:
        return self.generate_batch([prompt], max_new_tokens)[0]


class BatchingFrontend:
    """Continuous-batching-lite: coalesce concurrent requests into
    engine batches (max_batch or max_wait_ms, whichever first). The
    queue/collector machinery is the shared ``_MicroBatcher`` — the same
    one ``CacheRouter`` uses over ``Policy.serve_batch``."""

    def __init__(self, engine: LLMEngine, max_batch: int = 8,
                 max_wait_ms: float = 5.0, max_new_tokens: int = 32):
        from repro.serving.router import _MicroBatcher
        self.engine = engine
        self.max_new = max_new_tokens
        self._mb = _MicroBatcher(self._serve, max_batch, max_wait_ms / 1e3,
                                 name="batching-frontend")

    def submit(self, prompt: str, timeout_s: float = 60.0) -> str:
        p = self._mb.submit(prompt)
        p.done.wait(timeout_s)
        return p.result if p.result is not None else ""

    def submit_many(self, prompts: List[str],
                    timeout_s: float = 60.0) -> List[str]:
        """Enqueue a pre-formed group and block until every answer is
        in. Usable directly as a policy's ``backend_batch_fn``: the
        group reaches the collector at once, so a cache micro-batch's
        misses become one engine prefill instead of ``len(prompts)``
        serialized ``submit`` calls."""
        pending = [self._mb.submit(p) for p in prompts]
        for p in pending:
            p.done.wait(timeout_s)
        return [p.result if p.result is not None else "" for p in pending]

    def _serve(self, batch):
        results = self.engine.generate_batch(
            [p.prompt for p in batch], self.max_new)
        for p, r in zip(batch, results):
            p.result = r

    def stop(self):
        self._mb.stop()
