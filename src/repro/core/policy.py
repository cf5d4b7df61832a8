"""Live (host-level) tiered semantic cache policies.

``BaselinePolicy`` = Algorithm 1 (GPTCache-style static thresholds).
``KritesPolicy``   = Algorithm 2: identical serving path + grey-zone
                     trigger feeding the async VerifyAndPromote pool.

These wrap the functional JAX tiers for production serving (the trace
simulator in core/simulate.py is the batched twin used for evaluation).
The backend, embedder and judge are injected callables, so the same policy
fronts an LLM engine, a GNN, or a recsys scorer (DESIGN.md §5). The
static-tier lookup is likewise injectable: pass ``index=`` (a
``FlatIndex`` or — for million-entry tiers — an ``IVFIndex``, DESIGN.md
§11) and the serving path routes its static top-1 through it; the
default (None) stays the exact flat/simsearch path.

One procedure makes every serving decision: ``serve_batch(prompts)``
(DESIGN.md §7). It embeds the whole micro-batch at once, does ONE fused
static-tier lookup via ``kernels/simsearch`` (Pallas on TPU, jnp
reference elsewhere) and ONE masked dynamic-tier lookup against the tier
snapshot, then resolves rows in request order, so a batch of N equals N
batches of one. Misses go to the backend as a single batch (amortized
prefill), grey-zone triggers are bulk-enqueued to the VerifyAndPromote
pool, and all tier mutations land as one fused scatter at the end of the
batch. ``serve(prompt)`` is a batch of one.

The policy keeps small host-side mirrors of the dynamic tier's decision
metadata (valid / last_used / static_origin / written_at) so per-row
bookkeeping (LRU slot choice, provenance reads, the promotion LWW
guard) never costs a device round-trip; the functional JAX tier stays
the source of truth for state that is looked up, checkpointed, or
sharded. Both mutation paths (the serving batch, async promotion)
update both under ``dyn_lock``.

**Multi-device serving (DESIGN.md §13).** Pass ``mesh=`` and the whole
serving path becomes mesh-aware: the static top-1 runs row-sharded
through ``sharded_cosine_topk`` (or inject a ``ShardedIVFIndex`` via
``index=`` for the ANN twin), the dynamic lookup through the
row-sharded masked top-1 with global-slot merge, and every tier write —
batched ``_bulk_insert``, LRU touches, async promotion —
lands on the owning shard as a shard-local scatter without ever
gathering the tier. Serving decisions are identical to the
single-device path on any shard count (test-enforced): scores are
bit-equal and the shard merge keeps the lowest-index tie rule.
"""
from __future__ import annotations

import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import adaptive as A
from repro.core import tiers as T
from repro.core.async_queue import VerifyAndPromotePool
from repro.core.exact_tier import ExactTier, canonicalize
from repro.core.judge import APPROVE, REJECT, REWRITE, Verdict, as_verdict
from repro.index.flat import l2_normalize, masked_cosine_topk

_BIG = np.int64(2**30)   # host twin of tiers.BIG (LRU key for invalid rows)


@jax.jit
def _masked_dyn_topk(emb, valid, q):
    """Dynamic-tier top-1 through the public masked index path. Tier
    rows are L2-normalized on insert, so ``corpus_normalized=True``
    skips the per-lookup corpus renormalization (a full (C, d) pass
    the old path paid on every call). Shared across policies: one
    compile per (capacity, batch) shape."""
    vals, idx = masked_cosine_topk(q, emb, valid, k=1,
                                   corpus_normalized=True)
    return vals[:, 0], idx[:, 0]


@jax.jit
def _promote_lookup(dyn: T.DynamicTier, q):
    """A promotion's dedup top-1 over the live tier: the expression of
    ``tiers.dynamic_lookup`` (masked matmul, argmax) as one compiled
    program, so the host reads ``(score, slot)`` after one dispatch.
    It is not ``_masked_dyn_topk``: that program is the serve path's
    lookup, which promotions leave to the serving batches."""
    return T.dynamic_lookup(dyn, q)


@jax.jit
def _bulk_insert(dyn: T.DynamicTier, V, slots, rows, ts, cls, exps=None
                 ) -> T.DynamicTier:
    """Scatter a batch's inserts into the tier in one fused update.
    Callers pad ``slots``/``rows``/``ts``/``cls``/``exps`` to a fixed
    length by repeating their first entry (identical values, so the
    duplicate scatter is benign) — keeping shapes static across
    batches. ``exps=None`` means no per-entry expiry (0), matching the
    ``sharded_bulk_insert`` twin."""
    if exps is None:
        exps = jnp.zeros_like(jnp.asarray(ts, jnp.int32))
    return dyn._replace(
        emb=dyn.emb.at[slots].set(V[rows]),
        cls=dyn.cls.at[slots].set(cls),
        answer_ref=dyn.answer_ref.at[slots].set(jnp.int32(-1)),
        static_origin=dyn.static_origin.at[slots].set(False),
        valid=dyn.valid.at[slots].set(True),
        written_at=dyn.written_at.at[slots].set(ts),
        expires_at=dyn.expires_at.at[slots].set(exps))


def _pad_to(arr: np.ndarray, n: int) -> np.ndarray:
    if len(arr) == n:
        return arr
    return np.concatenate([arr, np.repeat(arr[:1], n - len(arr), axis=0)])


def _usable_rows(V_np: np.ndarray) -> np.ndarray:
    """Which rows of an already-normalized (B, d) block are servable
    cache keys. ``l2_normalize`` maps a zero embedding to zero (its
    cosine against everything is 0, so argmax picks an arbitrary row)
    and passes NaN/inf through — and a non-finite key *inserted* into
    the tier poisons every later argmax over it. A good normalized row
    has unit norm, so ``> 0.5`` cleanly separates degenerate rows
    without chasing float error."""
    return np.isfinite(V_np).all(axis=-1) \
        & (np.linalg.norm(V_np, axis=-1) > 0.5)


@dataclass
class ServeResult:
    answer: object
    served_by: str   # 'l1' | 'static' | 'dynamic' | 'rewritten' | 'backend'
    static_origin: bool
    similarity: float
    latency_s: float
    # meta flags the freshness layer sets (DESIGN.md §16):
    #   "stale": True   — volatile hit whose content predates the
    #                     current drift epoch
    #   "bypass": "volatile" — served backend-only, cache skipped
    meta: dict = field(default_factory=dict)


class BaselinePolicy:
    """Algorithm 1. The dynamic tier is guarded by a lock so async
    promotions (Krites subclass) can't race the serving loop."""

    def __init__(self, cfg: T.CacheConfig, static_tier: T.StaticTier,
                 static_answers, embed_fn: Callable,
                 backend_fn: Callable, d: int, *,
                 embed_batch_fn: Optional[Callable] = None,
                 backend_batch_fn: Optional[Callable] = None,
                 index=None, dyn_index=None, static_texts=None,
                 mesh=None, shard_axis: str = "model", fused=None,
                 l1=None, freshness=None, adaptive=None):
        self.cfg = cfg
        self.static = static_tier
        # online threshold controller (core/adaptive.py, DESIGN.md §17):
        # when set, every serving path reads its live per-segment
        # (tau_static, tau_dynamic) under dyn_lock instead of the pinned
        # cfg values, and served requests are recorded into its bounded
        # window. None (or a frozen controller) keeps serving
        # bit-identical to the pinned-threshold policy.
        self.adaptive = adaptive
        # L1 exact-match front tier (DESIGN.md §16): an ExactTier, an
        # int capacity, or None (off). Probed on the canonical prompt
        # BEFORE the embedder — an L1 hit skips embed + both semantic
        # lookups entirely. Composable with every lookup config below
        # (index/dyn_index/mesh/fused): it sits strictly in front.
        self.l1 = ExactTier(capacity=l1) if isinstance(l1, int) else l1
        # staleness-risk layer (core/freshness.py): volatile-query
        # bypass, per-class TTLs for L1 + write-back entries, and the
        # drift clock for stale accounting. None = classic behaviour.
        self.freshness = freshness
        self._l1_hits = 0
        self._l1_bypass = 0
        self._stale_serves = 0
        self._ttl_evictions = 0
        # flips True at the first write that stamps a finite expiry; the
        # eager expiry sweep is a no-op until then, so TTL-free serving
        # pays nothing
        self._ttl_active = False
        # injectable static-tier index (FlatIndex/IVFIndex/
        # ShardedIVFIndex, DESIGN.md §11/§13); None = exact flat lookup
        self.index = index
        # injectable fused serve path (kernels/fused_serve, DESIGN.md
        # §15): ONE dispatch for the static IVF probe + the masked
        # dynamic top-1. Flag-gated and exclusive — it replaces both
        # lookups, so composing it with another index/mesh config would
        # silently shadow that config's lookup semantics.
        if fused is not None and (index is not None
                                  or dyn_index is not None
                                  or mesh is not None):
            raise ValueError(
                "fused= replaces both tier lookups; it cannot be "
                "combined with index=, dyn_index= or mesh=")
        self.fused = fused
        # injectable dynamic-tier index (SegmentedIndex, DESIGN.md §12);
        # None = exact flat masked scan. "segmented" builds the default.
        if dyn_index == "segmented":
            from repro.index.segmented import SegmentedIndex
            dyn_index = SegmentedIndex(cfg.capacity, d)
        self.dyn_index = dyn_index
        self.static_answers = static_answers
        # prompt texts of the curated entries, aligned with the tier
        # rows: the judge verifies on the (q_text, h_text, answer)
        # triple, so grey-zone payloads need the neighbor's real text
        self.static_texts = list(static_texts) if static_texts is not None \
            else None
        self.embed_fn = embed_fn
        self.backend_fn = backend_fn
        self.embed_batch_fn = embed_batch_fn
        self.backend_batch_fn = backend_batch_fn
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.dyn = T.make_dynamic_tier(cfg.capacity, d)
        self.dyn_answers: list = [None] * cfg.capacity
        self.dyn_lock = threading.Lock()
        self.t = 0
        self.events: list = []
        # host-side copies of the (immutable) static-tier metadata: the
        # serving loop indexes these per request, which must not cost a
        # device round-trip each time
        self._static_ref_np = np.asarray(static_tier.answer_ref)
        self._static_cls_np = np.asarray(static_tier.cls)
        # host mirrors of the dynamic tier's decision metadata
        self._valid_np = np.zeros(cfg.capacity, bool)
        self._last_used_np = np.zeros(cfg.capacity, np.int64)
        self._static_origin_np = np.zeros(cfg.capacity, bool)
        self._written_at_np = np.zeros(cfg.capacity, np.int64)
        self._expires_np = np.zeros(cfg.capacity, np.int64)
        # rewrite provenance (DESIGN.md §18): True for entries whose
        # answer is a REWRITE-verdict tailored variant, not the curated
        # static text. Device twin: ``answer_ref == -2`` sentinel — that
        # column is what snapshots/restores derive this mirror from.
        self._rewritten_np = np.zeros(cfg.capacity, bool)
        if mesh is None:
            self._touch_many = jax.jit(T.touch_many)
            self._bulk_insert_fn = _bulk_insert
            # one compiled row write for promotions; the tier is not
            # donated: snapshots, WAL replays and readers of an earlier
            # tier keep its buffers
            self._write_fn = jax.jit(T._write)
        else:
            self._init_mesh(d)

    def _init_mesh(self, d: int) -> None:
        """Mesh mode (DESIGN.md §13): place the tiers row-sharded and
        swap every lookup/scatter primitive for its shard-routed twin
        from ``index/sharded.py``. The host mirrors and all decision
        logic are unchanged — only the device primitives differ — which
        is what keeps sharded serving decision-identical."""
        from repro.index import sharded as Sh
        mesh, axis = self.mesh, self.shard_axis
        n_shards = mesh.shape[axis]
        if self.dyn_index is not None:
            raise ValueError(
                "dyn_index + mesh is not supported yet: the segmented "
                "index reranks against a host-managed layout; the "
                "sharded dynamic path uses the exact row-sharded "
                "masked scan (DESIGN.md §13)")
        assert self.cfg.capacity % n_shards == 0, \
            (self.cfg.capacity, n_shards)
        # static corpus: pad to a shard multiple with copies of row 0
        # (never returned — stable merge prefers the real row) and keep
        # it device-resident row-sharded; host metadata mirrors keep
        # their original (unpadded) length. An injected index (e.g.
        # ShardedIVFIndex) owns the static lookup instead, so skip the
        # duplicate device-resident corpus copy then.
        if self.index is None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._static_mesh_tier = self.static._replace(
                emb=jax.device_put(
                    Sh.pad_rows(self.static.emb, n_shards),
                    NamedSharding(mesh, P(axis, None))))
            self._sh_static_fn = jax.jit(functools.partial(
                T.static_lookup_batch, mesh=mesh, shard_axis=axis))
        self.dyn = Sh.shard_dynamic_tier(self.dyn, mesh, axis)
        self._sh_dyn_fn = jax.jit(functools.partial(
            T.dynamic_lookup_batch, mesh=mesh, shard_axis=axis))
        self._touch_many = jax.jit(functools.partial(
            Sh.sharded_touch_many, mesh=mesh, axis=axis))
        self._bulk_insert_fn = jax.jit(functools.partial(
            Sh.sharded_bulk_insert, mesh=mesh, axis=axis))
        self._write_fn = jax.jit(functools.partial(
            Sh.sharded_dyn_write, mesh=mesh, axis=axis))

    def _serve_static(self, idx: int):
        return self.static_answers[int(self._static_ref_np[idx])]

    def _static_topk_batch(self, V: jax.Array):
        """Static-tier top-1 for a (B, d) block through whichever path
        is configured: injected index, sharded exact scan, or the fused
        single-device kernel."""
        if self.index is not None:
            return T.static_lookup_batch(self.static, V, index=self.index)
        if self.mesh is not None:
            return self._sh_static_fn(self._static_mesh_tier, V)
        return T.static_lookup_batch(self.static, V)

    def _dyn_topk(self, dyn: T.DynamicTier, q: jax.Array):
        """Dynamic-tier top-1 for a (B, d) query block: exact masked
        matmul, its row-sharded twin (DESIGN.md §13), or the injected
        segmented index (DESIGN.md §12)."""
        if self.dyn_index is not None:
            vals, idx = self.dyn_index.topk(q, dyn.emb, k=1)
            return vals[:, 0], idx[:, 0]
        if self.mesh is not None:
            return self._sh_dyn_fn(dyn, q)
        return _masked_dyn_topk(dyn.emb, dyn.valid, q)

    def lookup_batch(self, V: jax.Array):
        """The two tier lookups :meth:`serve_batch` makes, on their own:
        static and dynamic top-1 of a normalized (B, d) block through
        the configured path (fused, injected indexes, sharded or flat),
        against the current dynamic tier. Returns host arrays
        ``(s_static, h_idx, s_dyn, j)`` and the tier they were taken
        on — what a reference check compares with."""
        with self.dyn_lock:
            snap = self.dyn
            if self.fused is not None:
                out = T.serve_lookup_batch(self.static, snap, V,
                                           self.fused)
            else:
                out = (*self._static_topk_batch(V),
                       *self._dyn_topk(snap, V))
            return jax.device_get(out), snap

    def _host_lru_slot(self) -> int:
        """Host twin of tiers._lru_slot over the mirrored metadata."""
        key = np.where(self._valid_np, self._last_used_np, -_BIG)
        return int(key.argmin())

    # ------------------------------------------------------------------
    # adaptive thresholds (core/adaptive.py, DESIGN.md §17)
    # ------------------------------------------------------------------

    def _live_taus(self, prompt: str):
        """The (tau_static, tau_dynamic, segment) this request serves
        under: the controller's live per-segment operating point, or
        the pinned cfg values (segment −1) without a controller.
        Called under ``dyn_lock``: the threshold pair is read there,
        the one source of truth the serving batch shares with the
        controller's adaptation writes."""
        if self.adaptive is None:
            return self.cfg.tau_static, self.cfg.tau_dynamic, -1
        seg = A.segment_of(prompt)
        return (self.adaptive.tau_static[seg],
                self.adaptive.tau_dynamic[seg], seg)

    def _adapt_record(self, v_np, meta, h_idx, seg, res) -> None:
        """Append a served semantic request of segment ``seg`` to the
        controller window. Called under ``dyn_lock``, once the batch's
        backend call has returned: a failed batch served nobody and
        records nothing. The outcome label starts as the
        caller-declared class (``meta['cls']``), falling back to the
        static neighbor's class; judge verdicts / error feedback
        rewrite it later via the seq stamped into
        ``res.meta['adapt_seq']``."""
        label = int((meta or {}).get("cls", -1))
        if label < 0:
            label = int(self._static_cls_np[h_idx])
        res.meta["adapt_seq"] = self.adaptive.record(v_np, label, seg)
        res.meta["segment"] = seg

    def _maybe_adapt(self) -> None:
        """Serve-call-boundary adaptation check. Must be called with
        ``dyn_lock`` released — the controller snapshots and installs
        under the lock itself and runs the shadow sweep outside it. It
        runs once per :meth:`serve_batch` call: a batch of one (every
        :meth:`serve`) checks after each request, the reference twin's
        cadence; a batch of N may overshoot ``adapt_every`` by up to
        N−1 records — the same deliberate batching relaxation as the L1
        write-back order."""
        if self.adaptive is not None:
            self.adaptive.maybe_adapt(self.dyn_lock, self.static.emb,
                                      self.static.cls)

    # -- hook for Krites (a no-op in the baseline) -------------------------
    def _after_static_miss_batch(self, rows) -> None:
        return

    def serve(self, prompt: str, meta: Optional[dict] = None) -> ServeResult:
        """Serve one request: a batch of one through :meth:`serve_batch`,
        the one implementation of the decision procedure."""
        return self.serve_batch([prompt], [meta])[0]

    def _mirror_write(self, slot: int, now: int, static_origin: bool,
                      written_at: Optional[int] = None,
                      expires: int = 0, rewritten: bool = False):
        """Host twin of a tier row write. ``now`` is the LRU clock;
        ``written_at`` (the LWW clock) defaults to it, but async
        promotions pass their enqueue time — same split as
        ``tiers._write``. ``expires`` stamps the per-entry expiry
        mirror (0 = never); ``rewritten`` marks a REWRITE-verdict
        tailored variant (DESIGN.md §18)."""
        self._valid_np[slot] = True
        self._last_used_np[slot] = now
        self._static_origin_np[slot] = static_origin
        self._written_at_np[slot] = now if written_at is None \
            else written_at
        self._expires_np[slot] = expires
        self._rewritten_np[slot] = rewritten
        if expires > 0:
            self._ttl_active = True

    # ------------------------------------------------------------------
    # freshness layer (DESIGN.md §16)
    # ------------------------------------------------------------------

    def _expire_mirrors(self, now: int) -> np.ndarray:
        """Flip the host mirrors of the dynamic-tier entries expired at
        tick ``now`` (expired iff ``now > expires_at > 0``): valid,
        expiry and rewrite bits, the answers, the eviction counter, and
        a tombstone in any injected dynamic index (the one mutation it
        can't observe through ``record_write``). Called under
        ``dyn_lock``. Returns the expired slots; the caller owns their
        device scatter."""
        if not self._ttl_active:
            return np.zeros(0, np.int64)
        dead = np.nonzero(self._valid_np & (self._expires_np > 0)
                          & (self._expires_np < now))[0]
        self._valid_np[dead] = False
        self._expires_np[dead] = 0
        self._rewritten_np[dead] = False
        for s in dead:
            if self.dyn_index is not None:
                self.dyn_index.invalidate(int(s))
            self.dyn_answers[int(s)] = None
        self._ttl_evictions += len(dead)
        return dead

    def _sweep_expired_locked(self, now: int) -> int:
        """Eagerly invalidate dynamic-tier entries past their
        ``expires_at``, mirrors and device tier at once — the host twin
        of ``tiers.evict_expired(tier, now)``. Called under ``dyn_lock``
        at the head of a promotion and by a restore, so neither sees an
        expired row. Returns how many entries died."""
        dead = self._expire_mirrors(now)
        if len(dead):
            idx = jnp.asarray(dead)
            self.dyn = self.dyn._replace(
                valid=self.dyn.valid.at[idx].set(False),
                expires_at=self.dyn.expires_at.at[idx].set(0))
        return len(dead)

    def _is_volatile(self, prompt: str) -> bool:
        return self.freshness is not None \
            and self.freshness.is_volatile(prompt)

    def _entry_expiry(self, prompt: str, now: int) -> int:
        """Per-entry expiry stamp for a cache write at tick ``now``:
        the freshness policy's class TTL, else the legacy global
        ``cfg.ttl`` (0 = never)."""
        if self.freshness is not None:
            return self.freshness.expires_at(prompt, now)
        return now + self.cfg.ttl if self.cfg.ttl > 0 else 0

    def _mark_stale(self, res: ServeResult, volatile: bool,
                    content_t: int, now: int) -> None:
        """Drift-clock stale accounting for a served hit (never for
        backend answers — those are fresh by construction)."""
        if self.freshness is None or res.served_by == "backend":
            return
        if self.freshness.is_stale(volatile, content_t, now):
            res.meta["stale"] = True
            self._stale_serves += 1

    # ------------------------------------------------------------------
    # batched serving path
    # ------------------------------------------------------------------

    def _embed_batch(self, prompts: Sequence[str]) -> jax.Array:
        if self.embed_batch_fn is not None:
            emb = self.embed_batch_fn(prompts)
        else:
            batch = getattr(self.embed_fn, "batch", None)
            emb = batch(list(prompts)) if batch is not None else \
                np.stack([np.asarray(self.embed_fn(p)) for p in prompts])
        return l2_normalize(jnp.asarray(emb, jnp.float32))

    def _backend_batch(self, prompts: List[str]) -> List[object]:
        if self.backend_batch_fn is not None:
            return list(self.backend_batch_fn(prompts))
        return [self.backend_fn(p) for p in prompts]

    def _snap_best_excluding(self, snap: T.DynamicTier, v, exclude):
        """Masked top-1 over the batch-start snapshot with ``exclude``d
        slots removed — the rare repair when an intra-batch insert evicts
        the snapshot argmax of a later row."""
        excl = np.zeros(self.cfg.capacity, bool)
        excl[list(exclude)] = True
        s, j = jax.device_get(_masked_dyn_topk(
            snap.emb, jnp.logical_and(snap.valid, jnp.asarray(~excl)),
            v[None]))
        return float(s[0]), int(j[0])

    def serve_batch(self, prompts: Sequence[str],
                    metas: Optional[Sequence[Optional[dict]]] = None
                    ) -> List[ServeResult]:
        """Serve a micro-batch: the one implementation of the serving
        decision (Algorithms 1 and 2). A batch of N equals, request for
        request, N batches of one (same answers, served_by,
        static_origin and promotions), and the baseline's decisions
        equal the numpy oracle's (``tests/ref_policy.py``); the fast
        primitives are batched instead of per-row.

        The dynamic-tier lock is held for the whole batch (backend call
        included), so concurrent promotions land between batches — they
        are asynchronous anyway, and this keeps the in-batch decision
        sequence deterministic.

        If the batched backend call raises, the batch's inserts are
        rolled back (no answerless cache entries) and the exception
        propagates; hits decided before the failure keep their LRU
        touches.

        Freshness front (DESIGN.md §16): volatile-bypass rows and L1
        exact-match hits are resolved BEFORE the embedder runs — only
        the remaining rows are embedded and looked up, so a pure-repeat
        batch costs zero embed calls and zero tier dispatches. Ticks
        are assigned to every row (front-resolved or not) in request
        order, so decisions equal those of batches of one. One
        deliberate relaxation: L1 write-backs land at the end of the
        batch, so under L1 *capacity pressure within a single batch* the
        LRU eviction order can differ from batches of one (the semantic
        decisions never do).

        Each call is one serve batch to ``repro.tracing``: the span
        ``policy.serve_batch`` with its steps as child spans.
        """
        if not prompts:
            return []
        with tracing.span("policy.serve_batch", rows=len(prompts)):
            return self._serve_batch(prompts, metas)

    def _serve_batch(self, prompts, metas) -> List[ServeResult]:
        t0 = time.monotonic()
        B = len(prompts)
        metas = list(metas) if metas is not None else [None] * B
        fresh = self.freshness

        # --- freshness front: resolve bypass + L1 rows pre-embedding ---
        front: dict = {}     # row -> ("bypass",)|("hit", entry)|("dup", p)
        keys: List[Optional[str]] = [None] * B
        vol = [False] * B
        exp_of = [0] * B     # L1 expiry stamp for producer rows
        with tracing.span("policy.front", rows=B):
            if fresh is not None or self.l1 is not None:
                pend: dict = {}  # canon key -> (producer row, expires_at)
                for i in range(B):
                    ti = self.t + i + 1
                    volatile = fresh is not None \
                        and fresh.is_volatile(prompts[i])
                    vol[i] = volatile
                    if volatile and fresh.volatile_bypass:
                        front[i] = ("bypass",)
                        continue
                    if self.l1 is None:
                        continue
                    k = canonicalize(prompts[i])
                    keys[i] = k
                    e = self.l1.get(k, ti)
                    if e is not None:
                        front[i] = ("hit", e)
                    elif k in pend and (pend[k][1] == 0
                                        or ti <= pend[k][1]):
                        front[i] = ("dup", pend[k][0])
                    else:
                        exp_of[i] = self._entry_expiry(prompts[i], ti)
                        pend[k] = (i, exp_of[i])
        sem = [i for i in range(B) if i not in front]
        pos_of = {i: p for p, i in enumerate(sem)}

        # pad the semantic sub-batch to a power-of-two bucket: device
        # shapes (and the compiled executables behind them) stay fixed
        # across the varying batch sizes a router produces
        V = V_np = ok = s_sb = h_idxb = None
        Bp = 1
        if sem:
            Bs = len(sem)
            Bp = 1 << (Bs - 1).bit_length()
            with tracing.span("policy.embed", rows=Bs):
                V = self._embed_batch([prompts[i] for i in sem])  # (Bs, d)
                if Bp != Bs:
                    V = jnp.pad(V, ((0, Bp - Bs), (0, 0)))
                # degenerate-embedding guard: zero out unusable rows so
                # one NaN can't leak through the fused lookups, and serve
                # them backend-only further down — never cached (the key
                # would poison the tier's argmax), never grey-triggered
                ok = _usable_rows(np.asarray(V)[:Bs])
                if not ok.all():
                    V = jnp.where(
                        jnp.asarray(np.pad(ok, (0, Bp - Bs)))[:, None],
                        V, 0.0)
                V_np = np.asarray(V)[:Bs]
            if self.fused is None:
                with tracing.span("policy.static_lookup", rows=Bs):
                    s_sb, h_idxb = jax.device_get(
                        self._static_topk_batch(V))           # fused top-1
                s_sb, h_idxb = s_sb[:Bs], h_idxb[:Bs]

        results: List[Optional[ServeResult]] = [None] * B
        content_of = [0] * B    # per-row content clock (drift accounting)
        grey_rows = []          # static-miss rows, for the Krites hook
        l1_dup_fill = []        # (row, producer row) — answer arrives late
        # rollback point: a failed batch serves nobody, so it must
        # record no events
        ev0 = len(self.events)
        with tracing.locked(self.dyn_lock, "policy.lock_wait"):
            # one masked lookup against the dynamic-tier snapshot; the
            # tier object is immutable, so `snap` stays the batch-start
            # state while mutations accumulate on the host
            snap = self.dyn
            if sem:
                if self.fused is not None:
                    # fused fast path (DESIGN.md §15): static probe +
                    # masked dynamic top-1 in ONE dispatch over the batch
                    with tracing.span("policy.lookup", rows=len(sem)):
                        s_sb, h_idxb, s_db, j_db = jax.device_get(
                            T.serve_lookup_batch(self.static, snap, V,
                                                 self.fused))
                    s_sb, h_idxb = s_sb[:len(sem)], h_idxb[:len(sem)]
                else:
                    with tracing.span("policy.dyn_lookup", rows=len(sem)):
                        s_db, j_db = jax.device_get(
                            self._dyn_topk(snap, V))
                s_db, j_db = s_db[:len(sem)], j_db[:len(sem)]

            written: dict = {}   # slot -> (row, pos) of its last writer
            w_meta: dict = {}    # slot -> (pos, t, cls, exp) bulk write
            saved: dict = {}     # slot -> pre-write mirror state (rollback)
            touched: set = set()
            excl: set = set()    # snapshot rows invalidated this batch
            dead: set = set()    # slots TTL-expired mid-batch
            backend_rows: List[int] = []
            backend_slots: List[int] = []
            deferred = []        # (row, producer row)
            adapt_rows = []      # (row, pos, h_idx, segment), recorded
                                 # once the batch has served

            for i in range(B):
                self.t += 1
                ti = self.t
                f = front.get(i)
                if f is not None:
                    if f[0] == "bypass":
                        self._l1_bypass += 1
                        backend_rows.append(i)
                        backend_slots.append(-1)
                        results[i] = ServeResult(
                            None, "backend", False, 0.0, 0.0,
                            meta={"bypass": "volatile"})
                        self.events.append(("backend", False))
                    elif f[0] == "hit":
                        e = f[1]
                        self._l1_hits += 1
                        results[i] = ServeResult(e.answer, "l1",
                                                 e.static_origin, 1.0,
                                                 0.0)
                        content_of[i] = e.content_t
                        self._mark_stale(results[i], vol[i],
                                         e.content_t, ti)
                        self.events.append(("l1", e.static_origin))
                    else:       # in-batch duplicate of a producer row
                        p = f[1]
                        self._l1_hits += 1
                        results[i] = ServeResult(
                            results[p].answer, "l1",
                            results[p].static_origin, 1.0, 0.0)
                        content_of[i] = content_of[p]
                        self._mark_stale(results[i], vol[i],
                                         content_of[p], ti)
                        self.events.append(("l1",
                                            results[p].static_origin))
                        if results[p].answer is None:
                            l1_dup_fill.append((i, p))
                    continue
                pos = pos_of[i]
                if not ok[pos]:
                    # backend-only: slot sentinel -1 skips the cache
                    # write when the batched answers come back
                    backend_rows.append(i)
                    backend_slots.append(-1)
                    results[i] = ServeResult(None, "backend", False,
                                             0.0, 0.0)
                    content_of[i] = ti
                    self.events.append(("backend", False))
                    continue
                ss_i, h_i = float(s_sb[pos]), int(h_idxb[pos])
                tau_si, tau_di, seg_i = self._live_taus(prompts[i])
                if seg_i >= 0:
                    adapt_rows.append((i, pos, h_i, seg_i))
                if ss_i >= tau_si:
                    results[i] = ServeResult(self._serve_static(h_i),
                                             "static", True, ss_i, 0.0)
                    content_of[i] = 0
                    self._mark_stale(results[i], vol[i], 0, ti)
                    self.events.append(("static", True))
                    continue

                # eager TTL expiry at this row's tick: mirrors flip now;
                # the device scatter is deferred to batch end
                for s in self._expire_mirrors(ti):
                    s = int(s)
                    written.pop(s, None)
                    dead.add(s)
                    excl.add(s)

                # dynamic candidate = snapshot best, repaired for slots
                # overwritten/expired this batch, merged with intra-batch
                # inserts
                s_d, j = float(s_db[pos]), int(j_db[pos])
                if j in excl:
                    s_d, j = self._snap_best_excluding(snap, V[pos],
                                                       excl)
                for slot, (wrow, wpos) in written.items():
                    sw = float(V_np[pos] @ V_np[wpos])
                    if sw > s_d or (sw == s_d and slot < j):
                        s_d, j = sw, slot

                if s_d >= tau_di:
                    self._last_used_np[j] = ti
                    touched.add(j)
                    if j in written:  # answer arrives with the batch call
                        origin, by = False, "dynamic"
                        results[i] = ServeResult(None, "dynamic", False,
                                                 s_d, 0.0)
                        deferred.append((i, written[j][0]))
                    else:
                        origin = bool(self._static_origin_np[j])
                        by = "rewritten" if self._rewritten_np[j] \
                            else "dynamic"
                        results[i] = ServeResult(self.dyn_answers[j],
                                                 by, origin, s_d, 0.0)
                    content_of[i] = int(self._written_at_np[j])
                    self._mark_stale(results[i], vol[i], content_of[i],
                                     ti)
                    self.events.append((by, origin))
                else:
                    slot = self._host_lru_slot()
                    if slot not in saved:
                        saved[slot] = (bool(self._valid_np[slot]),
                                       int(self._last_used_np[slot]),
                                       bool(self._static_origin_np[slot]),
                                       int(self._written_at_np[slot]),
                                       int(self._expires_np[slot]),
                                       bool(self._rewritten_np[slot]),
                                       self.dyn_answers[slot])
                    exp = self._entry_expiry(prompts[i], ti)
                    self._mirror_write(slot, ti, static_origin=False,
                                       expires=exp)
                    self.dyn_answers[slot] = None
                    written[slot] = (i, pos)
                    excl.add(slot)
                    dead.discard(slot)
                    w_meta[slot] = (pos, ti,
                                    (metas[i] or {}).get("cls", -1), exp)
                    backend_rows.append(i)
                    backend_slots.append(slot)
                    results[i] = ServeResult(None, "backend", False, s_d,
                                             0.0)
                    content_of[i] = ti
                    self.events.append(("backend", False))
                grey_rows.append((prompts[i], V_np[pos], h_i, ss_i,
                                  results[i], metas[i], ti, tau_si))

            # backend first: a failed batch must not commit its inserts,
            # so a backend outage can't poison the cache with answerless
            # entries
            answers: List[object] = []
            if backend_rows:
                try:
                    # one batched backend call amortizes prefill
                    with tracing.span("policy.backend",
                                      rows=len(backend_rows)):
                        answers = self._backend_batch(
                            [prompts[i] for i in backend_rows])
                except Exception:
                    for slot, st in saved.items():
                        (self._valid_np[slot], self._last_used_np[slot],
                         self._static_origin_np[slot],
                         self._written_at_np[slot],
                         self._expires_np[slot],
                         self._rewritten_np[slot],
                         self.dyn_answers[slot]) = st
                    del self.events[ev0:]
                    self._apply_batch_writes(V, {}, touched, Bp,
                                             dead=dead)
                    raise
            for i, pos, h_i, seg_i in adapt_rows:
                self._adapt_record(V_np[pos], metas[i], h_i, seg_i,
                                   results[i])
            with tracing.span("policy.writes", rows=len(w_meta)):
                self._apply_batch_writes(V, w_meta, touched, Bp,
                                         dead=dead)
            if backend_rows:
                for slot, i, ans in zip(backend_slots, backend_rows,
                                        answers):
                    # -1 = degenerate/bypass row, never cached; a slot
                    # whose entry TTL-expired mid-batch (or was rewritten
                    # by a later row) must not get this answer either
                    if slot >= 0 and self._valid_np[slot] \
                            and written.get(slot, (None,))[0] == i:
                        self.dyn_answers[slot] = ans
                    results[i].answer = ans
                for i, producer in deferred:
                    results[i].answer = results[producer].answer
                for i, producer in l1_dup_fill:
                    results[i].answer = results[producer].answer

        # L1 write-back: every semantic row's outcome becomes an exact-
        # match entry (in row order, after the batch's answers landed)
        if self.l1 is not None:
            for i in sem:
                self.l1.put(keys[i], results[i].answer,
                            static_origin=results[i].static_origin,
                            content_t=content_of[i],
                            expires_at=exp_of[i],
                            now=self.t - B + i + 1)

        lat = time.monotonic() - t0
        for r in results:
            r.latency_s = lat
        with tracing.span("policy.grey_submit", rows=len(grey_rows)):
            self._after_static_miss_batch(grey_rows)
        with tracing.span("policy.adapt"):
            self._maybe_adapt()
        return results  # type: ignore[return-value]

    def _apply_batch_writes(self, V: jax.Array, w_meta: dict,
                            touched: set, B: int, dead=()) -> None:
        """Push a batch's accumulated inserts + LRU touches to the JAX
        tier as one fused scatter per field (vs one dispatch per row).
        Index arrays are padded to the batch's power-of-two bucket so
        shapes — and hence compiled executables — stay fixed even when a
        router produces ragged batch sizes. ``dead`` slots (TTL-expired
        mid-batch, mirror-invalid) get their valid bit cleared first;
        inserts into slots the mirrors since invalidated are dropped —
        the mirrors are the source of decision truth within the batch."""
        dyn = self.dyn
        dead = [s for s in dead if not self._valid_np[s]]
        if dead:
            idx = jnp.asarray(sorted(dead))
            dyn = dyn._replace(
                valid=dyn.valid.at[idx].set(False),
                expires_at=dyn.expires_at.at[idx].set(0))
        w_meta = {s: m for s, m in w_meta.items() if self._valid_np[s]}
        if w_meta:
            slots = np.fromiter(w_meta.keys(), np.int64, len(w_meta))
            rows = np.asarray([w_meta[s][0] for s in slots])
            ts = np.asarray([w_meta[s][1] for s in slots], np.int32)
            cls = np.asarray([w_meta[s][2] for s in slots], np.int32)
            exps = np.asarray([w_meta[s][3] for s in slots], np.int32)
            dyn = self._bulk_insert_fn(dyn, V, _pad_to(slots, B),
                                       _pad_to(rows, B), _pad_to(ts, B),
                                       _pad_to(cls, B),
                                       exps=_pad_to(exps, B))
            if self.dyn_index is not None:
                V_np = np.asarray(V)
                for s, r in zip(slots, rows):
                    self.dyn_index.record_write(int(s), V_np[r])
        upd = set(w_meta) | touched
        if upd:
            sl = np.fromiter(upd, np.int64, len(upd))
            dyn = self._touch_many(dyn, _pad_to(sl, B),
                                   _pad_to(self._last_used_np[sl], B))
        self.dyn = dyn

    def describe_index(self) -> str:
        """Telemetry string for the static-tier index in use (router
        stats surface this — serving/router.py)."""
        if self.fused is not None:
            return self.fused.describe()
        if self.index is None:
            S = len(self._static_ref_np)
            if self.mesh is not None:
                return (f"sharded-flat(S={S}, "
                        f"shards={self.mesh.shape[self.shard_axis]})")
            return f"flat-exact(S={S})"
        describe = getattr(self.index, "describe", None)
        return describe() if describe else type(self.index).__name__

    def describe_dyn_index(self) -> str:
        """Telemetry string for the dynamic-tier lookup path."""
        if self.dyn_index is None:
            if self.mesh is not None:
                return (f"sharded-masked(C={self.cfg.capacity}, "
                        f"shards={self.mesh.shape[self.shard_axis]})")
            return f"flat-masked(C={self.cfg.capacity})"
        describe = getattr(self.dyn_index, "describe", None)
        return describe() if describe else type(self.dyn_index).__name__

    def shard_stats(self) -> Optional[dict]:
        """Mesh-serving telemetry (DESIGN.md §13): shard count and the
        per-shard occupancy of the row-sharded dynamic tier, computed
        from the host mirrors (no device round-trip). None when serving
        single-device."""
        if self.mesh is None:
            return None
        n_shards = self.mesh.shape[self.shard_axis]
        occ = self._valid_np.reshape(n_shards, -1).sum(axis=1)
        return {"shards": n_shards,
                "shard_occupancy": [int(x) for x in occ]}

    def dyn_index_stats(self) -> Optional[dict]:
        """Segment/tail occupancy + compaction counters of the injected
        dynamic index (None on the flat path) — surfaced by the router."""
        if self.dyn_index is None:
            return None
        stats = getattr(self.dyn_index, "stats", None)
        return stats() if stats else None

    def stats(self) -> dict:
        n = max(len(self.events), 1)
        by = [e[0] for e in self.events]
        # tier-internal counters first: the policy-level keys below
        # (notably l1_hits, which also counts in-batch exact dups the
        # tier never probes) stay authoritative on key collisions
        out = dict(self.l1.stats()) if self.l1 is not None else {}
        out.update({
            "requests": len(self.events),
            "static_hit_rate": by.count("static") / n,
            "dynamic_hit_rate": by.count("dynamic") / n,
            # TweakLLM rewrite variants served from the dynamic tier
            # (DESIGN.md §18) — a distinct hit source so coverage
            # dashboards can attribute the rewrite frontier
            "rewritten_hit_rate": by.count("rewritten") / n,
            "backend_rate": by.count("backend") / n,
            "l1_hit_rate": by.count("l1") / n,
            "static_origin_rate":
                sum(1 for e in self.events if e[1]) / n,
            # freshness subsystem counters (DESIGN.md §16) — always
            # present so dashboards don't branch on configuration
            "l1_hits": self._l1_hits,
            "l1_bypass_volatile": self._l1_bypass,
            "stale_serves": self._stale_serves,
            "ttl_evictions": self._ttl_evictions,
        })
        if self.adaptive is not None:
            out.update(self.adaptive.stats())
        return out

    def feedback(self, seq: int, ok: bool) -> bool:
        """Operator error feedback on a served answer: ``seq`` is the
        ``adapt_seq`` stamped into the ServeResult meta. A wrong-answer
        report poisons the controller window row's label so the next
        shadow sweep counts serving that query as an error. Returns
        False when no controller is attached or the row has already
        rotated out of the window."""
        if self.adaptive is None:
            return False
        with self.dyn_lock:
            before = self.adaptive.feedbacks
            self.adaptive.record_feedback(seq, ok)
            return self.adaptive.feedbacks > before


class KritesPolicy(BaselinePolicy):
    """Algorithm 2: baseline serving + async grey-zone verification."""

    def __init__(self, cfg: T.CacheConfig, static_tier: T.StaticTier,
                 static_answers, embed_fn, backend_fn, judge_fn, d: int,
                 n_workers: int = 2,
                 judge_rate_per_s: Optional[float] = None, *,
                 embed_batch_fn: Optional[Callable] = None,
                 backend_batch_fn: Optional[Callable] = None,
                 index=None, dyn_index=None, static_texts=None,
                 mesh=None, shard_axis: str = "model", wal=None,
                 fused=None, l1=None, freshness=None, adaptive=None,
                 rewriter=None):
        super().__init__(cfg, static_tier, static_answers, embed_fn,
                         backend_fn, d, embed_batch_fn=embed_batch_fn,
                         backend_batch_fn=backend_batch_fn, index=index,
                         dyn_index=dyn_index, static_texts=static_texts,
                         mesh=mesh, shard_axis=shard_axis, fused=fused,
                         l1=l1, freshness=freshness, adaptive=adaptive)
        # write-ahead promotion journal (core/promo_wal.py, DESIGN.md
        # §14): each approved verdict is appended — inside dyn_lock, so
        # journal order equals apply order — before its upsert, and
        # replayed idempotently on restart via the same LWW contract
        self.wal = wal
        # one judge-budget knob: cfg.judge_rate (per request, shared
        # with the trace simulator) is the default; judge_rate_per_s is
        # an explicit wall-clock override for live deployments
        if judge_rate_per_s is None:
            rate_kw = dict(rate_per_s=0.0, rate_per_req=cfg.judge_rate)
        else:
            rate_kw = dict(rate_per_s=judge_rate_per_s)
        self._judge_fn = judge_fn
        # TweakLLM rewriter (DESIGN.md §18): a ``RewriterFn`` producing
        # the tailored answer for REWRITE verdicts, run on the pool
        # worker threads — strictly off the serving path. Budgeted like
        # the judge: ``cfg.rewrite_rate`` tokens accrue per judged
        # task (the live twin of the simulator's per-step refill);
        # an empty bucket downgrades the verdict to REJECT.
        self._rewriter = rewriter
        self._rw_rate = float(cfg.rewrite_rate)
        self._rw_budget = 0.0
        self._rw_lock = threading.Lock()
        self.pool = VerifyAndPromotePool(
            judge_fn=self._judge_payload,
            promote_fn=self._promote,
            n_workers=n_workers, **rate_kw)

    def _judge_payload(self, payload: dict) -> Verdict:
        """Pool adapter: run the judge over the payload's verification
        triple and, for promoting outcomes, stamp the TTL verdict onto
        the payload — it rides the same object into ``_promote`` (and
        the WAL), so the entry's lifetime is decided at verification
        time. A REWRITE verdict additionally runs the rewriter here
        (worker thread, never the serving path); its tailored text and
        outcome tag ride the payload too. Legacy ``bool``-returning
        judges are auto-wrapped via ``as_verdict``."""
        ja = payload["judge_args"]
        # the rewrite token bucket refills per judged task whether or
        # not this verdict rewrites — same discipline as the simulator's
        # per-step refill at the completion-processing point
        if self._rewriter is not None:
            with self._rw_lock:
                self._rw_budget = min(self._rw_budget + self._rw_rate,
                                      1e9)
        verdict = as_verdict(self._judge_fn(**ja))
        if verdict.outcome == REWRITE:
            verdict = self._try_rewrite(verdict, payload, ja)
        if verdict.outcome != REJECT:
            payload["ttl"] = int(verdict.ttl) if verdict.ttl is not None \
                else self._assign_ttl(ja)
        payload["outcome"] = verdict.outcome
        # verdict evidence for the threshold controller (DESIGN.md §17):
        # rewrite the window row's outcome label so shadow sweeps score
        # candidate thresholds against what the judge actually decided.
        # REWRITE counts as not-approved: the judge ruled the static
        # neighbor NOT equivalent, so serving it as-is would be an error
        # — exactly what the window's static-serve scoring models.
        seq = payload.get("adapt_seq", 0)
        if self.adaptive is not None and seq:
            with self.dyn_lock:
                self.adaptive.record_verdict(seq, verdict.approved,
                                             ja["h_cls"])
        return verdict

    def _try_rewrite(self, verdict: Verdict, payload: dict,
                     ja: dict) -> Verdict:
        """Resolve a REWRITE verdict into a promotable tailored answer,
        or degrade it to REJECT: no rewriter / rewriter raised / empty
        text -> ``rewrite_failed``; token bucket empty ->
        ``rewrite_rate_limited``. The flags ride the payload so the
        pool's per-outcome stats attribute the degradation."""
        if self._rewriter is None:
            payload["rewrite_failed"] = True
            return Verdict(REJECT, confidence=verdict.confidence)
        with self._rw_lock:
            if self._rw_budget < 1.0:
                payload["rewrite_rate_limited"] = True
                return Verdict(REJECT, confidence=verdict.confidence)
            self._rw_budget -= 1.0
        text = verdict.text
        if not text:
            try:
                text = self._rewriter(ja.get("q_text", ""),
                                      ja.get("h_text", ""),
                                      ja.get("answer", ""))
            except Exception:  # noqa: BLE001 — degrade, don't retry:
                text = ""      # a broken rewriter must stay deterministic
        if not text:
            payload["rewrite_failed"] = True
            return Verdict(REJECT, confidence=verdict.confidence)
        payload["rewritten"] = str(text)
        return Verdict(REWRITE, text=str(text), ttl=verdict.ttl,
                       confidence=verdict.confidence)

    def _assign_ttl(self, ja: dict) -> int:
        """TTL verdict precedence (DESIGN.md §16): a freshness-aware
        judge is authoritative (it saw the texts); else the policy's
        own classifier; else the config-wide ttl (0 = unbounded)."""
        judge = self._judge_fn
        if getattr(judge, "freshness", None) is not None:
            return int(judge.assign_ttl(ja.get("q_text", ""),
                                        ja.get("h_text", ""),
                                        ja.get("answer", "")))
        if self.freshness is not None:
            return int(self.freshness.ttl_for_text(
                ja.get("q_text", "") or ja.get("h_text", "")))
        return int(self.cfg.ttl)

    def _grey_submission(self, prompt, v, h_idx, s_static, res, meta,
                         enq_t, tau_s=None):
        """Alg. 2 grey-zone gate -> (key, payload) for the pool, or None.

        The payload's ``judge_args`` carry the full verification triple
        the paper's judge is defined over: the query text, the static
        neighbor's prompt text (``static_texts``; the curated answer
        text is the fallback proxy when none were provided) and the
        curated answer itself — class ids alone are only the oracle
        shortcut.

        ``tau_s`` is the live tau_static the serving decision used
        (adaptive thresholds, DESIGN.md §17); the grey zone's upper
        edge must be that same value, not whatever the controller has
        moved it to since."""
        if tau_s is None:
            tau_s = self.cfg.tau_static
        if not (self.cfg.sigma_min <= s_static < tau_s):
            return None
        if self.cfg.dedup and res.served_by in ("dynamic", "rewritten") \
                and res.static_origin:
            return None  # a promoted pointer already serves this query
        va = np.asarray(v)
        fp = hash(va.tobytes())
        answer = self._serve_static(h_idx)
        h_text = self.static_texts[h_idx] \
            if self.static_texts is not None else str(answer)
        return ((fp, h_idx), {
            "v": va,
            "h_idx": h_idx,
            "enq_t": enq_t,
            "submitted_s": time.monotonic(),   # for promote.lag_s
            "adapt_seq": res.meta.get("adapt_seq", 0),
            "judge_args": {
                "q_cls": (meta or {}).get("cls", -1),
                "h_cls": int(self._static_cls_np[h_idx]),
                "q_text": prompt or "",
                "h_text": h_text,
                "answer": "" if answer is None else str(answer),
            },
        })

    def _after_static_miss_batch(self, rows) -> None:
        items = []
        for prompt, v, h_idx, s_static, res, meta, enq_t, tau_s in rows:
            sub = self._grey_submission(prompt, v, h_idx, s_static, res,
                                        meta, enq_t, tau_s)
            if sub is not None:
                items.append(sub)
        if items:
            self.pool.submit_many(items)

    def _promote(self, payload: dict, journal: bool = True):
        """Auxiliary overwrite: upsert the curated static answer under
        the new key — idempotent, near-duplicate keys overwrite in
        place, and last-writer-wins guarded exactly as
        ``tiers.upsert(lww=True)`` documents: a near-duplicate entry
        *written after this task was enqueued* (``written_at > enq_t``)
        is newer state a slow judge must not clobber, so the stale
        promotion is skipped and neither the device tier nor the host
        mirrors are touched.

        With a ``wal`` the verdict is journaled before the upsert
        (write-ahead: a crash after the append replays the promotion on
        restart; a crash before it re-judges at the next grey trigger).
        ``journal=False`` is the replay path — journaled records must
        not re-append.

        Clock split: ``written_at`` gets ``enq_t`` (the LWW guard must
        compare against the enqueue time), but ``last_used`` gets the
        *live* clock — a promotion applied after a slow judge is fresh
        state; stamping its LRU clock with the stale ``enq_t`` would
        make it the coldest entry in the tier and the eviction victim
        of the very next insert under churn.

        Device work, on one device: two compiled programs with the
        host between them. ``_promote_lookup`` gives the dedup
        ``(score, slot)``, read back in one ``device_get``; the host
        applies the ``dup_threshold`` test, the LWW guard, the TTL
        check, picks the slot and appends the WAL record; then the
        compiled ``_write_fn`` writes the row. Slot, clocks, class,
        answer ref and origin go in as plain scalars, so one compiled
        write serves every promotion. The tier is not donated: a
        snapshot, a WAL replay or a reader may still hold the tier this
        write replaces. A mesh runs the sharded twins of both programs;
        a segmented ``dyn_index`` keeps its own lookup.

        Spans: ``promote`` with children ``promote.lock_wait``,
        ``promote.lookup``, ``promote.wal`` and ``promote.write``; a
        landed upsert adds its lag from the pool submit to the counter
        ``promote.lag_s``."""
        with tracing.span("promote"):
            self._apply_promotion(payload, journal)

    def _apply_promotion(self, payload: dict, journal: bool) -> None:
        h_idx = payload["h_idx"]
        v = jnp.asarray(payload["v"])
        # a Python int: a numpy clock would key a second compile
        enq_t = int(payload["enq_t"])
        ja = payload.get("judge_args", {})
        # TTL verdict stamped by _judge_payload (or carried by a WAL
        # record on replay). Expiry anchors at enq_t — it is in the WAL
        # record, so replay reconstructs the same expires_at even though
        # apply_t differs across restarts.
        ttl = int(payload.get("ttl", self.cfg.ttl))
        exp = enq_t + ttl if ttl > 0 else 0
        # outcome tag stamped by _judge_payload (or replayed from the
        # WAL): REWRITE lands the tailored text keyed to the NEW
        # prompt's embedding and class, with the answer_ref=-2 sentinel
        # marking provenance; APPROVE lands the curated static pointer.
        rewrite = payload.get("outcome", APPROVE) == REWRITE
        if rewrite:
            answer = payload.get("rewritten", "")
            if not answer:
                return   # defensive: a REWRITE without text is a no-op
            cls, ref = int(ja.get("q_cls", -1)), -2
        else:
            answer = self._serve_static(h_idx)
            cls = int(self._static_cls_np[h_idx])
            ref = int(self._static_ref_np[h_idx])
        with tracing.locked(self.dyn_lock, "promote.lock_wait"):
            apply_t = self.t      # live LRU clock, read under the lock
            self._sweep_expired_locked(apply_t)
            if exp and exp < apply_t:
                return  # verdict outlived its own TTL; nothing to apply
            # the async promotion path rides the same index: dedup
            # lookup through the segmented tail/segments (§12) or the
            # row-sharded masked scan (§13), fresh write into the tier
            with tracing.span("promote.lookup"):
                if self.mesh is not None:
                    sd, jd = jax.device_get(
                        self._sh_dyn_fn(self.dyn, v[None]))
                    s_d, j = sd[0], jd[0]
                elif self.dyn_index is not None:
                    s_d, j = jax.device_get(T.dynamic_lookup(
                        self.dyn, v, index=self.dyn_index))
                else:
                    s_d, j = jax.device_get(_promote_lookup(self.dyn, v))
                s_d, j = float(s_d), int(j)
            dup = s_d >= self.cfg.dup_threshold
            if dup and self._written_at_np[j] > enq_t:
                return       # LWW: a newer write owns this key
            # journal only promotions that will actually apply — the
            # append still precedes the upsert (write-ahead contract),
            # but a stale promotion the LWW guard skips must not land
            # in the WAL, or replay/compaction re-applies a write the
            # live tier rightly refused, forever
            if journal and self.wal is not None:
                from repro.core.promo_wal import encode_record
                with tracing.span("promote.wal"):
                    self.wal.append(encode_record(
                        payload["v"], h_idx, enq_t, ttl=ttl,
                        q_text=ja.get("q_text", ""),
                        h_text=ja.get("h_text", ""),
                        outcome=REWRITE if rewrite else APPROVE,
                        rewritten=str(answer) if rewrite else "",
                        q_cls=int(ja.get("q_cls", -1))))
            with tracing.span("promote.write"):
                slot = j if dup else self._host_lru_slot()
                self.dyn = self._write_fn(
                    self.dyn, slot, v, cls, ref, True, enq_t,
                    last_used=apply_t, expires=exp)
                self._mirror_write(slot, apply_t, static_origin=True,
                                   written_at=enq_t, expires=exp,
                                   rewritten=rewrite)
                if self.dyn_index is not None:
                    self.dyn_index.record_write(slot, payload["v"])
                self.dyn_answers[slot] = answer
            if "submitted_s" in payload:      # not on a WAL replay
                tracing.add("promote.lag_s",
                            time.monotonic() - payload["submitted_s"])

    def stats(self) -> dict:
        out = super().stats()
        ps = self.pool.stats
        out.update({"judge_submitted": ps.submitted,
                    "judge_deduped": ps.deduped,
                    "judge_rate_limited": ps.rate_limited,
                    "judged": ps.judged, "approved": ps.approved,
                    "rejected": ps.rejected,
                    "rewritten": ps.rewritten,
                    "rewrite_failed": ps.rewrite_failed,
                    "rewrite_rate_limited": ps.rewrite_rate_limited,
                    "redispatched": ps.redispatched})
        if self.wal is not None:
            ws = self.wal.stats()
            out["wal_seq"] = ws["seq"]
            out["wal_synced_seq"] = ws["synced_seq"]
        return out
