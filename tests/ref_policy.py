"""Pure-numpy reference simulator: Algorithm 1 (baseline) and Algorithm 2
(Krites) as a plain Python loop over the request stream.

This is the *independent oracle* for the JAX simulator
(``repro.core.simulate``): no jit, no scan, no vmap — every rule of the
paper's Algorithms 1/2 written out imperatively, one request at a time.
``tests/test_ref_differential.py`` enforces that ``simulate`` and
``simulate_sweep`` match it decision-for-decision.

Semantics mirrored (see DESIGN.md §3-4, §10, §16):
- serving: static threshold, then dynamic threshold over valid rows,
  else miss + LRU write-back; LRU touch on dynamic hit;
- grey-zone trigger (Krites only): sigma_min <= s_static < tau_static,
  optional dedup skip when a promoted pointer already serves the query,
  token-bucket rate limiting;
- async VerifyAndPromote: a task enqueued at request t completes at
  request t + max(1, judge_latency), at most one completion per step
  (earliest due first), processed before the step's serving decision;
- promotion upsert: near-duplicate overwrite (sim >= 0.9999), else LRU
  slot; last-writer-wins guard comparing the duplicate's ``written_at``
  against the task's *enqueue* time, and the clock split of the live
  policy: the promoted row's ``written_at`` records the enqueue time
  (LWW) while ``last_used`` records the apply time (LRU-warm);
- freshness (§16): per-entry ``expires_at`` masks expired rows out of
  every lookup lazily (the eviction count lands once, at the first
  expired step); a promotion's expiry anchors at its *enqueue* time and
  a verdict that outlived its own TTL is dropped; the L1 exact-match
  front (one cell per exact-duplicate key) is probed after the volatile
  bypass and before any tier traffic, and every semantic serve writes
  back under its key with the content clock the staleness rule judges
  against (epoch(now) vs epoch(content); static content is epoch 0,
  backend answers are current by definition);
- rewrite verdicts (§18): when ``cfg.rewrite`` is on, a would-reject
  completion whose request was flagged ``rewritable`` spends one token
  from a per-step-refilled bucket (``cfg.rewrite_rate``) and promotes a
  tailored variant keyed to the *query's* class with the
  ``answer_ref = -2`` provenance sentinel; serving such a row reports
  the ``REWRITTEN_HIT`` event code.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

MISS, STATIC_HIT, DYN_HIT_DYNAMIC, DYN_HIT_PROMOTED, L1_HIT, \
    REWRITTEN_HIT = 0, 1, 2, 3, 4, 5
DEDUP_SIM = 0.9999
L1_NEVER = 1 << 30      # sim's unbounded-L1 sentinel (0 = empty cell)


class _RefSegIndex:
    """Pure-numpy twin of ``index/segmented.SegmentedIndex`` for the
    reference loop: a slot-id tail that seals into frozen segments, a
    compactor merging every ``compact_every`` of them, and tombstoning
    on overwrite/evict. Scores come from the *tier's* embedding matrix
    (the exact-rerank contract), so with the index's live set equal to
    the tier's valid set — the invariant this structure maintains —
    lookups are bit-identical to the flat masked scan. The reference
    simulator therefore stays a decision-for-decision oracle for both
    the flat and the segmented dynamic-lookup configs."""

    def __init__(self, tail_rows: int = 16, compact_every: int = 3):
        self.tail: dict = {}          # slot -> None (insertion order)
        self.segments: list = []      # frozen slot-id sets
        self.tail_rows = tail_rows
        self.compact_every = compact_every
        self.seals = self.merges = self.tombstones = 0

    def record_write(self, slot: int) -> None:
        self.invalidate(slot)
        if len(self.tail) == self.tail_rows:
            self.segments.append(set(self.tail))
            self.tail = {}
            self.seals += 1
            if len(self.segments) >= self.compact_every:
                merged = set().union(*self.segments)
                self.segments = [merged] if merged else []
                self.merges += 1
        self.tail[slot] = None

    def invalidate(self, slot: int) -> None:
        if self.tail.pop(slot, 0) is None:
            self.tombstones += 1
        for seg in self.segments:
            if slot in seg:
                seg.discard(slot)
                self.tombstones += 1

    def lookup(self, dyn: "_Dyn", q: np.ndarray, now=None):
        """Exact rerank of the live set against the tier matrix: the
        same sims vector the flat scan computes, masked to the index's
        live slots (tail + segments, tombstones excluded) and — when a
        clock is given — to unexpired rows."""
        sims = (dyn.emb @ q).astype(np.float32)
        live = np.zeros(len(sims), bool)
        for store in [self.tail, *self.segments]:
            for slot in store:
                live[slot] = True
        if now is not None:
            live &= (dyn.expires == 0) | (now <= dyn.expires)
        sims[~live] = -np.inf
        j = int(np.argmax(sims))
        return float(sims[j]), j


@dataclass
class _Dyn:
    """Mutable dynamic tier (struct-of-arrays, numpy)."""
    emb: np.ndarray
    cls: np.ndarray
    answer_ref: np.ndarray
    static_origin: np.ndarray
    valid: np.ndarray
    last_used: np.ndarray
    written_at: np.ndarray
    expires: np.ndarray = None
    index: object = None          # optional _RefSegIndex twin

    @classmethod
    def make(cls_, capacity: int, d: int, index=None) -> "_Dyn":
        return cls_(
            emb=np.zeros((capacity, d), np.float32),
            cls=np.zeros(capacity, np.int32),
            answer_ref=np.full(capacity, -1, np.int32),
            static_origin=np.zeros(capacity, bool),
            valid=np.zeros(capacity, bool),
            last_used=np.zeros(capacity, np.int32),
            written_at=np.zeros(capacity, np.int32),
            expires=np.zeros(capacity, np.int32),
            index=index,
        )

    def live(self, now=None) -> np.ndarray:
        """Valid AND unexpired (expiry is lazy: ``valid`` stays set, the
        mask does the killing — exactly the simulator's rule). With no
        clock, plain validity (the pre-§16 semantics; identical anyway
        whenever no entry carries an expiry)."""
        if now is None:
            return self.valid
        return self.valid & ((self.expires == 0) | (now <= self.expires))

    def lookup(self, q: np.ndarray, now=None):
        """Best (similarity, index) over live rows; (-inf, 0) if none."""
        if self.index is not None:
            return self.index.lookup(self, q, now)
        sims = (self.emb @ q).astype(np.float32)
        sims[~self.live(now)] = -np.inf
        j = int(np.argmax(sims))
        return float(sims[j]), j

    def lru_slot(self, now=None) -> int:
        """First dead (invalid or expired) row, else least-recently-used."""
        key = np.where(self.live(now), self.last_used.astype(np.int64),
                       -2**40)
        return int(np.argmin(key))

    def write(self, slot, q, cls, ref, so, now, written_at=None, exp=0):
        """``now`` stamps the LRU clock; ``written_at`` (default
        ``now``) stamps the LWW clock — promotions pass their enqueue
        time, mirroring ``tiers._write``. ``exp`` is the entry's
        ``expires_at`` (0 = never)."""
        self.emb[slot] = q
        self.cls[slot] = cls
        self.answer_ref[slot] = ref
        self.static_origin[slot] = so
        self.valid[slot] = True
        self.last_used[slot] = now
        self.written_at[slot] = now if written_at is None else written_at
        self.expires[slot] = exp
        if self.index is not None:
            self.index.record_write(slot)

    def upsert(self, q, cls, ref, now, enq=None, so=True, exp=0,
               dup_sim=DEDUP_SIM):
        """Idempotent, LWW-guarded promotion write (Alg. 2 line 21).

        ``enq`` is the promotion's enqueue time (default ``now``): the
        LWW guard compares against it and it becomes the row's
        ``written_at``, while ``now`` — the apply time — becomes the
        LRU clock, so a delayed promotion lands LRU-warm (the live
        ``KritesPolicy._promote`` clock split). ``dup_sim`` is the
        near-duplicate overwrite gate (``CacheConfig.dup_threshold``)."""
        enq = now if enq is None else enq
        s, j = self.lookup(q, now)
        dup = s >= dup_sim
        if dup and self.written_at[j] > enq:
            return                     # stale judgment: newer entry wins
        self.write(j if dup else self.lru_slot(now), q, cls, ref, so,
                   now, written_at=enq, exp=exp)


@dataclass
class _Task:
    due: int
    emb: np.ndarray
    qcls: int
    hcls: int
    href: int
    flip: bool
    vol: bool = False
    rw: bool = False


def ref_simulate(static_emb, static_cls, q_emb, q_cls, cfg, krites,
                 capacity=None, judge_flip=None, dyn_index=None,
                 drain=False, crash_after=None,
                 extra_replays=0, volatile=None, key_id=None,
                 drift_every=0, rewritable=None) -> dict:
    """Reference run; returns plain-numpy analogues of ``SimResult``.

    ``cfg`` is any object with the :class:`repro.core.tiers.CacheConfig`
    fields (tau_static, tau_dynamic, sigma_min, capacity, judge_latency,
    dedup, judge_rate). ``dyn_index='segmented'`` routes dynamic
    lookups through the :class:`_RefSegIndex` twin (tail + sealed
    segments + tombstones, exact rerank) — decisions must be identical
    to the flat config, keeping this loop the oracle for both.

    **Recovery semantics** (the numpy oracle for DESIGN.md §14).
    ``drain=True`` runs the end-of-trace promotion burst: every still-
    pending task is judged in due order and each approved promotion is
    first appended to a journal (the WAL analogue — journal order is
    apply order) and then upserted. ``crash_after=k`` models a crash
    mid-burst: only the first ``k`` journaled upserts land before the
    process dies; recovery then replays the *whole* journal, in order,
    with each record's original ``now`` — and ``extra_replays`` runs
    replay again that many times. The contract under test: any
    ``crash_after`` point followed by >=1 replay, plus any number of
    extra replays, yields a ``final`` tier state identical to the
    uninterrupted run — replay idempotence and the LWW ``written_at``
    guard are exactly what make this hold. ``final`` (the full dynamic
    tier arrays) and ``journal_len`` are added to the result only when
    ``drain=True``, so the existing simulator differentials — which
    have no drain phase — are untouched.

    **Freshness semantics** (the numpy oracle for DESIGN.md §16),
    driven by the ``cfg`` fields ``l1`` / ``volatile_bypass`` /
    ``ttl_volatile`` / ``ttl_stable`` (read with safe defaults so
    pre-§16 config objects keep working) plus the per-request
    ``volatile`` (bool) and ``key_id`` (exact-duplicate id) arrays and
    the ``drift_every`` ground-truth rotation period. All freshness
    logic is inert when those fields are off, so legacy calls stay
    bit-identical.
    """
    static_emb = np.asarray(static_emb, np.float32)
    static_cls = np.asarray(static_cls, np.int32)
    q_emb = np.asarray(q_emb, np.float32)
    q_cls = np.asarray(q_cls, np.int32)
    N, d = q_emb.shape
    if judge_flip is None:
        judge_flip = np.zeros(N, bool)
    if volatile is None:
        volatile = np.zeros(N, bool)
    if key_id is None:
        key_id = np.zeros(N, np.int64)
    if rewritable is None:
        rewritable = np.zeros(N, bool)

    C = capacity or cfg.capacity
    lat = max(1, cfg.judge_latency)
    dup_sim = float(getattr(cfg, "dup_threshold", DEDUP_SIM))
    l1f = bool(getattr(cfg, "l1", False))
    vbp = bool(getattr(cfg, "volatile_bypass", False))
    ttl_v = int(getattr(cfg, "ttl_volatile", 0))
    ttl_s = int(getattr(cfg, "ttl_stable", 0))
    rw_on = bool(getattr(cfg, "rewrite", False))
    rrate = float(getattr(cfg, "rewrite_rate", 1.0))
    rbud = np.float32(0.0)
    D = int(drift_every)
    dyn = _Dyn.make(C, d, index=_RefSegIndex()
                    if dyn_index == "segmented" else None)
    pending: list[_Task] = []
    budget = np.float32(1.0)
    l1: dict = {}          # key_id -> (expires, content_t, ok, so)

    # hoisted static lookup, like the simulator
    sims = q_emb @ static_emb.T
    h_idx = np.argmax(sims, axis=1)
    s_static = sims[np.arange(N), h_idx].astype(np.float32)
    h_cls = static_cls[h_idx]

    served_by = np.zeros(N, np.int8)
    correct = np.zeros(N, bool)
    static_origin = np.zeros(N, bool)
    stale = np.zeros(N, bool)
    judge_calls = judge_approved = promotions = enq_dropped = 0
    ttl_evicted = bypassed = rewrites = rewrite_dropped = 0

    def epoch(x):
        return x // D

    for t in range(N):
        q, qc = q_emb[t], int(q_cls[t])
        ss, hc, hr = float(s_static[t]), int(h_cls[t]), int(h_idx[t])
        vol, kid = bool(volatile[t]), int(key_id[t])

        # ---- 0. per-entry expiry: lazy death, counted exactly once at
        # the first expired step — before any write can reuse the slot
        ttl_evicted += int(np.sum(dyn.valid & (dyn.expires > 0)
                                  & (t == dyn.expires + 1)))

        # ---- 1. async completion due now (earliest first, one per step)
        # the rewrite token bucket refills once per step at the
        # completion point (the sim cores refill inside their step fn)
        if rw_on:
            rbud = np.float32(min(rbud + np.float32(rrate), 1e9))
        due_i = min((i for i, p in enumerate(pending) if p.due <= t),
                    key=lambda i: pending[i].due, default=None)
        if due_i is not None:
            task = pending.pop(due_i)
            judge_calls += 1
            approve = task.qcls == task.hcls or task.flip
            # REWRITE verdict (§18): a would-reject whose request was
            # rewritable spends a rewrite token and promotes a tailored
            # variant keyed to the *query's* class, answer_ref = -2
            rw_can = False
            if rw_on and not approve and task.rw:
                if rbud >= 1.0:
                    rw_can = True
                    rbud = np.float32(rbud - np.float32(1.0))
                    rewrites += 1
                else:
                    rewrite_dropped += 1
            if approve:
                judge_approved += 1
            if approve or rw_can:
                promotions += 1       # counts the verdict, like the sim
                # TTL verdict: expiry anchors at the *enqueue* time (what
                # the promotion WAL records); a verdict that outlived its
                # own TTL is dropped, like the live _promote
                tau_p = ttl_v if task.vol else ttl_s
                enq = task.due - lat
                exp_p = enq + tau_p if tau_p > 0 else 0
                if not (exp_p > 0 and exp_p < t):
                    cls_p = task.qcls if rw_can else task.hcls
                    ref_p = -2 if rw_can else task.href
                    dyn.upsert(task.emb, cls_p, ref_p, now=t,
                               enq=enq, exp=exp_p, dup_sim=dup_sim)

        # ---- 1b. freshness front: volatile bypass, then the L1 exact-
        # match probe — both before any tier traffic
        byp = vbp and vol
        le, l1_w, l1_ok, l1_so = l1.get(kid, (0, 0, False, False))
        l1hit = l1f and not byp and le > 0 and t <= le
        front = byp or l1hit
        if byp:
            bypassed += 1

        # ---- 2. serving path ----
        static_hit_sem = ss >= cfg.tau_static
        s_dyn, j_dyn = dyn.lookup(q, t)
        dyn_hit_sem = (not static_hit_sem) and s_dyn >= cfg.tau_dynamic
        static_hit = static_hit_sem and not front
        dyn_hit = dyn_hit_sem and not front
        miss = not front and not (static_hit_sem or dyn_hit_sem)
        wa_j = int(dyn.written_at[j_dyn])

        is_promoted = dyn_hit and bool(dyn.static_origin[j_dyn])
        is_rewritten = rw_on and dyn_hit \
            and int(dyn.answer_ref[j_dyn]) == -2
        if l1hit:
            served_by[t], served_cls = L1_HIT, qc
        elif static_hit:
            served_by[t], served_cls = STATIC_HIT, hc
        elif is_rewritten:
            served_by[t], served_cls = REWRITTEN_HIT, int(dyn.cls[j_dyn])
        elif is_promoted:
            served_by[t], served_cls = DYN_HIT_PROMOTED, int(dyn.cls[j_dyn])
        elif dyn_hit:
            served_by[t], served_cls = DYN_HIT_DYNAMIC, int(dyn.cls[j_dyn])
        else:
            served_by[t], served_cls = MISS, qc
        correct[t] = l1_ok if l1hit else served_cls == qc
        static_origin[t] = l1_so if l1hit else (static_hit or is_promoted)

        # drift staleness: a volatile query served content produced in
        # an earlier drift epoch (static is epoch 0; backend is current)
        if D > 0 and vol:
            if l1hit:
                stale[t] = epoch(t) != epoch(l1_w)
            elif static_hit:
                stale[t] = epoch(t) != 0
            elif dyn_hit:
                stale[t] = epoch(t) != epoch(wa_j)

        if dyn_hit:
            dyn.last_used[j_dyn] = t          # LRU touch
        tau_q = ttl_v if vol else ttl_s
        exp_q = t + tau_q if tau_q > 0 else 0
        if miss:
            dyn.write(dyn.lru_slot(t), q, qc, -1, False, t, exp=exp_q)

        # ---- 2b. L1 write-back: every semantic serve lands under the
        # query's exact key (never refreshed by later hits — the stored
        # content clock is what staleness is judged against)
        if l1f and not front:
            content_t = 0 if static_hit else (wa_j if dyn_hit else t)
            l1[kid] = (exp_q if tau_q > 0 else L1_NEVER, content_t,
                       bool(correct[t]), bool(static_origin[t]))

        # ---- 3. grey-zone trigger (off-path); front-resolved requests
        # never embed, so they can never trigger
        grey = cfg.sigma_min <= ss < cfg.tau_static
        want = grey and bool(krites) and not front
        if cfg.dedup and is_promoted and s_dyn >= cfg.tau_dynamic:
            want = False
        budget = np.float32(min(budget + np.float32(cfg.judge_rate), 1e9))
        if want and budget >= 1.0:
            budget = np.float32(budget - np.float32(1.0))
            pending.append(_Task(t + lat, q.copy(), qc, hc, hr,
                                 bool(judge_flip[t]), vol,
                                 bool(rewritable[t])))
        elif want:
            enq_dropped += 1

    out = {
        "served_by": served_by, "correct": correct,
        "static_origin": static_origin, "stale": stale,
        "judge_calls": judge_calls,
        "judge_approved": judge_approved, "promotions": promotions,
        "enq_dropped": enq_dropped,
        "ttl_evicted": ttl_evicted, "bypassed": bypassed,
        "rewrites": rewrites, "rewrite_dropped": rewrite_dropped,
    }
    if not drain:
        return out

    # ---- 4. end-of-trace drain: judge the backlog, journal-then-apply
    journal = []              # (emb, cls, ref, now, enq) in append order
    for task in sorted(pending, key=lambda p: p.due):
        judge_calls += 1
        if task.qcls == task.hcls or task.flip:
            judge_approved += 1
            promotions += 1
            journal.append((task.emb, task.hcls, task.href,
                            int(task.due), int(task.due) - lat))
    applied = len(journal) if crash_after is None \
        else min(crash_after, len(journal))
    for rec in journal[:applied]:       # upserts that landed pre-crash
        dyn.upsert(*rec, dup_sim=dup_sim)
    if crash_after is not None or extra_replays:
        for _ in range(max(1 if crash_after is not None else 0,
                           extra_replays)):
            for rec in journal:         # full-journal replay, in order
                dyn.upsert(*rec, dup_sim=dup_sim)

    out.update({
        "judge_calls": judge_calls, "judge_approved": judge_approved,
        "promotions": promotions,
        "journal_len": len(journal),
        "final": {
            "emb": dyn.emb.copy(), "cls": dyn.cls.copy(),
            "answer_ref": dyn.answer_ref.copy(),
            "static_origin": dyn.static_origin.copy(),
            "valid": dyn.valid.copy(),
            "last_used": dyn.last_used.copy(),
            "written_at": dyn.written_at.copy(),
            "expires": dyn.expires.copy(),
        },
    })
    return out


def ref_adaptive(static_emb, static_cls, q_emb, q_label, q_seg, cfg,
                 params=None, feedback=None) -> dict:
    """Numpy twin of ``BaselinePolicy`` + ``AdaptiveController`` served
    one request at a time (the oracle for DESIGN.md §17).

    One imperative loop per request: serve under the *live per-segment*
    thresholds, record (embedding, label, segment) into the bounded
    window, and at the controller's cadence run the shadow sweep — here
    evaluated candidate-by-candidate through :func:`ref_simulate`
    (krites=False), the independent numpy evaluator, instead of the
    live controller's one batched ``simulate_sweep`` dispatch. The
    *selection* arithmetic (grid construction, feasibility, hysteresis,
    bounded step, LCG exploration) is deliberately the shared pure code
    from ``core/adaptive.py``: the oracle's independence lives in the
    decision streams, and the existing simulator differentials already
    pin ``ref_simulate`` against ``simulate_sweep``. Every adaptive
    decision — tau trajectory, move/explore/regret counters, and the
    serving stream they produce — must match the live policy
    field-identically.

    ``q_label`` is the caller-declared class per request (−1 = none:
    the static neighbor's class is recorded instead, like the live
    ``_adapt_record``). ``q_seg`` is the per-request traffic segment.
    ``feedback``, when given, marks requests whose served answer gets
    an immediate wrong-answer report: the window row's label is
    poisoned with the live path's unique ``−2−seq`` sentinel right
    after serving, before the next request.
    """
    from repro.core.adaptive import (N_SEGMENTS, AdaptiveParams,
                                     candidate_grid, choose_candidate,
                                     lcg_next)
    from repro.core.tiers import CacheConfig

    p = params or AdaptiveParams()
    static_emb = np.asarray(static_emb, np.float32)
    static_cls = np.asarray(static_cls, np.int32)
    q_emb = np.asarray(q_emb, np.float32)
    q_label = np.asarray(q_label, np.int64)
    q_seg = np.asarray(q_seg, np.int64)
    N, d = q_emb.shape
    if feedback is None:
        feedback = np.zeros(N, bool)

    tau_s = [float(cfg.tau_static)] * N_SEGMENTS
    tau_d = [float(cfg.tau_dynamic)] * N_SEGMENTS
    w_emb = np.zeros((p.window, d), np.float32)
    w_label = np.zeros(p.window, np.int32)
    w_seg = np.zeros(p.window, np.int8)
    count = since = 0
    rng = lcg_next(p.seed & ((1 << 64) - 1))
    dyn = _Dyn.make(cfg.capacity, d)
    adaptations = moves = explores = 0
    regret = [0] * N_SEGMENTS

    sims = q_emb @ static_emb.T
    h_idx = np.argmax(sims, axis=1)
    s_static = sims[np.arange(N), h_idx].astype(np.float32)
    h_cls = static_cls[h_idx]

    served_by = np.zeros(N, np.int8)
    tau_trail = []          # (request idx, tau_s copy, tau_d copy)

    def shadow_cfg(ts, td):
        # must construct the SAME candidate config the live
        # AdaptiveController._shadow_cfg builds
        return CacheConfig(tau_static=ts, tau_dynamic=td, sigma_min=0.0,
                           capacity=p.shadow_capacity, judge_latency=1,
                           dup_threshold=1.0)

    for t in range(N):
        q, seg = q_emb[t], int(q_seg[t])
        ss = float(s_static[t])
        if ss >= tau_s[seg]:
            served_by[t] = STATIC_HIT
        else:
            s_dyn, j = dyn.lookup(q, t)
            if s_dyn >= tau_d[seg]:
                served_by[t] = DYN_HIT_PROMOTED \
                    if dyn.static_origin[j] else DYN_HIT_DYNAMIC
                dyn.last_used[j] = t
            else:
                served_by[t] = MISS
                dyn.write(dyn.lru_slot(t), q, int(q_label[t]), -1,
                          False, t)
        # window record (every semantic serve) + optional feedback
        label = int(q_label[t]) if q_label[t] >= 0 else int(h_cls[t])
        i = count % p.window
        w_emb[i], w_seg[i] = q, seg
        count += 1
        since += 1
        w_label[i] = (-2 - count) if feedback[t] else label

        # serve-call-boundary adaptation check (scalar cadence)
        if since < p.adapt_every or count < p.window:
            continue
        since = 0
        pos = count % p.window
        order = np.concatenate([np.arange(pos, p.window),
                                np.arange(0, pos)])
        emb, lab, sg = w_emb[order], w_label[order], w_seg[order]
        rng = lcg_next(rng)
        adaptations += 1

        active = [s for s in range(N_SEGMENTS)
                  if int((sg == s).sum()) >= p.min_segment]
        if not active:
            continue
        spans, cfgs = {}, []
        for s in active:
            cands, ci = candidate_grid(tau_s[s], tau_d[s], p)
            spans[s] = (len(cfgs), cands, ci)
            cfgs.extend(shadow_cfg(ts, td) for ts, td in cands)
        sb = np.stack([ref_simulate(static_emb, static_cls, emb, lab,
                                    c, krites=False)["served_by"]
                       for c in cfgs])
        cr = np.stack([ref_simulate(static_emb, static_cls, emb, lab,
                                    c, krites=False)["correct"]
                       for c in cfgs])
        hit = sb != MISS
        bad = hit & ~cr
        explore = (rng >> 17) % 1_000_000 < int(p.epsilon * 1_000_000)
        for s in active:
            start, cands, ci = spans[s]
            mask = sg == s
            n_seg = int(mask.sum())
            hits = [int((hit[start + k] & mask).sum())
                    for k in range(len(cands))]
            errs = [int((bad[start + k] & mask).sum())
                    for k in range(len(cands))]
            pick = (lcg_next(rng + s) >> 11) if explore else None
            k, reason = choose_candidate(hits, errs, n_seg, ci, p, pick)
            g, _ = choose_candidate(hits, errs, n_seg, ci, p, None)
            regret[s] += max(0, hits[g] - hits[ci])
            if reason == "explore":
                explores += 1
            cs, cd = tau_s[s], tau_d[s]
            ts = cs + min(max(cands[k][0] - cs, -p.max_step), p.max_step)
            td = cd + min(max(cands[k][1] - cd, -p.max_step), p.max_step)
            ts = min(max(ts, p.tau_lo), p.tau_hi)
            td = min(max(td, p.tau_lo), p.tau_hi)
            if (ts, td) != (tau_s[s], tau_d[s]):
                moves += 1
                tau_s[s], tau_d[s] = ts, td
        tau_trail.append((t, list(tau_s), list(tau_d)))

    return {
        "served_by": served_by, "tau_static": tau_s, "tau_dynamic": tau_d,
        "tau_trail": tau_trail, "adaptations": adaptations,
        "moves": moves, "explores": explores, "regret": regret,
        "count": count,
    }
