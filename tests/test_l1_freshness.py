"""L1 exact-match front tier + freshness subsystem conformance
(``core/exact_tier.py``, ``core/freshness.py``, DESIGN.md §16).

Four contracts, each with its own section:

1. Canonicalization properties — equal canonical forms (case folds,
   whitespace runs, composed/decomposed unicode) always alias one L1
   entry; distinct canonical forms never do. Property-based via the
   ``_hypothesis_compat`` shim, so the tests run with or without
   hypothesis installed.
2. TTL monotonicity properties — a longer cache life never expires an
   entry sooner (0 = unbounded sits at the top of the order), liveness
   is downward-closed in time, and ``tiers.evict_expired``'s per-entry
   path is bit-identical to the legacy global-``ttl`` wrapper on the
   induced ``expires_at = written_at + ttl`` stamps.
3. Live-policy serving — the headline acceptance gates: ZERO embedder
   calls on a pure-repeat trace (scalar and batched), decision
   agreement 1.0 vs a no-L1 twin on non-repeat traffic, volatile
   bypass leaving the cache untouched, and L1/dynamic entries dying on
   their per-class TTL.
4. Crash recovery — SIGKILL a serving child after it snapshots a
   policy holding live + expired L1 entries and TTL-stamped dynamic
   entries; the warm restore must drop the expired entries (no
   resurrection), serve the live ones from L1, and make every
   subsequent decision field-identically to an uninterrupted policy.

Determinism: orthonormal prompt pools (pairwise similarity 0, so every
threshold decision is unambiguous), judge workers disabled.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import textwrap
import time
import unicodedata
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import tiers as T
from repro.core.exact_tier import ExactTier, canonicalize
from repro.core.freshness import (FreshnessPolicy, STABLE, UNKNOWN,
                                  VOLATILE, classify)
from repro.core.policy import KritesPolicy

from _hypothesis_compat import given, settings, st

# ---------------------------------------------------------------------------
# 1. canonicalization properties
# ---------------------------------------------------------------------------

# tokens chosen to exercise every canonicalization axis: casefold
# beyond lower() ("Straße"/"STRASSE"), composed vs decomposed accents
# ("café" vs "café"), plain ASCII, and a non-letter token
_TOKENS = ["Straße", "café", "café", "WEATHER", "émigré",
           "hello", "42nd", "ß"]
_WS = [" ", "  ", "\t", "\n", " \t ", " ", "\r\n"]
_CASERS = [str.lower, str.upper, str.title, lambda s: s]


def _variant(tokens, seps, casers, nfd):
    """One surface form of ``tokens``: per-token case mutation, a
    chosen whitespace run between tokens, optional NFD re-encoding of
    the whole string."""
    parts = [c(t) for t, c in zip(tokens, casers)]
    out = seps[0].join([""] + parts) + seps[1]     # ragged edges too
    return unicodedata.normalize("NFD", out) if nfd else out


_tok_lists = st.lists(st.sampled_from(_TOKENS), min_size=1, max_size=5)
_two_seps = st.tuples(st.sampled_from(_WS), st.sampled_from(_WS))
_case_picks = st.lists(st.sampled_from(_CASERS), min_size=5, max_size=5)


@settings(max_examples=60)
@given(_tok_lists, _two_seps, _case_picks, st.booleans())
def test_canonicalize_collapses_surface_variants(tokens, seps, casers,
                                                 nfd):
    base = canonicalize(" ".join(tokens))
    var = _variant(tokens, seps, casers, nfd)
    assert canonicalize(var) == base
    # idempotence: canonical forms are fixed points
    assert canonicalize(base) == base
    # canonical forms carry no leading/trailing/doubled whitespace
    assert base == " ".join(base.split())


@settings(max_examples=60)
@given(_tok_lists, _two_seps, _case_picks, st.booleans())
def test_l1_aliases_equal_canonical_forms(tokens, seps, casers, nfd):
    """put() under one surface form, get() under another: same entry."""
    tier = ExactTier(capacity=8)
    base = " ".join(tokens)
    tier.put(canonicalize(base), "answer-0", content_t=3, now=1)
    var = _variant(tokens, seps, casers, nfd)
    e = tier.get(canonicalize(var), now=2)
    assert e is not None and e.answer == "answer-0"
    assert e.content_t == 3
    assert len(tier) == 1          # one entry, not a variant per form


@settings(max_examples=60)
@given(_tok_lists, _tok_lists)
def test_l1_never_aliases_distinct_canonical_forms(toks_a, toks_b):
    ka = canonicalize(" ".join(toks_a))
    kb = canonicalize(" ".join(toks_b))
    if ka == kb:                   # same canonical form: out of scope
        return
    tier = ExactTier(capacity=8)
    tier.put(ka, "A", now=1)
    tier.put(kb, "B", now=2)
    assert tier.get(ka, now=3).answer == "A"
    assert tier.get(kb, now=3).answer == "B"
    assert len(tier) == 2


def test_classify_is_surface_form_invariant():
    """The staleness class keys off canonical tokens, so phrasing noise
    (case, whitespace, unicode form) never flips a class."""
    assert classify("what is the PRICE of eggs") == VOLATILE
    assert classify("  what\tis the price of eggs ") == VOLATILE
    assert classify("DEFINE perihelion") == STABLE
    assert classify("tell me about turtles") == UNKNOWN


# ---------------------------------------------------------------------------
# 2. TTL monotonicity properties
# ---------------------------------------------------------------------------

def _lifetime(ttl: int) -> float:
    """Effective cache life under the 0-means-never contract."""
    return float("inf") if ttl == 0 else float(ttl)


def _live(exp: int, now: int) -> bool:
    """The subsystem-wide liveness rule (tiers.live_mask, ExactTier.get,
    the simulator, the numpy oracle): live while now <= expires_at."""
    return exp == 0 or now <= exp


@settings(max_examples=80)
@given(st.integers(0, 64), st.integers(0, 64), st.integers(1, 100),
       st.integers(0, 200))
def test_ttl_monotone_longer_life_never_dies_sooner(ttl_a, ttl_b, wr,
                                                    dt):
    """If ttl_b grants at least ttl_a's lifetime, then at every probe
    tick an entry live under ttl_a is live under ttl_b."""
    if _lifetime(ttl_b) < _lifetime(ttl_a):
        ttl_a, ttl_b = ttl_b, ttl_a
    f_a = FreshnessPolicy(ttl_volatile=ttl_a)
    f_b = FreshnessPolicy(ttl_volatile=ttl_b)
    exp_a = f_a.expires_at("price now", wr)
    exp_b = f_b.expires_at("price now", wr)
    now = wr + dt
    if _live(exp_a, now):
        assert _live(exp_b, now), (ttl_a, ttl_b, wr, now)


@settings(max_examples=80)
@given(st.integers(0, 64), st.integers(1, 100), st.integers(0, 100),
       st.integers(0, 100))
def test_ttl_liveness_downward_closed_in_time(ttl, wr, d1, d2):
    """An entry dead at some tick never comes back later — and the
    ExactTier probe agrees with the pure liveness predicate."""
    exp = wr + ttl if ttl > 0 else 0
    n1, n2 = wr + min(d1, d2), wr + max(d1, d2)
    if not _live(exp, n1):
        assert not _live(exp, n2)
    tier = ExactTier(capacity=4)
    tier.put("k", "v", expires_at=exp, now=wr)
    assert (tier.get("k", now=n1) is not None) == _live(exp, n1)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64), st.integers(1, 200))
def test_evict_expired_per_entry_matches_legacy_ttl(seed, ttl, now):
    """Satellite pin: the per-entry ``expires_at`` path of
    ``tiers.evict_expired`` is bit-identical to the legacy global-ttl
    wrapper on the stamps it induces, and ttl=0 stays a no-op."""
    rng = np.random.default_rng(seed)
    cap = 16
    tier = T.make_dynamic_tier(cap, 4)._replace(
        valid=jnp.asarray(rng.integers(0, 2, cap).astype(bool)),
        written_at=jnp.asarray(rng.integers(0, 200, cap), jnp.int32))
    legacy = T.evict_expired(tier, now=now, ttl=ttl)
    per_entry = T.evict_expired(
        tier._replace(expires_at=(tier.written_at + ttl)
                      .astype(jnp.int32)), now=now)
    assert np.array_equal(np.asarray(legacy.valid),
                          np.asarray(per_entry.valid))
    # ttl=0 = disabled: nothing dies, no matter how old
    untouched = T.evict_expired(tier, now=10**9, ttl=0)
    assert np.array_equal(np.asarray(untouched.valid),
                          np.asarray(tier.valid))
    # exp=0 rows never expire on the per-entry path either
    never = T.evict_expired(tier, now=10**9)
    assert np.array_equal(np.asarray(never.valid),
                          np.asarray(tier.valid))


# ---------------------------------------------------------------------------
# 3. live-policy serving gates
# ---------------------------------------------------------------------------

D, S = 32, 6


def _pool(n, d, seed=0):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(d, n)))
    return np.ascontiguousarray(q.T, np.float32)


P = _pool(32, D)
# prompt texts carry their freshness class; embeddings are orthonormal
# to the static tier and each other, so every one is a semantic miss
VOL_PROMPTS = [f"price of item {i}" for i in range(4)]          # volatile
STA_PROMPTS = [f"define object {i}" for i in range(12)]         # stable
UNK_PROMPTS = [f"tell me about thing {i}" for i in range(10)]   # unknown
ALL_PROMPTS = VOL_PROMPTS + STA_PROMPTS + UNK_PROMPTS
EMB = {p: P[S + i] for i, p in enumerate(ALL_PROMPTS)}


def _mk(l1=None, freshness=None, capacity=16, embed_fn=None):
    tier = T.StaticTier(emb=jnp.asarray(P[:S]),
                        cls=jnp.arange(S, dtype=jnp.int32),
                        answer_ref=jnp.arange(S, dtype=jnp.int32))
    cfg = T.CacheConfig(0.95, 0.9, sigma_min=0.3, capacity=capacity)
    return KritesPolicy(cfg, tier, [f"a{i}" for i in range(S)],
                        embed_fn=embed_fn or (lambda p: EMB[p]),
                        backend_fn=lambda p: f"gen({p})",
                        judge_fn=lambda **kw: True, d=D, n_workers=0,
                        l1=l1, freshness=freshness)


def _dec(r):
    return (r.served_by, str(r.answer), bool(r.static_origin),
            round(float(r.similarity), 5), bool(r.meta.get("stale")))


def test_pure_repeat_trace_costs_zero_embed_calls_scalar():
    """The headline L1 gate: after the cold pass, byte-identical (up to
    canonicalization) repeats never reach the embedder or either
    semantic lookup."""
    calls = []

    def embed(p):
        calls.append(p)
        return EMB[p]

    pol = _mk(l1=64, embed_fn=embed)
    base = UNK_PROMPTS[:8]
    cold = [pol.serve(p) for p in base]
    assert len(calls) == len(base)
    assert all(r.served_by == "backend" for r in cold)

    for _ in range(3):
        for p, c in zip(base, cold):
            r = pol.serve(p)
            assert r.served_by == "l1"
            assert r.answer == c.answer
    assert len(calls) == len(base), "repeats paid the embedder"
    assert pol._l1_hits == 3 * len(base)

    # canonical variants are repeats too — EMB has no entry for these
    # surface forms, so touching the embedder would KeyError
    for var in ("  Tell me ABOUT thing 0 ", "tell\tme about thing 1",
                unicodedata.normalize("NFD", "Tell me about thing 2")):
        assert pol.serve(var).served_by == "l1"
    assert len(calls) == len(base)


def test_pure_repeat_batch_costs_zero_embed_calls():
    """Batched twin: a warm pure-repeat batch embeds nothing; a cold
    batch with in-batch exact duplicates embeds each canonical form
    once (the producer row) and serves the dups from it."""
    calls = []

    def embed(p):
        calls.append(p)
        return EMB[p]

    pol = _mk(l1=64, embed_fn=embed)
    base = UNK_PROMPTS[:6]
    cold = pol.serve_batch(base)
    assert len(calls) == len(base)

    warm = pol.serve_batch(list(base) + ["TELL me about thing 0  "])
    assert len(calls) == len(base), "warm batch paid the embedder"
    assert all(r.served_by == "l1" for r in warm)
    assert [r.answer for r in warm[:-1]] == [r.answer for r in cold]
    assert warm[-1].answer == cold[0].answer

    # in-batch duplicates: one embed for the producer, dups ride along
    pol2 = _mk(l1=64, embed_fn=embed)
    n0 = len(calls)
    rs = pol2.serve_batch(["define object 0", "DEFINE object 0",
                           "define  object 0"])
    assert len(calls) == n0 + 1
    assert rs[0].served_by == "backend"
    assert [r.served_by for r in rs[1:]] == ["l1", "l1"]
    assert {r.answer for r in rs} == {rs[0].answer}


@pytest.mark.parametrize("batched", [False, True],
                         ids=["scalar", "batched"])
def test_l1_decision_agreement_on_non_repeat_traffic(batched):
    """Acceptance gate: on traffic with no exact repeats the L1 policy
    and its no-L1 twin make field-identical decisions — the front tier
    is invisible to semantic serving. Both twins share the freshness
    TTLs so the expiry path is exercised under agreement too."""
    fresh = dict(volatile_bypass=False, ttl_volatile=4, ttl_stable=0,
                 ttl_unknown=0)
    with_l1 = _mk(l1=64, freshness=FreshnessPolicy(**fresh), capacity=8)
    without = _mk(l1=None, freshness=FreshnessPolicy(**fresh), capacity=8)

    # every prompt distinct (capacity 8 < 26 prompts: LRU churn and
    # volatile TTL deaths both happen mid-trace)
    trace = [p for pair in zip(ALL_PROMPTS[::-1], ALL_PROMPTS)
             for p in pair][:26]
    seen = set()
    trace = [p for p in trace if not (p in seen or seen.add(p))]
    if batched:
        got = [_dec(r) for r in with_l1.serve_batch(trace)]
        want = [_dec(r) for r in without.serve_batch(trace)]
    else:
        got = [_dec(with_l1.serve(p)) for p in trace]
        want = [_dec(without.serve(p)) for p in trace]
    agreement = sum(g == w for g, w in zip(got, want)) / len(trace)
    assert agreement == 1.0, list(zip(got, want))
    assert with_l1._l1_hits == 0            # nothing repeated
    assert with_l1.l1.stats()["l1_misses"] > 0   # but L1 was probed
    assert np.array_equal(with_l1._valid_np, without._valid_np)
    assert np.array_equal(with_l1._expires_np, without._expires_np)


def test_volatile_bypass_serves_backend_and_touches_nothing():
    calls = []

    def embed(p):
        calls.append(p)
        return EMB[p]

    pol = _mk(l1=16, freshness=FreshnessPolicy(volatile_bypass=True,
                                               ttl_volatile=4),
              embed_fn=embed)
    r = pol.serve(VOL_PROMPTS[0])
    assert r.served_by == "backend"
    assert r.meta.get("bypass") == "volatile"
    assert calls == []                      # no embed
    assert len(pol.l1) == 0                 # no L1 write-back
    assert not pol._valid_np.any()          # no dynamic write
    assert pol._l1_bypass == 1
    # repeats stay bypassed: still no cache, still no embed
    assert pol.serve(VOL_PROMPTS[0]).served_by == "backend"
    assert calls == [] and len(pol.l1) == 0
    # batched path agrees
    rs = pol.serve_batch([VOL_PROMPTS[1], UNK_PROMPTS[0]])
    assert rs[0].meta.get("bypass") == "volatile"
    assert rs[1].served_by == "backend" and "bypass" not in rs[1].meta
    assert calls == [UNK_PROMPTS[0]]
    assert pol._l1_bypass == 3


def test_per_class_ttl_expires_l1_and_dynamic_entries():
    """Volatile entries die after ttl_volatile ticks on BOTH tiers;
    stable entries (ttl 0) never do."""
    pol = _mk(l1=16, freshness=FreshnessPolicy(volatile_bypass=False,
                                               ttl_volatile=3,
                                               ttl_stable=0))
    pol.serve(VOL_PROMPTS[0])               # t=1, expires_at=4
    pol.serve(STA_PROMPTS[0])               # t=2, never expires
    assert pol.serve(VOL_PROMPTS[0]).served_by == "l1"   # t=3 <= 4
    for p in UNK_PROMPTS[:4]:               # t=4..7: clock past expiry
        pol.serve(p)
    r = pol.serve(VOL_PROMPTS[0])           # t=8 > 4: dead everywhere
    assert r.served_by == "backend"
    assert pol.l1.stats()["l1_ttl_evictions"] >= 1
    assert pol._ttl_evictions >= 1          # dynamic twin died eagerly
    assert pol.serve(STA_PROMPTS[0]).served_by == "l1"   # still live


def test_stale_accounting_flags_drifted_volatile_hits():
    """With a drift clock, a volatile L1 hit whose content dates from
    an earlier epoch is served but flagged + counted stale."""
    pol = _mk(l1=16, freshness=FreshnessPolicy(volatile_bypass=False,
                                               ttl_volatile=64,
                                               drift_every=4))
    pol.serve(VOL_PROMPTS[0])               # t=1: content epoch 0
    r = pol.serve(VOL_PROMPTS[0])           # t=2: same epoch — fresh
    assert r.served_by == "l1" and "stale" not in r.meta
    for p in UNK_PROMPTS[:3]:               # advance to t=5 (epoch 1)
        pol.serve(p)
    r = pol.serve(VOL_PROMPTS[0])           # t=6: epoch drifted
    assert r.served_by == "l1" and r.meta.get("stale") is True
    assert pol._stale_serves == 1
    # stable hits never flag, whatever the epoch distance
    pol.serve(STA_PROMPTS[0])
    for p in UNK_PROMPTS[3:8]:
        pol.serve(p)
    r = pol.serve(STA_PROMPTS[0])
    assert r.served_by == "l1" and "stale" not in r.meta
    assert pol._stale_serves == 1


# ---------------------------------------------------------------------------
# 4. SIGKILL crash recovery with live + expired L1/TTL state
# ---------------------------------------------------------------------------

SRC = str(Path(__file__).resolve().parent.parent / "src")
ENV = {
    "PYTHONPATH": SRC,
    "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
    "JAX_PLATFORMS": "cpu",
    "PYTHONUNBUFFERED": "1",
}

# Shared world: child process (snapshot side) and parent (recovery +
# reference side) exec the same block, so the comparison is
# apples-to-apples. The drive leaves the snapshot holding every
# interesting freshness state at t=14: two EXPIRED L1 entries (early
# volatile, exp 4/5, never re-touched so lazily still present), two
# LIVE TTL-stamped L1 + dynamic entries (late volatile, exp 16/17),
# ten unbounded stable entries, and >0 eager dynamic TTL evictions.
COMMON = textwrap.dedent("""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import tiers as T
    from repro.core.freshness import FreshnessPolicy
    from repro.core.policy import KritesPolicy

    D, S = 32, 4

    def _pool(n, d, seed=0):
        rng = np.random.default_rng(seed)
        q, _ = np.linalg.qr(rng.normal(size=(d, n)))
        return np.ascontiguousarray(q.T, np.float32)

    P = _pool(32, D)
    VOL_OLD = [f"price of relic {i}" for i in range(2)]
    STA = [f"define artifact {i}" for i in range(10)]
    VOL_NEW = [f"price of gadget {i}" for i in range(2)]
    NEW = [f"tell me about widget {i}" for i in range(6)]
    ALL = VOL_OLD + STA + VOL_NEW + NEW
    EMB = {p: P[S + i] for i, p in enumerate(ALL)}

    def mk_policy():
        tier = T.StaticTier(emb=jnp.asarray(P[:S]),
                            cls=jnp.arange(S, dtype=jnp.int32),
                            answer_ref=jnp.arange(S, dtype=jnp.int32))
        cfg = T.CacheConfig(0.95, 0.9, sigma_min=0.3, capacity=16)
        return KritesPolicy(
            cfg, tier, [f"a{i}" for i in range(S)],
            embed_fn=lambda p: EMB[p],
            backend_fn=lambda p: "gen(" + p + ")",
            judge_fn=lambda **kw: True, d=D, n_workers=0,
            l1=64, freshness=FreshnessPolicy(volatile_bypass=False,
                                             ttl_volatile=3,
                                             ttl_stable=0,
                                             ttl_unknown=0))

    def drive_prefix(pol):
        for p in VOL_OLD:         # t=1,2  -> expires_at 4,5
            pol.serve(p)
        for p in STA:             # t=3..12 -> never expire
            pol.serve(p)
        for p in VOL_NEW:         # t=13,14 -> expires_at 16,17 (live)
            pol.serve(p)
""")

CHILD = COMMON + textwrap.dedent("""
    import sys
    from pathlib import Path
    from repro.serving import persist

    snap = Path(sys.argv[1])
    pol = mk_policy()
    drive_prefix(pol)
    persist.save_snapshot(snap, pol)
    print("SNAP", flush=True)
    for p in NEW:                 # post-snapshot tail: lost to the kill
        pol.serve(p)
    print("DONE", flush=True)
""")

_NS: dict = {}


def _ns():
    if not _NS:
        exec(COMMON, _NS)
    return _NS


def _run_child_killed_after_snap(tmp: Path):
    proc = subprocess.Popen([sys.executable, "-c", CHILD, str(tmp)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=ENV)
    try:
        deadline = time.monotonic() + 300
        for line in proc.stdout:
            assert time.monotonic() < deadline, "child wedged"
            if line.strip() == "SNAP":
                os.kill(proc.pid, signal.SIGKILL)
                break
            assert line.strip() != "DONE", "missed the kill window"
        else:
            pytest.fail(f"child died early:\n{proc.stderr.read()}")
        proc.wait(timeout=60)
    finally:
        proc.stderr.close()
        proc.stdout.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)


def test_sigkill_freshness_recovery(tmp_path):
    from repro.serving import persist

    _run_child_killed_after_snap(tmp_path)
    ns = _ns()

    # the snapshot itself holds the expired L1 rows (lazy expiry): 14
    # entries saved, exactly the two early-volatile ones already dead
    snap = persist.load_snapshot(tmp_path)
    l1_saved = snap.extra["l1"]
    assert len(l1_saved) == 14
    t_snap = 14
    dead_keys = {k for k, *_rest, exp, _wr in
                 [(e[0], e[4], e[5]) for e in l1_saved]
                 if 0 < exp < t_snap}
    assert dead_keys == {f"price of relic {i}" for i in range(2)}

    restored = ns["mk_policy"]()
    rep = persist.restore_policy(restored, snap)
    # no resurrection: expired L1 entries dropped at restore time
    assert rep["l1_restored"] == 12
    assert all(not (0 < e.expires_at < restored.t)
               for e in restored.l1._od.values())
    assert restored.t == t_snap

    # uninterrupted reference: same prefix, never crashed
    reference = ns["mk_policy"]()
    ns["drive_prefix"](reference)
    assert np.array_equal(restored._valid_np, reference._valid_np)
    assert np.array_equal(restored._expires_np, reference._expires_np)
    assert np.array_equal(restored._written_at_np,
                          reference._written_at_np)
    assert reference._ttl_evictions > 0     # early volatile dyn rows died

    # decision sweep: live L1 entries serve, expired ones re-resolve,
    # TTL'd entries keep dying on schedule — field-identical throughout
    probe = (ns["STA"][:3]                  # live L1 -> 'l1'
             + ["DEFINE  artifact 0"]       # canonical variant -> 'l1'
             + ns["VOL_OLD"]                # expired -> semantic path
             + ns["VOL_NEW"]                # exp 16/17 vs ticks 21,22
             + ns["NEW"]                    # fresh misses
             + ns["NEW"][:2])               # then repeats -> 'l1'
    got = [_dec(restored.serve(p)) for p in probe]
    want = [_dec(reference.serve(p)) for p in probe]
    assert got == want
    assert got[0][0] == "l1" and got[3][0] == "l1"
    assert got[4][0] != "l1" and got[5][0] != "l1"   # stayed dead
    assert got[-2][0] == "l1" and got[-1][0] == "l1"
    assert np.array_equal(restored._valid_np, reference._valid_np)
    assert np.array_equal(restored._expires_np, reference._expires_np)
