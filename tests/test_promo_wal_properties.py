"""Property tests for the write-ahead promotion journal
(``core/promo_wal.py``, DESIGN.md §14), via the ``_hypothesis_compat``
shim (full hypothesis when installed, the deterministic fallback runner
otherwise).

The properties pinned here are the ones crash recovery rests on:

1. **frame round-trip** — encode/append/scan reproduces every record,
   in order, with bit-exact fp32 vectors (a decimal round-trip could
   move a key across the 0.9999 dedup threshold);
2. **prefix-crash safety** — a journal cut at ANY byte offset (torn
   append) or with any single byte corrupted still scans to a valid
   prefix of the original records, never raises, and reopening the WAL
   truncates the damage so subsequent appends produce a clean journal;
3. **replay idempotence** — replaying a journal N times into a policy
   leaves exactly the state of one replay;
4. **LWW interleaving** — randomized promotion sequences (shared keys,
   shuffled ``enq_t``) replay to the same final tier state as live
   application, and both agree with the independent numpy oracle
   (``ref_policy._Dyn.upsert``);
5. **compaction** — dropping the seq-prefix a snapshot covers keeps
   every snapshot ``wal_seq`` cursor valid and appends continuing the
   original seq numbering. The journal is the ADMITTED subsequence of
   the promotion stream (LWW-skipped promotions never journal), so the
   cursor arithmetic runs through the same admission rule.

Property tests manage their own per-example temp dirs (the shim's
fallback runner hides the wrapped signature, so pytest fixtures cannot
be injected into ``@given`` tests).
"""
from __future__ import annotations

import tempfile
from contextlib import contextmanager
from pathlib import Path

import numpy as np
from _hypothesis_compat import given, settings, st
from ref_policy import _Dyn

import jax.numpy as jnp

from repro.core import tiers as T
from repro.core.policy import KritesPolicy
from repro.core.promo_wal import (PromotionWAL, compact, decode_vector,
                                  encode_record, read_wal, replay_into,
                                  scan_wal)

D, S, CAP = 16, 8, 8


def _unit_pool(n: int, d: int = D, seed: int = 0) -> np.ndarray:
    """n well-separated unit vectors (pairwise sim far below the 0.9999
    dedup threshold), deterministic."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(d, n)))
    return np.ascontiguousarray(q.T, np.float32)


POOL = _unit_pool(S)
STATIC = T.StaticTier(emb=jnp.asarray(_unit_pool(S, seed=9)),
                      cls=jnp.arange(S, dtype=jnp.int32),
                      answer_ref=jnp.arange(S, dtype=jnp.int32))


@contextmanager
def _wal_path():
    with tempfile.TemporaryDirectory(prefix="pwal-test-") as tmp:
        yield Path(tmp) / "w.wal"


def _policy(wal=None) -> KritesPolicy:
    cfg = T.CacheConfig(0.95, 0.9, sigma_min=0.3, capacity=CAP)
    return KritesPolicy(cfg, STATIC, [f"a{i}" for i in range(S)],
                        embed_fn=lambda p: np.zeros(D, np.float32),
                        backend_fn=lambda p: "b",
                        judge_fn=lambda **kw: True, d=D,
                        n_workers=0, wal=wal)


def _payloads(ops):
    """(key_id, h_idx, enq_t) triples -> _promote payloads over POOL."""
    return [{"v": POOL[k], "h_idx": h, "enq_t": t} for k, h, t in ops]


def _admitted(ops):
    """Indices of the ops the WAL admits. ``_promote`` journals only
    promotions that actually apply: a record whose key already holds a
    strictly newer ``enq_t`` is LWW-skipped — no tier write, no WAL
    record — so the journal is a subsequence of the op stream. POOL
    keys are orthonormal (dedup is exact-match) and CAP covers every
    distinct key, so per-key max-enq_t bookkeeping models admission
    exactly."""
    latest: dict = {}
    out = []
    for i, (k, h, t) in enumerate(ops):
        if k in latest and latest[k] > t:
            continue
        latest[k] = t
        out.append(i)
    return out


def _state(pol: KritesPolicy) -> tuple:
    return (np.asarray(pol.dyn.emb).tobytes(),
            pol._valid_np.tolist(), pol._written_at_np.tolist(),
            pol._last_used_np.tolist(), pol._static_origin_np.tolist(),
            np.asarray(pol.dyn.cls).tolist(),
            np.asarray(pol.dyn.answer_ref).tolist(),
            list(pol.dyn_answers))


# an op stream: which pool vector (keys repeat -> dedup/LWW paths),
# which static neighbor, and a shuffled logical enqueue time
OPS = st.lists(st.tuples(st.integers(0, S - 1), st.integers(0, S - 1),
                         st.integers(1, 30)), min_size=1, max_size=24)


# ---------------------------------------------------------------------------
# 1. frame round-trip
# ---------------------------------------------------------------------------

def test_vector_roundtrip_bit_exact():
    rng = np.random.default_rng(1)
    for _ in range(50):
        v = rng.normal(size=D).astype(np.float32) * \
            np.float32(rng.choice([1e-20, 1.0, 1e20]))
        rec = encode_record(v, 0, 1)
        assert decode_vector(rec).tobytes() == v.tobytes()


@given(OPS)
@settings(max_examples=25, deadline=None)
def test_append_scan_roundtrip(ops):
    with _wal_path() as path:
        with PromotionWAL(path, fsync_every=4) as wal:
            for k, h, t in ops:
                wal.append(encode_record(POOL[k], h, t))
        records, clean = read_wal(path)
        assert clean and len(records) == len(ops)
        for i, (rec, (k, h, t)) in enumerate(zip(records, ops)):
            assert rec["seq"] == i + 1
            assert (rec["h_idx"], rec["enq_t"]) == (h, t)
            assert decode_vector(rec).tobytes() == POOL[k].tobytes()


# ---------------------------------------------------------------------------
# 2. prefix-crash safety
# ---------------------------------------------------------------------------

@given(OPS, st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_any_truncation_scans_to_valid_prefix(ops, cut_frac):
    with _wal_path() as path:
        with PromotionWAL(path, fsync_every=1) as wal:
            for k, h, t in ops:
                wal.append(encode_record(POOL[k], h, t))
        data = path.read_bytes()
        cut = int(len(data) * cut_frac)
        path.write_bytes(data[:cut])              # torn tail
        records, clean, valid_bytes = scan_wal(path)
        assert len(records) <= len(ops)
        for i, rec in enumerate(records):         # a prefix, in order
            assert rec["seq"] == i + 1
        assert valid_bytes <= cut
        # reopening truncates the damage; appends continue the seq
        with PromotionWAL(path, fsync_every=1) as wal:
            assert wal.seq == len(records)
            wal.append(encode_record(POOL[0], 0, 99))
        records2, clean2 = read_wal(path)
        assert clean2 and len(records2) == len(records) + 1
        assert records2[-1]["seq"] == len(records) + 1


@given(OPS, st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_single_byte_corruption_never_raises(ops, pos_frac):
    with _wal_path() as path:
        with PromotionWAL(path, fsync_every=1) as wal:
            for k, h, t in ops:
                wal.append(encode_record(POOL[k], h, t))
        data = bytearray(path.read_bytes())
        pos = min(int(len(data) * pos_frac), len(data) - 1)
        data[pos] ^= 0xFF
        path.write_bytes(bytes(data))
        records, clean, _ = scan_wal(path)        # must not raise
        if pos >= 8:                              # header intact
            for i, rec in enumerate(records):
                assert rec["seq"] == i + 1
        else:
            assert records == [] and not clean


# ---------------------------------------------------------------------------
# 3. + 4. replay idempotence and LWW, vs live state and the numpy oracle
# ---------------------------------------------------------------------------

@given(OPS, st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_replay_idempotent_and_matches_live(ops, n_replays):
    with _wal_path() as path:
        live = _policy(wal=PromotionWAL(path, fsync_every=1))
        for p in _payloads(ops):
            live._promote(p)
        live.wal.close()
        want = _state(live)

        fresh = _policy()
        for _ in range(n_replays):
            rep = replay_into(fresh, path)
            assert rep["clean"]
        assert _state(fresh) == want, \
            f"{n_replays} replays != live application"


@given(OPS)
@settings(max_examples=15, deadline=None)
def test_lww_interleaving_matches_numpy_oracle(ops):
    """Same op stream through three implementations — live policy,
    journal replay, and the independent ``ref_policy._Dyn`` upsert loop
    — must agree on every tier field (valid/written_at/emb/slots)."""
    with _wal_path() as path:
        live = _policy(wal=PromotionWAL(path, fsync_every=1))
        oracle = _Dyn.make(CAP, D)
        ref_np = np.asarray(STATIC.answer_ref)
        cls_np = np.asarray(STATIC.cls)
        for k, h, t in ops:
            live._promote({"v": POOL[k], "h_idx": h, "enq_t": t})
            # the policy never serves here, so its live clock stays 0:
            # apply time (LRU clock) 0, enqueue time (LWW clock) t
            oracle.upsert(POOL[k], int(cls_np[h]), int(ref_np[h]), 0,
                          enq=t)
        live.wal.close()

        replayed = _policy()
        replay_into(replayed, path)

        for pol in (live, replayed):
            assert pol._valid_np.tolist() == oracle.valid.tolist()
            assert pol._written_at_np.tolist() == \
                oracle.written_at.tolist()
            assert pol._last_used_np.tolist() == \
                oracle.last_used.tolist()
            assert np.array_equal(
                np.asarray(pol.dyn.emb)[pol._valid_np],
                oracle.emb[oracle.valid])
            assert np.asarray(pol.dyn.cls).tolist() == \
                oracle.cls.tolist()
            assert np.asarray(pol.dyn.answer_ref).tolist() == \
                oracle.answer_ref.tolist()


def test_stale_replay_cannot_clobber_newer_write(tmp_path):
    """Direct LWW pin: a journaled promotion older than the entry now
    holding its key must be a no-op on replay (the crash-recovery twin
    of test_promotion_and_payload.test_stale_promote_skips...)."""
    path = tmp_path / "w.wal"
    wal = PromotionWAL(path, fsync_every=1)
    wal.append(encode_record(POOL[0], 0, 5))     # journaled at t=5
    wal.close()

    pol = _policy()
    pol._promote({"v": POOL[0], "h_idx": 1, "enq_t": 10},
                 journal=False)                  # newer write, same key
    before = _state(pol)
    rep = replay_into(pol, path)
    assert rep["replayed"] == 1
    assert _state(pol) == before, \
        "stale journal record clobbered a newer write"
    slot = int(np.argmax(pol._valid_np))
    assert pol._written_at_np[slot] == 10


def test_equal_timestamp_replay_beats_miss_insert(tmp_path):
    """A promotion and a miss-insert of the same key at the same
    logical time: the promotion wins live (strict-> LWW guard), so it
    must also win on replay — recovery keeps the promoted provenance."""
    path = tmp_path / "w.wal"
    wal = PromotionWAL(path, fsync_every=1)
    wal.append(encode_record(POOL[2], 3, 7))
    wal.close()

    pol = _policy()
    with pol.dyn_lock:   # the miss-insert twin: same key, same t
        slot = pol._host_lru_slot()
        pol.dyn = pol._write_fn(pol.dyn, slot, jnp.asarray(POOL[2]),
                                jnp.int32(-1), jnp.int32(-1),
                                jnp.asarray(False), 7)
        pol._mirror_write(slot, 7, static_origin=False)
        pol.dyn_answers[slot] = "miss"
    replay_into(pol, path)
    assert bool(pol._static_origin_np[slot])
    assert pol.dyn_answers[slot] == "a3"


# ---------------------------------------------------------------------------
# 5. compaction
# ---------------------------------------------------------------------------

@given(OPS, st.floats(0.0, 1.0))
@settings(max_examples=15, deadline=None)
def test_compact_preserves_cursor_and_seq(ops, keep_frac):
    with _wal_path() as path:
        live = _policy(wal=PromotionWAL(path, fsync_every=1))
        for p in _payloads(ops):
            live._promote(p)
        live.wal.close()
        want = _state(live)
        adm = _admitted(ops)         # journal = admitted subsequence
        cursor = int(len(adm) * keep_frac)     # a snapshot's wal_seq

        # state-at-cursor + replay-of-tail must still reach `want`
        # whether or not the prefix has been compacted away
        kept = compact(path, keep_from_seq=cursor)
        assert kept == len(adm) - cursor
        recovered = _policy()
        # the snapshot at wal_seq=cursor held the state after the op
        # that produced journal record `cursor`; LWW-skipped ops in
        # between are state no-ops, so replaying the op prefix through
        # that point reconstructs it exactly
        n_at_cursor = adm[cursor - 1] + 1 if cursor else 0
        for p in _payloads(ops[:n_at_cursor]):
            recovered._promote(p, journal=False)
        rep = replay_into(recovered, path, skip=cursor)
        assert rep["skipped"] == 0 and rep["replayed"] == kept
        assert _state(recovered) == want

        # appends after compaction continue the original numbering
        with PromotionWAL(path, fsync_every=1) as wal:
            assert wal.seq == len(adm)
            assert wal.append(encode_record(POOL[0], 0, 50)) \
                == len(adm) + 1
