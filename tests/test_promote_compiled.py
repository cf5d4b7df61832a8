"""Promotions run their device work as two compiled programs.

``KritesPolicy._apply_promotion`` takes the dedup ``(score, slot)``
from the compiled ``_promote_lookup`` and writes the row with the
compiled ``_write_fn``. These tests hold that path to the eager
sequence it replaced (``tiers.dynamic_lookup`` then ``tiers._write``),
bit for bit; check that varied slots and clocks reuse one compiled
program each; and check that a WAL append that raises leaves the tier
and its host mirrors as they were (the append precedes the write).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiers as T
from repro.core.judge import APPROVE, REWRITE, OracleJudge
from repro.core.policy import KritesPolicy, _promote_lookup
from repro.core.tiers import CacheConfig, make_static_tier

D = 8
MIRRORS = ("_valid_np", "_last_used_np", "_static_origin_np",
           "_written_at_np", "_expires_np", "_rewritten_np")


def _para(i, j, w):
    v = np.eye(D, dtype=np.float32)[i] + w * np.eye(D, dtype=np.float32)[j]
    return (v / np.linalg.norm(v)).astype(np.float32)


V_A, V_B, V_C = _para(0, 1, 0.3), _para(1, 2, 0.3), _para(2, 3, 0.3)


def _policy():
    n = 4
    tier = make_static_tier(jnp.asarray(np.eye(D, dtype=np.float32)[:n]),
                            jnp.arange(n, dtype=jnp.int32))
    return KritesPolicy(CacheConfig(0.99, 0.99, capacity=3), tier,
                        [f"curated-{i}" for i in range(n)],
                        lambda p: _para(0, 4, 0.9),
                        lambda p: f"gen({p})", OracleJudge(), d=D,
                        n_workers=1,
                        static_texts=[f"canon-{i}" for i in range(n)])


def _seeded():
    """A full three-row tier: A in slot 0 (LRU clock 9), B in slot 1
    (clock 4, the LRU victim, expiry 63), C in slot 2 (clock 11); the
    live clock then stands at 20."""
    pol = _policy()
    for t, payload in ((9, {"v": V_A, "h_idx": 0, "enq_t": 8}),
                       (4, {"v": V_B, "h_idx": 1, "enq_t": 3, "ttl": 60}),
                       (11, {"v": V_C, "h_idx": 2, "enq_t": 10})):
        pol.t = t
        pol._promote(payload)
    pol.t = 20
    return pol


def _state(pol):
    return {"tier": pol.dyn, "answers": list(pol.dyn_answers),
            **{m: getattr(pol, m).copy() for m in MIRRORS}}


def _eager_promote(state, payload, now):
    """The eager sequence: ``tiers.dynamic_lookup`` and ``tiers._write``
    op by op, with the host's dedup, LWW, TTL and slot rules."""
    out = dict(state, answers=list(state["answers"]),
               **{m: state[m].copy() for m in MIRRORS})
    enq_t, ttl = payload["enq_t"], payload.get("ttl", 0)
    exp = enq_t + ttl if ttl > 0 else 0
    if exp and exp < now:
        return out
    v = jnp.asarray(payload["v"])
    s, j = T.dynamic_lookup(state["tier"], v)
    s, j = float(s), int(j)
    dup = s >= CacheConfig(0.99, 0.99).dup_threshold
    if dup and out["_written_at_np"][j] > enq_t:
        return out
    rewrite = payload.get("outcome", APPROVE) == REWRITE
    h = payload["h_idx"]
    cls, ref, answer = ((payload["judge_args"]["q_cls"], -2,
                         payload["rewritten"]) if rewrite
                        else (h, h, f"curated-{h}"))
    slot = j if dup else int(np.where(out["_valid_np"],
                                      out["_last_used_np"],
                                      -2**30).argmin())
    out["tier"] = T._write(state["tier"], slot, v, jnp.int32(cls),
                           jnp.int32(ref), jnp.asarray(True), enq_t,
                           last_used=now, expires=exp)
    for m, val in (("_valid_np", True), ("_last_used_np", now),
                   ("_static_origin_np", True), ("_written_at_np", enq_t),
                   ("_expires_np", exp), ("_rewritten_np", rewrite)):
        out[m][slot] = val
    out["answers"][slot] = answer
    return out


def _assert_same(got, want):
    for field in T.DynamicTier._fields:
        a = np.asarray(getattr(got["tier"], field))
        b = np.asarray(getattr(want["tier"], field))
        assert a.dtype == b.dtype and a.shape == b.shape, field
        assert a.tobytes() == b.tobytes(), field
    for m in MIRRORS:
        assert np.array_equal(got[m], want[m]), m
    assert got["answers"] == want["answers"]


CASES = {
    # A again, enqueued after A's write: overwrites slot 0 in place
    "dedup_overwrite": ({"v": V_A, "h_idx": 3, "enq_t": 12}, 0),
    # a new key in a full tier: takes B's slot, the least recently used
    "fresh_lru_slot": ({"v": _para(3, 5, 0.4), "h_idx": 3, "enq_t": 15,
                        "ttl": 9}, 1),
    # C again, but enqueued before C's write: LWW leaves it alone
    "lww_skip": ({"v": V_C, "h_idx": 0, "enq_t": 7}, None),
    # expiry 5 + 10 lies before the live clock 20: nothing applies
    "past_ttl": ({"v": _para(2, 6, 0.5), "h_idx": 2, "enq_t": 5,
                  "ttl": 10}, None),
    # a tailored answer under the query's class and the -2 sentinel
    "rewrite": ({"v": _para(3, 7, 0.6), "h_idx": 3, "enq_t": 18,
                 "outcome": REWRITE, "rewritten": "tailored(q)",
                 "judge_args": {"q_cls": 5}}, 1),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiled_promotion_matches_eager_sequence(case):
    payload, slot = CASES[case]
    pol = _seeded()
    before = _state(pol)
    want = _eager_promote(before, payload, pol.t)
    pol._promote(dict(payload))
    got = _state(pol)
    pol.pool.stop()
    _assert_same(got, want)
    if slot is None:
        _assert_same(got, before)      # the case skipped, as meant
    else:
        changed = np.zeros(3, bool)
        for a, b in zip(got["tier"], before["tier"]):
            diff = np.asarray(a) != np.asarray(b)
            changed |= diff.reshape(3, -1).any(1)
        assert changed.tolist() == [s == slot for s in range(3)]
        assert got["_written_at_np"][slot] == payload["enq_t"]


def test_promotion_programs_compile_once():
    """Varied slots, enqueue and live clocks, expiries and outcomes,
    and a scalar serve's insert, all reuse one compiled lookup and one
    compiled write: none of these values keys a new compile."""
    jax.clear_caches()
    pol = _seeded()
    payloads = [{"v": V_A, "h_idx": 1, "enq_t": 13},
                {"v": _para(4, 5, 0.2), "h_idx": 2, "enq_t": 19, "ttl": 4},
                {"v": V_C, "h_idx": 3, "enq_t": 2},
                {"v": _para(5, 6, 0.7), "h_idx": 0, "enq_t": 30,
                 "outcome": REWRITE, "rewritten": "t", "judge_args":
                 {"q_cls": 2}},
                {"v": _para(6, 7, 0.1), "h_idx": 3, "enq_t": np.int64(31),
                 "ttl": 100}]
    for k, payload in enumerate(payloads):
        pol.t = 25 + 7 * k
        pol._promote(payload)
    res = pol.serve("a miss", {"cls": 1})
    pol.pool.drain()
    pol.pool.stop()
    assert res.served_by == "backend"
    assert "gen(a miss)" in pol.dyn_answers
    assert _promote_lookup._cache_size() == 1
    assert pol._write_fn._cache_size() == 1


class _FailingWAL:
    def __init__(self):
        self.calls = 0

    def append(self, record):
        self.calls += 1
        raise OSError("journal device full")


def test_failed_wal_append_leaves_tier_untouched():
    """The WAL append comes before the compiled write: when it raises,
    the device tier, its host mirrors and the answers stay as they
    were, and ``dyn_lock`` is released."""
    wal = _FailingWAL()
    pol = _seeded()
    pol.wal = wal
    before = _state(pol)
    with pytest.raises(OSError):
        pol._promote({"v": _para(3, 5, 0.4), "h_idx": 3, "enq_t": 15})
    pol.pool.stop()
    assert wal.calls == 1
    assert pol.dyn is before["tier"]
    _assert_same(_state(pol), before)
    assert pol.dyn_lock.acquire(blocking=False)
    pol.dyn_lock.release()
