"""The serving decision procedure, ``serve_batch``: a batch of N must
equal N batches of one (``serve`` is a batch of one) request for request
(same answers, served_by, static_origin, same promotions), the baseline
must equal the numpy oracle ``ref_policy.ref_simulate`` at any batch
split, and the router must preserve it under concurrency."""
import dataclasses
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from ref_policy import (DYN_HIT_DYNAMIC, DYN_HIT_PROMOTED, MISS,
                        STATIC_HIT, ref_simulate)

from repro.core.adaptive import AdaptiveController, AdaptiveParams
from repro.core.judge import OracleJudge
from repro.core.policy import BaselinePolicy, KritesPolicy
from repro.core.tiers import CacheConfig, make_static_tier
from repro.data.synth_traces import LMARENA_LIKE, build_benchmark
from repro.index.flat import l2_normalize
from repro.serving.router import CacheRouter

N = 500
BATCH = 8


def _trace_setup(n=N, capacity=128):
    """Synthetic trace as a live-policy workload: prompt 'q<i>' embeds to
    eval row i, so embeddings are identical at every batch split."""
    spec = dataclasses.replace(LMARENA_LIKE, n_requests=4000,
                               n_classes=120)
    bench = build_benchmark(spec)
    emb = {f"q{i}": bench.eval_emb[i] for i in range(n)}
    prompts = [f"q{i}" for i in range(n)]
    metas = [{"cls": int(bench.eval_cls[i])} for i in range(n)]
    tier = make_static_tier(jnp.asarray(bench.static_emb),
                            jnp.asarray(bench.static_cls))
    answers = [f"curated-{int(c)}" for c in bench.static_cls]
    cfg = CacheConfig(tau_static=0.88, tau_dynamic=0.88, sigma_min=0.0,
                      capacity=capacity)
    d = bench.static_emb.shape[1]

    def embed_fn(p):
        return emb[p]

    def embed_batch_fn(ps):
        return np.stack([emb[p] for p in ps])

    def backend_fn(p):
        return f"gen({p})"

    def backend_batch_fn(ps):
        return [f"gen({p})" for p in ps]

    return dict(cfg=cfg, tier=tier, answers=answers, d=d,
                prompts=prompts, metas=metas, embed_fn=embed_fn,
                embed_batch_fn=embed_batch_fn, backend_fn=backend_fn,
                backend_batch_fn=backend_batch_fn,
                q_emb=bench.eval_emb[:n], q_cls=bench.eval_cls[:n],
                static_cls=bench.static_cls)


def _assert_rows_equal(scalar, batched):
    assert len(scalar) == len(batched)
    for i, (a, b) in enumerate(zip(scalar, batched)):
        assert a.served_by == b.served_by, i
        assert a.answer == b.answer, i
        assert a.static_origin == b.static_origin, i
        assert a.similarity == b.similarity \
            or abs(a.similarity - b.similarity) < 1e-5, i


def _split(n, sizes):
    """Consecutive [lo, hi) slices of range(n), cycling through
    ``sizes``."""
    out, lo, k = [], 0, 0
    while lo < n:
        hi = min(n, lo + sizes[k % len(sizes)])
        out.append((lo, hi))
        lo, k = hi, k + 1
    return out


_REF_CODE = {"static": STATIC_HIT, "backend": MISS}


@pytest.mark.parametrize("sizes", [(1,), (8,), (3, 5, 7, 1)],
                         ids=["batch1", "batch8", "ragged"])
def test_serve_batch_matches_reference_baseline(sizes):
    """Algorithm 1 through the production path equals the numpy oracle
    row for row, whatever the batch split. The trace keeps every score
    more than 1e-5 from its threshold, so device and numpy matmuls
    cannot disagree on a decision."""
    s = _trace_setup()
    cfg = s["cfg"]
    pol = BaselinePolicy(cfg, s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         embed_batch_fn=s["embed_batch_fn"],
                         backend_batch_fn=s["backend_batch_fn"])
    out = []
    for lo, hi in _split(N, sizes):
        out += pol.serve_batch(s["prompts"][lo:hi], s["metas"][lo:hi])
    q = np.asarray(l2_normalize(jnp.asarray(s["q_emb"], jnp.float32)))
    s_emb = np.asarray(s["tier"].emb)
    s_static = (q @ s_emb.T).max(axis=1)
    assert np.all(np.abs(s_static - cfg.tau_static) > 1e-5)
    s_dyn = np.asarray([r.similarity for r in out
                        if r.served_by != "static"])
    assert np.all(np.abs(s_dyn - cfg.tau_dynamic) > 1e-5)
    ref = ref_simulate(s_emb, s["static_cls"], q, s["q_cls"], cfg,
                       krites=False, capacity=128)
    for i, r in enumerate(out):
        want = _REF_CODE.get(r.served_by, DYN_HIT_PROMOTED
                             if r.static_origin else DYN_HIT_DYNAMIC)
        assert want == int(ref["served_by"][i]), i
        assert r.static_origin == bool(ref["static_origin"][i]), i
    assert {r.served_by for r in out} == {"static", "dynamic", "backend"}


def test_serve_batch_matches_scalar_baseline():
    """Batches of 8 equal batches of one (``serve``)."""
    s = _trace_setup()
    mk = lambda: BaselinePolicy(  # noqa: E731
        s["cfg"], s["tier"], s["answers"], s["embed_fn"], s["backend_fn"],
        d=s["d"], embed_batch_fn=s["embed_batch_fn"],
        backend_batch_fn=s["backend_batch_fn"])
    p_scalar, p_batch = mk(), mk()
    scalar = [p_scalar.serve(p, m)
              for p, m in zip(s["prompts"], s["metas"])]
    batched = []
    for i in range(0, N, BATCH):
        batched += p_batch.serve_batch(s["prompts"][i:i + BATCH],
                                       s["metas"][i:i + BATCH])
    _assert_rows_equal(scalar, batched)
    assert p_scalar.events == p_batch.events
    assert p_scalar.stats() == p_batch.stats()
    # the trace must actually exercise all three tiers
    by = {r.served_by for r in scalar}
    assert by == {"static", "dynamic", "backend"}


class _GatedOracle:
    """Oracle judge that blocks until the test opens the gate, so
    promotions land only at controlled (batch) boundaries."""

    def __init__(self):
        self.gate = threading.Event()

    def __call__(self, q_cls, h_cls, **kw):
        self.gate.wait()
        return int(q_cls) == int(h_cls)


def _run_krites_scalar(s, judge):
    pol = KritesPolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                       s["backend_fn"], judge, d=s["d"], n_workers=1)
    out = []
    for i in range(0, N, BATCH):
        for p, m in zip(s["prompts"][i:i + BATCH],
                        s["metas"][i:i + BATCH]):
            out.append(pol.serve(p, m))
        judge.gate.set()
        pol.pool.drain()
        judge.gate.clear()
    judge.gate.set()
    pol.pool.drain()
    pol.pool.stop()
    return pol, out


def _run_krites_batched(s, judge):
    pol = KritesPolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                       s["backend_fn"], judge, d=s["d"], n_workers=1,
                       embed_batch_fn=s["embed_batch_fn"],
                       backend_batch_fn=s["backend_batch_fn"])
    out = []
    for i in range(0, N, BATCH):
        out += pol.serve_batch(s["prompts"][i:i + BATCH],
                               s["metas"][i:i + BATCH])
        judge.gate.set()
        pol.pool.drain()
        judge.gate.clear()
    judge.gate.set()
    pol.pool.drain()
    pol.pool.stop()
    return pol, out


def test_serve_batch_matches_scalar_krites_with_promotions():
    """Full Alg. 2 equivalence of batches of 8 and batches of one
    (``serve``): promotions land at the same batch boundaries in both
    runs, so every decision — including dynamic hits on promoted
    entries — must match request for request."""
    s = _trace_setup()
    pol_s, scalar = _run_krites_scalar(s, _GatedOracle())
    pol_b, batched = _run_krites_batched(s, _GatedOracle())
    _assert_rows_equal(scalar, batched)
    assert pol_s.events == pol_b.events
    ss, sb = pol_s.stats(), pol_b.stats()
    for k in ("judge_submitted", "judged", "approved", "static_hit_rate",
              "dynamic_hit_rate", "backend_rate", "static_origin_rate"):
        assert ss[k] == sb[k], k
    # promotions must actually have happened and been served from
    assert sb["approved"] > 0
    assert any(r.served_by == "dynamic" and r.static_origin
               for r in batched)


def test_intra_batch_duplicate_hits_fresh_insert():
    """A duplicate within one batch must see the earlier row's backend
    insert, exactly as the sequential path would."""
    s = _trace_setup()
    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         backend_batch_fn=s["backend_batch_fn"])
    # find a prompt that misses both tiers when served cold
    probe = BaselinePolicy(s["cfg"], s["tier"], s["answers"],
                           s["embed_fn"], s["backend_fn"], d=s["d"])
    novel = next(p for p, m in zip(s["prompts"], s["metas"])
                 if probe.serve(p, m).served_by == "backend")
    r1, r2 = pol.serve_batch([novel, novel])
    assert r1.served_by == "backend"
    assert r2.served_by == "dynamic" and not r2.static_origin
    assert r2.answer == r1.answer == f"gen({novel})"


def test_grey_zone_promotion_visible_to_later_batch():
    d = 8
    s_emb = np.eye(d, dtype=np.float32)[:4]
    tier = make_static_tier(jnp.asarray(s_emb),
                            jnp.arange(4, dtype=jnp.int32))
    para = s_emb[0] + 0.3 * s_emb[1]
    para /= np.linalg.norm(para)
    assert 0.5 < float(para @ s_emb[0]) < 0.98
    emb = {"para": para.astype(np.float32)}
    cfg = CacheConfig(tau_static=0.98, tau_dynamic=0.98, sigma_min=0.5,
                      capacity=16)
    kr = KritesPolicy(cfg, tier, [f"curated-{i}" for i in range(4)],
                      lambda p: emb[p], lambda p: f"gen({p})",
                      OracleJudge(), d=d)
    r1 = kr.serve_batch(["para"], [{"cls": 0}])[0]
    assert r1.served_by == "backend"
    kr.pool.drain()
    r2 = kr.serve_batch(["para"], [{"cls": 0}])[0]
    assert r2.served_by == "dynamic" and r2.static_origin
    assert r2.answer == "curated-0"
    kr.pool.stop()


def _find_novel(s):
    """A prompt that misses both tiers when served cold."""
    probe = BaselinePolicy(s["cfg"], s["tier"], s["answers"],
                           s["embed_fn"], s["backend_fn"], d=s["d"])
    return next(p for p, m in zip(s["prompts"], s["metas"])
                if probe.serve(p, m).served_by == "backend")


def test_backend_failure_rolls_back_inserts():
    """A failed batched backend call must not leave answerless entries
    in the dynamic tier."""
    s = _trace_setup()
    state = {"fail": True}

    def flaky_batch(ps):
        if state["fail"]:
            raise RuntimeError("backend down")
        return [f"gen({p})" for p in ps]

    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         backend_batch_fn=flaky_batch)
    novel = _find_novel(s)
    with pytest.raises(RuntimeError):
        pol.serve_batch([novel])
    # a failed batch served nobody, so it must record no events
    assert pol.stats()["requests"] == 0
    # retry after recovery: must go to the backend again (no poisoned
    # dynamic hit serving None)
    state["fail"] = False
    r = pol.serve_batch([novel])[0]
    assert r.served_by == "backend"
    assert r.answer == f"gen({novel})"


def test_backend_failure_records_no_adaptive_evidence():
    """A failed batch served nobody, so the threshold controller's
    window gains no row from it — not even from the batch's static
    hits; the retry records every row."""
    s = _trace_setup()
    state = {"fail": True}

    def flaky_batch(ps):
        if state["fail"]:
            raise RuntimeError("backend down")
        return [f"gen({p})" for p in ps]

    ctl = AdaptiveController(s["cfg"], d=s["d"],
                             params=AdaptiveParams(window=64))
    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         backend_batch_fn=flaky_batch, adaptive=ctl)
    probe = BaselinePolicy(s["cfg"], s["tier"], s["answers"],
                           s["embed_fn"], s["backend_fn"], d=s["d"])
    hit = next(p for p, m in zip(s["prompts"], s["metas"])
               if probe.serve(p, m).served_by == "static")
    batch = [hit, _find_novel(s)]
    with pytest.raises(RuntimeError):
        pol.serve_batch(batch)
    assert pol.stats()["adaptive_window_fill"] == 0
    state["fail"] = False
    out = pol.serve_batch(batch)
    assert [r.served_by for r in out] == ["static", "backend"]
    assert [r.meta["adapt_seq"] for r in out] == [1, 2]
    assert pol.stats()["adaptive_window_fill"] == 2


def test_router_surfaces_backend_errors():
    s = _trace_setup()

    def broken_batch(ps):
        raise RuntimeError("backend down")

    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         backend_batch_fn=broken_batch)
    router = CacheRouter(pol, max_batch=4, max_wait_ms=1.0)
    novel = _find_novel(s)
    res = router.submit(novel, timeout_s=10.0)
    assert res is None
    st = router.stats()
    assert st["errors"] >= 1
    assert "backend down" in st["last_error"]
    router.stop()


def test_router_concurrent_matches_policy_decisions():
    s = _trace_setup(n=200)
    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         embed_batch_fn=s["embed_batch_fn"],
                         backend_batch_fn=s["backend_batch_fn"])
    router = CacheRouter(pol, max_batch=16, max_wait_ms=5.0)
    results = router.submit_many(s["prompts"][:200], s["metas"][:200])
    assert all(r is not None for r in results)
    st = router.stats()
    assert st["requests"] == 200
    assert st["batches"] < 200          # batching actually happened
    assert st["mean_batch_size"] > 1.0
    counts = (st["static_hit_rate"] + st["dynamic_hit_rate"]
              + st["backend_rate"])
    assert abs(counts - 1.0) < 1e-9
    assert "p99_latency_ms" in st
    router.stop()


def test_router_latency_percentiles_under_concurrent_submit():
    """Latency telemetry under concurrent clients: every request lands
    in the (bounded) percentile window, percentiles are ordered, and
    the window cap keeps a long-lived router from sorting its whole
    history."""
    s = _trace_setup(n=160)
    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"],
                         embed_batch_fn=s["embed_batch_fn"],
                         backend_batch_fn=s["backend_batch_fn"])
    router = CacheRouter(pol, max_batch=16, max_wait_ms=2.0,
                         latency_window=100)
    out = {}

    def client(lo, hi):
        for i in range(lo, hi):
            out[i] = router.submit(s["prompts"][i], s["metas"][i])

    threads = [threading.Thread(target=client, args=(k * 40, k * 40 + 40))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == 160 and all(v is not None for v in out.values())
    st = router.stats()
    assert st["requests"] == 160
    assert 0 < st["p50_latency_ms"] <= st["p99_latency_ms"]
    # bounded window: only the last `latency_window` samples retained
    assert len(router._latencies) == 100
    router.stop()


def test_router_threaded_submit():
    s = _trace_setup(n=120)
    pol = BaselinePolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                         s["backend_fn"], d=s["d"])
    router = CacheRouter(pol, max_batch=8, max_wait_ms=20.0)
    out = {}

    def client(lo, hi):
        for i in range(lo, hi):
            out[i] = router.submit(s["prompts"][i], s["metas"][i])

    threads = [threading.Thread(target=client, args=(k * 30, k * 30 + 30))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == 120 and all(v is not None for v in out.values())
    assert router.stats()["requests"] == 120
    router.stop()
