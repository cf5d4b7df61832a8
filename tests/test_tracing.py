"""``repro.tracing``: span nesting and self time, thread safety, the
compile and GC hooks, and the spans and counters the serve path records
(policy step, engine, judge pool and promote, the stdio service loop)."""
import gc
import io
import json
import random
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.configs import smoke_config
from repro.core.judge import OracleJudge
from repro.core.policy import KritesPolicy
from repro.serving.engine import BatchingFrontend, LLMEngine
from repro.serving.router import _MicroBatcher
from test_serve_batch import _trace_setup


@pytest.fixture(autouse=True)
def fresh():
    tracing.snapshot(reset=True)
    yield


def _spans():
    return tracing.snapshot()["spans"]


def _counters():
    return tracing.snapshot()["counters"]


def test_nesting_self_time_and_max():
    gc.disable()           # no collection may land inside as a child
    try:
        with tracing.span("t.outer", rows=3):
            time.sleep(0.02)
            with tracing.span("t.inner", rows=1):
                time.sleep(0.03)
            with tracing.span("t.inner", rows=5):
                time.sleep(0.005)
    finally:
        gc.enable()
    sp = _spans()
    o, i = sp["t.outer"], sp["t.inner"]
    assert o["calls"] == 1 and o["rows"] == 3
    assert i["calls"] == 2 and i["rows"] == 6
    assert i["self_seconds"] == i["seconds"]
    assert o["self_seconds"] == pytest.approx(o["seconds"] - i["seconds"],
                                              abs=1e-12)
    assert o["self_seconds"] >= 0.02
    assert 0.03 <= i["max_seconds"] < i["seconds"]
    assert o["max_seconds"] == o["seconds"]


def test_gc_is_a_child_span():
    with tracing.span("t.gc_parent"):
        gc.collect()
    sp = _spans()
    assert sp["gc"]["calls"] >= 1
    p = sp["t.gc_parent"]
    assert p["self_seconds"] <= p["seconds"] - sp["gc"]["seconds"] + 1e-9


def test_aggregates_from_eight_threads_sum_exactly():
    n = 1000

    def work():
        for _ in range(n):
            with tracing.span("t.mt", rows=1):
                tracing.add("t.mt_count", 1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)          # switch threads as often as can be
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    s, c = _spans()["t.mt"], _counters()["t.mt_count"]
    assert s["calls"] == 8 * n and s["rows"] == 8 * n
    assert c == {"n": 8 * n, "sum": 8.0 * n, "max": 1.0}


def test_compile_counted_under_its_span_once():
    k = random.random()          # a program no cache has seen
    f = jax.jit(lambda x: x * k + 1.0)
    x = jnp.ones(3).block_until_ready()
    with tracing.span("t.compiling"):
        f(x).block_until_ready()
    with tracing.span("t.compiling"):
        f(x).block_until_ready()
    c = _counters()["compile.t.compiling"]
    assert c["n"] == 1 and c["sum"] > 0
    assert tracing.compiles()[0] >= 1


def test_cache_load_is_not_a_compile():
    with tracing.span("t.loading"):
        jax.monitoring.record_event(tracing.CACHE_HIT_EVENT)
        jax.monitoring.record_event_duration_secs(tracing.COMPILE_EVENT,
                                                  0.5)
    assert "compile.t.loading" not in _counters()
    with tracing.span("t.loading"):
        jax.monitoring.record_event_duration_secs(tracing.COMPILE_EVENT,
                                                  0.25)
    assert _counters()["compile.t.loading"] == {"n": 1, "sum": 0.25,
                                                "max": 0.25}


def test_snapshot_reset_clears():
    with tracing.span("t.x"):
        tracing.add("t.y", 2.0)
    snap = tracing.snapshot(reset=True)
    assert snap["spans"]["t.x"]["calls"] == 1
    assert snap["counters"]["t.y"]["sum"] == 2.0
    json.dumps(snap)                      # plain JSON
    after = tracing.snapshot()
    assert "t.x" not in after["spans"] and "t.y" not in after["counters"]


def test_batcher_counts_each_requests_wait():
    started = []

    def serve(batch):
        started.append(time.monotonic())
        for p in batch:
            p.result = p.prompt

    mb = _MicroBatcher(serve, max_batch=4, max_wait_s=0.05, name="t-mb")
    try:
        t0 = time.monotonic()
        ps = [mb.submit(x) for x in "abc"]
        for p in ps:
            assert p.done.wait(5)
    finally:
        mb.stop()
    c = _counters()["t-mb.wait_s"]
    assert c["n"] == 3 and len(started) == 1
    # each waits from its submit to the batch's start, no longer
    assert 0 < c["max"] <= started[0] - t0
    assert c["sum"] <= 3 * (started[0] - t0)


def _policy(**kw):
    s = _trace_setup(n=64, capacity=256)
    pol = KritesPolicy(s["cfg"], s["tier"], s["answers"], s["embed_fn"],
                       s["backend_fn"], OracleJudge(), d=s["d"],
                       embed_batch_fn=s["embed_batch_fn"],
                       backend_batch_fn=s["backend_batch_fn"], **kw)
    return pol, s


def test_serve_batch_emits_policy_spans_with_rows():
    pol, s = _policy()
    try:
        out = pol.serve_batch(s["prompts"][:16], s["metas"][:16])
        pol.pool.drain()
    finally:
        pol.pool.stop()
    sp = _spans()
    assert sp["policy.serve_batch"]["calls"] == 1
    assert sp["policy.serve_batch"]["rows"] == 16
    for name in ("policy.front", "policy.embed", "policy.static_lookup",
                 "policy.dyn_lookup"):
        assert sp[name]["calls"] == 1
        assert sp[name]["rows"] == 16
    assert sp["policy.lock_wait"]["calls"] == 1
    misses = sum(r.served_by == "backend" for r in out)
    assert misses > 0 and sp["policy.backend"]["rows"] == misses
    assert sp["policy.writes"]["rows"] == misses
    assert sp["policy.grey_submit"]["rows"] == \
        sum(r.served_by != "static" for r in out)
    assert sp["policy.adapt"]["calls"] == 1
    children = sum(v["seconds"] for k, v in sp.items()
                   if k.startswith("policy.") and k != "policy.serve_batch")
    assert children <= sp["policy.serve_batch"]["seconds"]


def test_promote_lag_counts_landed_promotions():
    pol, s = _policy()
    try:
        for i in range(0, 64, 16):
            pol.serve_batch(s["prompts"][i:i + 16], s["metas"][i:i + 16])
        pol.pool.drain()
    finally:
        pol.pool.stop()
    sp, c = _spans(), _counters()
    landed = sp["promote.write"]["calls"]
    assert landed > 0
    assert c["promote.lag_s"]["n"] == landed <= pol.pool.stats.approved
    assert sp["promote.lock_wait"]["calls"] == sp["promote"]["calls"]
    assert c["judge.queue_wait_s"]["n"] == sp["judge.call"]["calls"] \
        == pol.pool.stats.judged


def test_engine_decode_spans_equal_decode_steps():
    eng = LLMEngine(smoke_config("qwen3-1.7b"), max_len=48)
    front = BatchingFrontend(eng, max_batch=4, max_new_tokens=4)
    try:
        front.submit_many(["hello", "world!", "a longer prompt"])
        eng.generate_batch(["x"], max_new_tokens=3)
    finally:
        front.stop()
    sp, c = _spans(), _counters()
    assert sp["engine.decode"]["calls"] == eng.stats.decode_steps > 0
    assert sp["engine.batch"]["calls"] == eng.stats.batches == 2
    assert sp["engine.prefill"]["rows"] == eng.stats.prefills == 4
    assert sp["engine.sample"]["calls"] == \
        eng.stats.decode_steps + eng.stats.batches
    assert c["batching-frontend.wait_s"]["n"] == 3
    # a batch of max_new tokens decodes max_new - 1 steps, each served
    # and each dispatched before the tokens it consumes were read
    unserved = c.get("engine.decode_steps_unserved", {"n": 0})["n"]
    assert eng.stats.decode_steps == 3 + 2 and unserved == 0
    assert c["engine.decode_steps_ahead"]["n"] == eng.stats.decode_steps
    # named programs: the profiler reads jit_prefill / jit_decode
    assert (eng._prefill.__name__, eng._decode.__name__) == \
        ("prefill", "decode")


def test_stdio_stats_carry_the_queue_wait_of_every_request(monkeypatch):
    from repro.launch.serve import _serve_stdio
    pol, s = _policy()
    n = 10
    lines = [json.dumps({"op": "serve", "id": k, "prompt": s["prompts"][k],
                         "cls": s["metas"][k]["cls"]}) for k in range(n)]
    lines += [json.dumps({"op": "stats", "id": "st"}),
              json.dumps({"op": "shutdown"})]
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdin", io.StringIO("\n".join(lines) + "\n"))
    monkeypatch.setattr(sys, "stdout", out)
    try:
        _serve_stdio(pol, None, None)
    finally:
        pol.pool.stop()
    replies = [json.loads(ln) for ln in out.getvalue().splitlines()]
    served = [r for r in replies if r.get("served_by")]
    stats = next(r for r in replies if r.get("id") == "st")
    tc = stats["trace"]["counters"]
    assert len(served) == n
    assert tc["loop.queue_wait_s"]["n"] == n
    assert tc["loop.batch_rows"]["sum"] == n
    assert stats["trace"]["spans"]["loop.reply"]["rows"] == n
