"""The program's spans and counters as the per-layer readers see them
(``bench/program.py``): rebuilt from a trace's ``krites.*`` events, and
absent on a program without them."""
import gzip
import json
from pathlib import Path

import pytest

from bench import program, run, trace

DATA = Path(__file__).resolve().parents[1] / "testdata"

READERS = ["queue_wait_ms", "lock_wait_ms_per_batch",
           "policy_self_ms_per_batch", "featurize_ms_per_req",
           "engine_wait_ms", "decode_ms_per_step"]

EXPECTED = {"queue_wait_ms": 150.0, "lock_wait_ms_per_batch": 1.0,
            "policy_self_ms_per_batch": 25.0, "featurize_ms_per_req": 1.1,
            "engine_wait_ms": 50.0, "decode_ms_per_step": 10.0}

# what each reader needs: (spans, counters)
NEEDS = {"queue_wait_ms": ((), ("loop.queue_wait_s",)),
         "lock_wait_ms_per_batch": (("policy.lock_wait",
                                     "policy.serve_batch"), ()),
         "policy_self_ms_per_batch": (("policy.serve_batch",), ()),
         "featurize_ms_per_req": (("embed.featurize",), ()),
         "engine_wait_ms": ((), ("batching-frontend.wait_s",)),
         "decode_ms_per_step": (("engine.decode",), ())}


def _child_events():
    """A chip process's program events: 4 serve batches of 25 rows, 300
    ms each, of which 25 ms outside the child spans; 90 decode steps of
    10 ms on the engine's thread; 100 loop waits of 150 ms and 60
    batcher waits of 50 ms."""
    ev = [_meta(9, "/host:CPU")]
    for b in range(4):
        t = b * 400_000
        ev += [_x(9, 5, "krites.policy.serve_batch", t, 300_000, rows=25),
               _x(9, 5, "krites.embed.featurize", t + 1, 27_500, rows=25),
               _x(9, 5, "krites.policy.lock_wait", t + 30_000, 1_000),
               _x(9, 5, "krites.policy.backend", t + 40_000, 246_500)]
    ev += [_x(9, 6, "krites.engine.decode", 50_000 + 12_000 * k, 10_000,
              rows=8) for k in range(90)]
    ev += [_x(9, 5, "krites.loop.queue_wait_s", 7, 0, value=0.15)
           for _ in range(100)]
    ev += [_x(9, 6, "krites.batching-frontend.wait_s", 9, 0, value=0.05)
           for _ in range(60)]
    return ev


def _trace_dir(path, events):
    path.mkdir(parents=True, exist_ok=True)
    with gzip.open(path / "x.perfetto_trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(path)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_child_snapshot(name, tmp_path):
    ctx = {"child": {"trace_dir": _trace_dir(tmp_path, _child_events())}}
    assert run._reader(name)(ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_its_span_or_counter(name, tmp_path):
    read = run._reader(name)
    # the parent's program: no program events, or no trace at all
    assert read({"child": {"trace_dir": None}}) is None
    assert read({"child": {"trace_dir": str(DATA)}}) is None
    spans, counters = NEEDS[name]
    for k, missing in enumerate(spans + counters):
        events = [e for e in _child_events()
                  if e.get("name") != program.PREFIX + missing]
        ctx = {"child": {"trace_dir": _trace_dir(tmp_path / str(k),
                                                 events)}}
        assert read(ctx) is None, missing


def _meta(pid, name, tid=None, thread=None):
    if thread is None:
        return {"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": name}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": thread}}


def _x(pid, tid, name, ts, dur, **args):
    e = {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
         "dur": dur}
    if args:
        e["args"] = {k: str(v) for k, v in args.items()}
    return e


# device busy over [0, 20) and [40, 50) of a 100 us window; on the host
# the benchmark's serve_batch span holds the program's step, whose
# backend call (with two decode steps inside it, on the engine's thread)
# is open over the gap [20, 40)
HAND_MADE = [
    _meta(1, "/device:TPU:0"), _meta(1, "", 1, "XLA Ops"),
    _meta(9, "/host:CPU"),
    _x(1, 1, "fusion.1", 0, 20), _x(1, 1, "fusion.2", 40, 10),
    _x(9, 5, "bench.serve_batch", 10, 50),
    _x(9, 5, "krites.policy.serve_batch", 12, 46, rows=3),
    _x(9, 5, "krites.policy.embed", 13, 5, rows=3),
    _x(9, 5, "bench.backend", 18, 30),
    _x(9, 5, "krites.policy.backend", 19, 28, rows=2),
    _x(9, 6, "krites.engine.decode", 21, 6, rows=8),
    _x(9, 6, "krites.engine.decode", 28, 8, rows=8),
    _x(9, 5, "krites.loop.queue_wait_s", 11, 0, value=0.25),
    _x(9, 5, "krites.loop.queue_wait_s", 11, 0, value=0.75),
    _x(9, 5, "python work", 0, 100)]


def test_aggregates_from_trace_events():
    agg = program.from_events(HAND_MADE)
    sp, ct = agg["spans"], agg["counters"]
    step = sp["policy.serve_batch"]
    assert step["calls"] == 1 and step["rows"] == 3
    assert step["seconds"] == pytest.approx(46e-6)
    assert step["self_seconds"] == pytest.approx((46 - 5 - 28) * 1e-6)
    # the engine's spans are on their own thread: no parent there
    assert sp["policy.backend"]["self_seconds"] == pytest.approx(28e-6)
    assert sp["engine.decode"]["calls"] == 2
    assert sp["engine.decode"]["max_seconds"] == pytest.approx(8e-6)
    assert ct["loop.queue_wait_s"] == {"n": 2, "sum": 1.0, "max": 0.75}
    assert program.from_events(HAND_MADE[:6]) is None


def test_readers_rebuild_from_a_trace_dir(tmp_path):
    ctx = {"child": {"trace_dir": _trace_dir(tmp_path, HAND_MADE)}}
    assert run._reader("queue_wait_ms")(ctx) == pytest.approx(500.0)
    assert run._reader("decode_ms_per_step")(ctx) == pytest.approx(7e-3)
    assert run._reader("policy_self_ms_per_batch")(ctx) == \
        pytest.approx(13e-3)


def test_an_unexpected_event_layout_is_an_error(tmp_path):
    # the exporter writes a name before its arguments; the program's
    # events are decoded alone on that layout, and on no other
    args_first = [dict(sorted(e.items())) for e in HAND_MADE]
    with pytest.raises(ValueError, match="laid out"):
        program.from_trace(_trace_dir(tmp_path, args_first))


def test_an_exported_trace_reads_as_the_snapshot(tmp_path):
    jax = pytest.importorskip("jax")
    from repro import tracing
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    tracing.snapshot(reset=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts,
                             create_perfetto_trace=True)
    try:
        for rows in (3, 5):
            with tracing.span("t.outer", rows=rows):
                with tracing.span("t.inner"):
                    tracing.add("t.value", rows / 8)
    finally:
        jax.profiler.stop_trace()
    snap = tracing.snapshot(reset=True)
    agg = program.from_trace(str(tmp_path))
    for name in ("t.outer", "t.inner"):
        got, want = agg["spans"][name], snap["spans"][name]
        assert (got["calls"], got["rows"]) == (want["calls"], want["rows"])
        # the annotation opens before the span's clock starts and closes
        # after it stops
        assert got["seconds"] >= want["seconds"]
    assert agg["counters"]["t.value"] == snap["counters"]["t.value"]


def test_idle_gap_goes_to_the_innermost_program_span():
    gaps = dict(program.idle_gaps(HAND_MADE))
    # [20, 40): at 30 the engine's decode [28, 36) started last
    assert gaps["krites.engine.decode"] == pytest.approx(20e-6)
    # [50, 100): at 75 no span is open
    assert gaps[trace.NO_SPAN] == pytest.approx(50e-6)
    # the benchmark's own reduction still sees its spans alone
    assert dict(trace.reduce_events(HAND_MADE)["idle_gaps"])["backend"] \
        == pytest.approx(20e-6)


def test_recorded_trace_has_no_program_spans():
    path = DATA / "conv_40ms.perfetto_trace.json.gz"
    events = json.load(gzip.open(path, "rt"))["traceEvents"]
    assert program.from_events(events) is None
    assert program.from_trace(str(DATA)) is None
    assert program.idle_gaps(events) == \
        trace.reduce_events(events)["idle_gaps"]
