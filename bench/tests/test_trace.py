"""The trace reduction, on a hand-made trace and on 40 ms recorded from
a run of the conv mix on a 2^20-row flat tier on a TPU v5e
(bench/testdata)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"


def _meta(pid, name, tid=None, thread=None):
    if thread is None:
        return {"ph": "M", "name": "process_name", "pid": pid,
                "args": {"name": name}}
    return {"ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
            "args": {"name": thread}}


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur}


def test_hand_made_trace():
    ev = [_meta(1, "/device:TPU:0"), _meta(1, "", 1, "XLA Ops"),
          _meta(1, "", 2, "XLA Modules"), _meta(9, "/host:CPU"),
          # device ops: [0,10) and [5,20) overlap, then [40,50)
          _x(1, 1, "fusion.1", 0, 10), _x(1, 1, "fusion.2", 5, 15),
          _x(1, 1, "fusion.1", 40, 10),
          _x(1, 2, "jit_cosine_topk(1)", 0, 20),
          _x(1, 2, "jit__lambda(2)", 40, 10),
          # host spans: backend over [20, 35), nothing over [35, 40)
          _x(9, 5, "bench.serve_batch", 15, 20),
          _x(9, 5, "bench.backend", 20, 15),
          _x(9, 5, "python work", 0, 100)]
    r = trace.reduce_events(ev)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(30e-6)       # [0,20) + [40,50)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(20e-6)]
    gaps = dict(r["idle_gaps"])
    # [20,40): middle 30 is inside backend; [50,100): no span at 75
    assert gaps["backend"] == pytest.approx(20e-6)
    assert gaps[trace.NO_SPAN] == pytest.approx(50e-6)
    secs, calls = trace.program_seconds(r, ["cosine_topk"])
    assert secs == pytest.approx(20e-6) and calls == 1


def _busy_by_bitmap(events):
    """Busy microseconds of the device ops, counted one microsecond at a
    time (a second way to take the union)."""
    pn = {e["pid"]: e["args"]["name"] for e in events
          if e.get("ph") == "M" and e["name"] == "process_name"}
    tn = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
          if e.get("ph") == "M" and e["name"] == "thread_name"}
    busy = set()
    for e in events:
        if e.get("ph") == "X" and pn.get(e["pid"], "").startswith(
                "/device:") and tn.get((e["pid"], e["tid"])) == "XLA Ops":
            a = float(e["ts"])
            busy.update(range(int(a), int(a + float(e["dur"]))))
    return len(busy)


def test_recorded_trace():
    path = DATA / "conv_40ms.perfetto_trace.json.gz"
    events = json.load(gzip.open(path, "rt"))["traceEvents"]
    r = trace.reduce_events(events)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 0.06   # events start in 40 ms
    assert r["busy_s"] * 1e6 == pytest.approx(_busy_by_bitmap(events),
                                              rel=0.02, abs=5)
    names = [n for n, _ in r["idle_gaps"]]
    assert set(names) <= {"serve_batch", "embed", "backend", "judge",
                          trace.NO_SPAN}
    total_idle = sum(s for _, s in r["idle_gaps"])
    assert total_idle == pytest.approx(r["window_s"] - r["busy_s"],
                                       rel=1e-6)
