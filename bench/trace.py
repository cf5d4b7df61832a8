"""Reduce a profiler trace to the numbers the per-layer metrics read.

Reads the ``perfetto_trace.json.gz`` that ``jax.profiler`` writes with
``create_perfetto_trace=True``, with nothing but ``gzip`` and
``json``. Device planes are the processes whose name starts with
``/device:``; their operations are the complete events on the thread
named ``XLA Ops`` and their programs those on ``XLA Modules`` (every
complete event of a device process where it has no such threads). The
benchmark's own host spans are the events named ``bench.*``.

- ``busy_s``: the union of the device's operation intervals, averaged
  over the devices that ran any; ``window_s``: first to last event.
- ``device_ops``: the ten operation names with the most device time.
- ``idle_gaps``: device idle time summed by the innermost benchmark
  span open at the middle of each gap (``host: no span`` between
  them), the ten largest.
- ``modules``: device seconds per program name.
"""
from __future__ import annotations

import bisect
import gzip
import json
from pathlib import Path

OPS, MODULES = "XLA Ops", "XLA Modules"
NO_SPAN = "host: no span"


def find(trace_dir) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*perfetto_trace.json.gz"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def _union(iv):
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(trace_dir) -> dict | None:
    path = find(trace_dir)
    if path is None:
        return None
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return reduce_events(data["traceEvents"] if isinstance(data, dict)
                         else data)


def reduce_events(events) -> dict:
    pname, tname = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname[e["pid"]] = e.get("args", {}).get("name", "")
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            tname[(e["pid"], e["tid"])] = e.get("args", {}).get("name", "")
    dev = {p for p, n in pname.items() if n.startswith("/device:")}
    threads_of = {}
    for (p, t), n in tname.items():
        threads_of.setdefault(p, set()).add(n)
    lo, hi = float("inf"), float("-inf")
    ops, mods, calls, spans = {}, {}, {}, []
    for e in events:
        if e.get("ph") != "X":
            continue
        ts, dur = float(e["ts"]), float(e.get("dur", 0.0))
        lo, hi = min(lo, ts), max(hi, ts + dur)
        pid = e["pid"]
        if pid in dev:
            th = tname.get((pid, e["tid"]), "")
            has = threads_of.get(pid, set())
            if th == OPS or OPS not in has and th != MODULES:
                ops.setdefault(pid, []).append((ts, ts + dur, e["name"]))
            elif th == MODULES:
                mods[e["name"]] = mods.get(e["name"], 0.0) + dur
                calls[e["name"]] = calls.get(e["name"], 0) + 1
        elif str(e.get("name", "")).startswith("bench."):
            spans.append((ts, ts + dur, e["name"][len("bench."):]))
    if lo > hi:
        lo = hi = 0.0
    busy_per_dev, op_time = [], {}
    gaps: dict = {}
    at = _SpanIndex(spans)
    for pid, iv in ops.items():
        for a, b, n in iv:
            op_time[n] = op_time.get(n, 0.0) + (b - a)
        merged = _union([(a, b) for a, b, _ in iv])
        busy_per_dev.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            name = at((a + b) / 2)
            gaps[name] = gaps.get(name, 0.0) + (b - a)
    n_dev = max(len(busy_per_dev), 1)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy_per_dev) / n_dev / 1e6,
            "window_s": (hi - lo) / 1e6,
            "devices": len(busy_per_dev),
            "device_ops": [[n, s / 1e6] for n, s in top],
            "idle_gaps": [[n, s / 1e6 / n_dev] for n, s in idle],
            "modules": {n: s / 1e6 for n, s in mods.items()},
            "module_calls": calls,
            "ops": {n: s / 1e6 for n, s in op_time.items()}}


class _SpanIndex:
    """The innermost (latest-starting) benchmark span open at a time:
    a binary search for the last span started by then, and a walk back
    that stops where no earlier span reaches that far."""

    def __init__(self, spans):
        self.spans = sorted(spans)
        self.starts = [a for a, _, _ in self.spans]
        self.reach, r = [], float("-inf")
        for _, b, _ in self.spans:
            r = max(r, b)
            self.reach.append(r)

    def __call__(self, t: float) -> str:
        j = bisect.bisect_right(self.starts, t) - 1
        while j >= 0 and self.reach[j] >= t:
            if self.spans[j][1] >= t:
                return self.spans[j][2]
            j -= 1
        return NO_SPAN


def program_seconds(red: dict, names) -> tuple:
    """Device seconds and calls of the programs whose name holds one of
    ``names`` (from the operations, and no calls, where the trace has
    no programs)."""
    table = red["modules"] or red["ops"]
    secs = sum(s for n, s in table.items() if any(k in n for k in names))
    calls = sum(c for n, c in red.get("module_calls", {}).items()
                if any(k in n for k in names))
    return secs, calls
