"""The program's own spans and counters (``repro.tracing``), as the
per-layer readers see them.

``aggregates(ctx)`` returns them in the shape of
``repro.tracing.snapshot()``: ``{"spans": {name: {calls, seconds,
self_seconds, rows, max_seconds}}, "counters": {name: {n, sum, max}}}``,
rebuilt from the ``krites.*`` events of a traced run's profiler trace,
which holds the window's first seconds, up to the profiler's event cap.
A span is a complete event (its self time is its length less that of
the program spans directly inside it on its thread), a counter's value
a zero-length event with a ``value`` argument. None where the trace
holds none, as on a program without them, or where there is no trace.

    python3 bench/program.py <trace dir>

prints the aggregates and the device's idle time by the innermost span
open at the middle of each gap, program (``krites.*``) and benchmark
spans alike.
"""
from __future__ import annotations

import functools
import gzip
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PREFIX = "krites."


def aggregates(ctx) -> dict | None:
    return from_trace(ctx["child"].get("trace_dir"))


def spans(ctx) -> dict:
    return (aggregates(ctx) or {}).get("spans", {})


def counters(ctx) -> dict:
    return (aggregates(ctx) or {}).get("counters", {})


def _text(trace_dir) -> str | None:
    path = trace.find(trace_dir) if trace_dir else None
    if path is None:
        return None
    with gzip.open(path, "rt") as f:
        return f.read()


def _events(text: str) -> list:
    data = json.loads(text)
    return data["traceEvents"] if isinstance(data, dict) else data


def _program_only(text: str) -> list:
    """The ``krites.*`` events alone, each decoded where its name is
    found, without parsing the ~10^6 others. The profiler's exporter
    writes an event's name before its arguments; a trace laid out
    otherwise is an error."""
    dec, out, pos = json.JSONDecoder(), [], 0
    while (i := text.find('"' + PREFIX, pos)) >= 0:
        e, pos = dec.raw_decode(text, text.rfind("{", 0, i))
        if not str(e.get("name", "")).startswith(PREFIX):
            raise ValueError(f"a trace event at offset {i} is not laid out "
                             "as the profiler exports it")
        out.append(e)
    return out


@functools.lru_cache(maxsize=2)
def from_trace(trace_dir) -> dict | None:
    text = _text(trace_dir)
    if text is None or '"' + PREFIX not in text:   # a program without them
        return None
    return from_events(_program_only(text))


def _program_events(events):
    """(thread, start, length, name, args) of each ``krites.*`` complete
    event."""
    for e in events:
        name = str(e.get("name", ""))
        if e.get("ph") == "X" and name.startswith(PREFIX):
            yield ((e["pid"], e["tid"]), float(e["ts"]),
                   float(e.get("dur", 0.0)), name[len(PREFIX):],
                   e.get("args") or {})


def from_events(events) -> dict | None:
    sp, ct, by_thread = {}, {}, {}
    for th, ts, dur, name, args in _program_events(events):
        if "value" in args:
            v = float(args["value"])
            c = ct.setdefault(name, {"n": 0, "sum": 0.0, "max": v})
            c["n"] += 1
            c["sum"] += v
            c["max"] = max(c["max"], v)
        else:
            by_thread.setdefault(th, []).append(
                [ts, dur, name, int(args.get("rows", 0)), 0.0])
    for iv in by_thread.values():
        iv.sort(key=lambda s: (s[0], -s[1]))     # a parent before its child
        open_ = []
        for s in iv:
            while open_ and open_[-1][0] + open_[-1][1] <= s[0]:
                open_.pop()
            if open_:
                open_[-1][4] += s[1]
            open_.append(s)
        for ts, dur, name, rows, child in iv:
            a = sp.setdefault(name, {"calls": 0, "seconds": 0.0,
                                     "self_seconds": 0.0, "rows": 0,
                                     "max_seconds": 0.0})
            a["calls"] += 1
            a["seconds"] += dur / 1e6
            a["self_seconds"] += (dur - child) / 1e6
            a["rows"] += rows
            a["max_seconds"] = max(a["max_seconds"], dur / 1e6)
    if not sp and not ct:
        return None
    return {"spans": sp, "counters": ct}


def idle_gaps(events) -> list:
    """``bench/trace.py``'s idle gaps with the program's spans taken as
    well as the benchmark's: a program span keeps its ``krites.`` prefix.
    On a trace with no program spans this is ``reduce_events``'s own."""
    ev = []
    for e in events:
        name = str(e.get("name", ""))
        if e.get("ph") == "X" and name.startswith(PREFIX) \
                and "value" not in (e.get("args") or {}):
            e = {**e, "name": "bench." + name}
        ev.append(e)
    return trace.reduce_events(ev)["idle_gaps"]


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        raise SystemExit("usage: python3 bench/program.py <trace dir>")
    text = _text(argv[0])
    if text is None or '"' + PREFIX not in text:
        raise SystemExit(f"no program spans in a trace under {argv[0]}")
    print(json.dumps({"program": from_events(_program_only(text)),
                      "idle_gaps": idle_gaps(_events(text))}, indent=1))


if __name__ == "__main__":
    main()
