"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It generates the cell's traffic from
``--seed`` (``bench/traffic.py`` over ``bench/traffic/<mix>.json``),
starts the chip process ``bench/child.py``, waits for its ``ready``
line, and then sends the window's requests open loop over the
service's JSON-lines protocol: each request at its scheduled time,
whether or not earlier ones have been answered, and each timed from
that schedule to its reply. After the window it collects the child's
record and the result of its reference comparison, and prints one JSON
line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, each read by its own reader
``bench/metrics/<name>.py`` from the child's spans and counters and
from the profiler's trace (``bench/trace.py``). Earlier lines give the
compiles inside the window (there should be none), how late the
generator sent, and the served mix. The numbers the comparison
checked are printed beside their limits as the last lines on stderr
and under ``checks``, last in the result line.

``--rate`` overrides the mix's fixed rate (the sweep that finds the
knee); ``--control 1`` puts the control, the reference one precision
down, in the program's place in the comparison, so the run must come
out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench import common, traffic  # noqa: E402

HIT = ("static", "dynamic", "rewritten", "l1")
READY_TIMEOUT_S = 1150.0      # the first run of a cell compiles
REPLY_GRACE_S = 60.0          # a reply that comes late is late, not lost


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests per second instead of the mix's")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the control in the program's place")
    ap.add_argument("--smoke", type=int, choices=(0, 1), default=0,
                    help="tests only: the CPU at the 'smoke' sizes")
    ap.add_argument("--fault", default="", help="tests only")
    return ap.parse_args(argv)


def _fail(msg: str, log: Path | None = None) -> None:
    if log is not None and log.is_file():
        sys.stderr.write(log.read_text()[-6000:])
    sys.stderr.write(f"bench: {msg}\n")
    sys.exit(1)


def start_child(args, log: Path) -> subprocess.Popen:
    env = dict(os.environ)
    # the compile cache lives at a fixed path inside the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = str(common.JAX_CACHE)
    common.JAX_CACHE.mkdir(parents=True, exist_ok=True)
    if args.smoke:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, str(BENCH / "child.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--control", str(args.control), "--smoke", str(args.smoke)]
    if args.rate is not None:
        cmd += ["--rate", str(args.rate)]
    if args.fault:
        cmd += ["--fault", args.fault]
    return subprocess.Popen(cmd, stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE, stderr=log.open("w"),
                            env=env, text=True, bufsize=1,
                            cwd=str(common.CHECKOUT))


class Replies:
    """Reads the child's stdout: replies by id, then its record."""

    def __init__(self, proc):
        self.proc = proc
        self.ready = threading.Event()
        self.got: dict = {}          # id -> (monotonic time, reply)
        self.record = None
        self.done = threading.Event()
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        for line in self.proc.stdout:
            t = time.monotonic()
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if "child" in msg:
                self.record = msg["child"]
            elif msg.get("ready"):
                self.ready.set()
            elif msg.get("id") is not None:
                self.got[msg["id"]] = (t, msg)
        self.done.set()


def send_window(proc, reqs, rows_of, t0: float) -> list:
    """Open loop: request k goes out at ``t0 + offset_k``. Returns how
    late each send was, in seconds."""
    late = []
    for k, (off, prompt, cls) in enumerate(reqs):
        due = t0 + off
        now = time.monotonic()
        if due > now:
            time.sleep(due - now)
        late.append(time.monotonic() - due)
        proc.stdin.write(json.dumps({"op": "serve", "id": k,
                                     "prompt": prompt,
                                     "cls": rows_of(cls)}) + "\n")
        proc.stdin.flush()
    return late


def window_metrics(window, got: dict, t0: float, t_close: float):
    """The end-to-end metrics over all requests of the window. Request
    ``k`` was due at ``t0 + offset_k`` and is timed from then to its
    reply (``got[k] = (time, reply)``), so a stall delays every request
    scheduled behind it; one with no reply waited until ``t_close``.
    Returns (metrics, served_by counts, hits, failed)."""
    lat, hit_lat, served = [], [], {}
    curated = failed = 0
    for k, (off, _, _) in enumerate(window):
        r = got.get(k)
        if r is None or not r[1].get("ok"):
            failed += 1
            lat.append(t_close - (t0 + off))
            continue
        t, msg = r
        dt = t - (t0 + off)
        lat.append(dt)
        by = msg.get("served_by")
        served[by] = served.get(by, 0) + 1
        if by in HIT:
            hit_lat.append(dt)
        if by == "static" or (by in HIT and msg.get("static_origin")):
            curated += 1
    e2e = {"p50_ms": 1e3 * common.quantile(lat, 0.50),
           "p95_ms": 1e3 * common.quantile(lat, 0.95),
           "hit_p95_ms": 1e3 * common.quantile(hit_lat, 0.95),
           "curated_frac": curated / max(len(window), 1)}
    return e2e, served, len(hit_lat), failed


def main(argv=None) -> None:
    args = parse(argv)
    bm = common.benchmark()
    wl = common.workload(args.workload)
    mix = traffic.load_mix(wl["traffic"])
    if args.smoke:
        mix.update(mix.get("smoke", {}))
    conf = common.config(wl["config"])
    static_rows = int((conf.get("smoke") or {}).get(
        "static_rows", conf["deployment"]["static_rows"])
        if args.smoke else conf["deployment"]["static_rows"])
    tr = traffic.generate(mix, args.seed, args.seconds, args.rate)

    def rows_of(c: int) -> int:
        return tr.judge_class(c, static_rows)

    logs = common.CACHE / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    log = logs / f"{args.workload}.{args.seed}.log"
    t_spawn = time.monotonic()
    proc = start_child(args, log)
    rep = Replies(proc)
    try:
        while not rep.ready.wait(0.5):
            if proc.poll() is not None or rep.done.is_set():
                _fail(f"the chip process ended before it was ready "
                      f"(exit {proc.poll()})", log)
            if time.monotonic() - t_spawn > READY_TIMEOUT_S:
                _fail("the chip process was not ready in time", log)
        setup_s = time.monotonic() - t_spawn

        t0 = time.monotonic()
        late = send_window(proc, tr.window, rows_of, t0)
        n = len(tr.window)
        deadline = t0 + args.seconds + REPLY_GRACE_S
        while len(rep.got) < n and time.monotonic() < deadline \
                and not rep.done.is_set():
            time.sleep(0.01)
        t_close = time.monotonic()
        proc.stdin.write(json.dumps({"op": "shutdown"}) + "\n")
        proc.stdin.flush()
        proc.stdin.close()
        rep.done.wait(900)
        proc.wait(60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0 or rep.record is None:
        _fail(f"the chip process failed (exit {proc.returncode})", log)
    child = rep.record

    e2e, served, hits, failed = window_metrics(tr.window, rep.got, t0,
                                               t_close)
    e2e["setup_s"] = setup_s
    late_ms = sorted(1e3 * x for x in late)
    print(json.dumps({"window": {
        "requests": n, "rate_per_s": tr.rate, "seconds": args.seconds,
        "compiles_in_window": child["window_compiles"],
        "compile_sites": child.get("window_compile_sites"),
        "generator_late_ms": {
            "p50": common.quantile(late_ms, 0.5),
            "p99": common.quantile(late_ms, 0.99),
            "max": late_ms[-1] if late_ms else 0.0},
        "served_by": served, "hits": hits,
        "tails_ms": {"p95": e2e["p95_ms"], "hit_p95": e2e["hit_p95_ms"]},
        "batches": len(child["batch_rows"]),
        "max_batch_rows": max(child["batch_rows"], default=0),
        "split_calls": child["split_calls"],
        "setup_child_s": child["setup_s"],
        "compiles_setup": child["compiles_setup"],
        "cache_loads_setup": child.get("cache_loads_setup"),
        "check_s": child["check_s"],
        "check_counts": child["check"]["counts"],
        "program_checks": child["check"].get("program"),
        "prompt_chars": tr.lengths}}), flush=True)

    device = dict(child["device"])
    out = {"correct": bool(child["check"]["correct"]), "attempted": n,
           "failed": failed}
    if args.trace:
        from bench import trace as trace_mod
        red = trace_mod.reduce(child["trace_dir"]) \
            if child.get("trace_dir") else None
        ctx = {"child": child, "trace": red, "deployment":
               _deployment(conf, args), "workload": wl,
               "reference": common.reference(conf),
               "peaks": _peaks(device["kind"]), "e2e": e2e}
        metrics = {}
        for m in bm["per_layer"]:
            if not _applies(m, wl, bm):
                continue
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            out["device"] = device
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
    else:
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in bm["end_to_end"] if _applies(m, wl, bm)}
    out.setdefault("device", device)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in child["check"]["numbers"].items()}
    for k, (v, lim) in child["check"]["numbers"].items():
        sys.stderr.write(f"check {k} {v!r} limit {lim!r}\n")
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def _deployment(conf, args):
    dep = dict(conf["deployment"])
    if args.smoke:
        dep.update(conf.get("smoke", {}))
    return dep


def _applies(m: dict, wl: dict, bm: dict) -> bool:
    if "workloads" in m:
        return wl["name"] in m["workloads"]
    moves = m.get("moves")
    if moves is None:
        return True
    for e in bm["end_to_end"]:
        if e["name"] == moves:
            return _applies(e, wl, bm)
    return True


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no reader for metric {name!r} ({path})")
    return common.load(path, f"bench_metric_{name}").read


def _peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


if __name__ == "__main__":
    main()
