"""Live load harness: streaming trace replay against a long-lived
serve process at a target QPS (DESIGN.md §14).

Unlike the in-process benchmarks, this drives ``launch/serve.py
--serve-stdio`` over its JSON-lines protocol from a *separate* process
— the same topology a production deployment has — with **open-loop**
pacing: each request has a scheduled send time on a fixed QPS grid and
its latency is measured from that schedule, so a stalled service
accrues queueing delay instead of silently slowing the generator
(no coordinated omission). Reported per window:

- p50/p99 end-to-end latency (schedule -> reply),
- tier hit-rate drift (static / dynamic / backend shares over time —
  the dynamic share should climb as promotions land),
- judge-queue depth + WAL seq, sampled via interleaved ``stats`` ops.

    PYTHONPATH=src python -m benchmarks.load_service --qps 50 \
        --duration 20 [--snapshot-dir DIR] [--snapshot-mid]

The service process runs on whatever platform this one is given
(``JAX_PLATFORMS`` is inherited); its backend is qwen3-1.7b at smoke
width (``SERVICE_ARCH``), so the harness measures the cache service.
On an accelerator the service must be the only process that holds it.

``--smoke`` is the CI gate (scripts/ci.sh): a short burst against a
snapshotting service, a mid-run snapshot, a clean shutdown, then a
restart from the snapshot that must come back warm (restored clock
advances, no cold backend storm) and keep serving.

``--restore-bench`` measures warm snapshot restore vs cold index
rebuild at a >=256k-row static tier (EXPERIMENTS.md): the time to
re-install the packed IVF layout from disk vs re-running k-means +
quantization over the corpus.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

SRC = str(Path(__file__).resolve().parent.parent / "src")


class _Pending:
    __slots__ = ("sched", "reply", "recv_t", "done")

    def __init__(self, sched: float):
        self.sched = sched
        self.reply = None
        self.recv_t = 0.0
        self.done = threading.Event()


def _check_chip_free() -> None:
    """A chip belongs to one process: a parent that already runs jax on
    an accelerator would leave the service child none."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "this process holds the accelerator; start the load "
                "harness from a process that has not run jax")


class ServeClient:
    """Client for the ``--serve-stdio`` JSON-lines protocol: spawns the
    service, tags every message with an id, and matches replies on a
    reader thread (receive-timestamping them for latency accounting)."""

    def __init__(self, extra_args=(), env_extra=None, start_timeout=300.0):
        _check_chip_free()
        env = dict(os.environ,
                   PYTHONPATH=SRC + (os.pathsep + os.environ["PYTHONPATH"]
                                     if os.environ.get("PYTHONPATH")
                                     else ""))
        env.update(env_extra or {})
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve", "--serve-stdio",
             *extra_args],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, bufsize=1, env=env)
        self._lock = threading.Lock()
        self._pending: dict = {}
        self._next_id = 0
        self._ready = None
        self._ready_ev = threading.Event()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        if not self._ready_ev.wait(start_timeout):
            self.kill()
            raise TimeoutError("service did not come up")

    @property
    def ready(self) -> dict:
        return self._ready or {}

    def _read(self):
        for line in self.proc.stdout:
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if self._ready is None and obj.get("ready"):
                self._ready = obj
                self._ready_ev.set()
                continue
            now = time.monotonic()
            with self._lock:
                p = self._pending.pop(obj.get("id"), None)
            if p is not None:
                p.reply, p.recv_t = obj, now
                p.done.set()

    def send(self, msg: dict, sched: float = None) -> _Pending:
        p = _Pending(time.monotonic() if sched is None else sched)
        with self._lock:
            msg["id"] = self._next_id
            self._next_id += 1
            self._pending[msg["id"]] = p
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        return p

    def call(self, msg: dict, timeout: float = 300.0) -> dict:
        p = self.send(msg)
        if not p.done.wait(timeout):
            raise TimeoutError(f"no reply to {msg}")
        return p.reply

    def shutdown(self, timeout: float = 30.0) -> int:
        try:
            self.call({"op": "shutdown"}, timeout)
        except Exception:  # noqa: BLE001 — fall through to kill
            pass
        try:
            self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.kill()
        return self.proc.returncode

    def kill(self):
        self.proc.kill()
        self.proc.wait(10)


def _trace(n: int, seed: int = 0):
    """The launcher's demo workload, regenerated here so the harness
    and the service agree on the intent set without sharing state."""
    from repro.launch.serve import DEMO_INTENTS, DEMO_PREFIXES
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        c = int(rng.integers(0, len(DEMO_INTENTS)))
        p = DEMO_PREFIXES[int(rng.integers(0, len(DEMO_PREFIXES)))] \
            + DEMO_INTENTS[c]
        out.append((p, c))
    return out


def run_load(client: ServeClient, qps: float, duration_s: float, *,
             window_s: float = 2.0, stats_every_s: float = 1.0,
             snapshot_at_s: float = None, seed: int = 0) -> dict:
    """Open-loop replay at ``qps`` for ``duration_s``; returns windowed
    latency/hit-rate series plus judge-depth samples."""
    n = max(1, int(qps * duration_s))
    trace = _trace(n, seed)
    pend = []
    depth_samples = []
    stop = threading.Event()

    def _poll_stats():
        while not stop.is_set():
            try:
                st = client.call({"op": "stats"}, 60.0)["stats"]
            except Exception:  # noqa: BLE001 — service shutting down
                return
            depth_samples.append({
                "t": round(time.monotonic() - start, 2),
                "judge_queued": st.get("judge_queued", 0),
                "judge_inflight": st.get("judge_inflight", 0),
                "wal_seq": st.get("wal_seq"),
            })
            stop.wait(stats_every_s)

    start = time.monotonic() + 0.05
    poller = threading.Thread(target=_poll_stats, daemon=True)
    poller.start()
    snap_reply = None
    for k, (prompt, cls) in enumerate(trace):
        sched = start + k / qps
        delay = sched - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if snapshot_at_s is not None and sched - start >= snapshot_at_s:
            snap_reply = client.call({"op": "snapshot"})
            snapshot_at_s = None
        pend.append(client.send(
            {"op": "serve", "prompt": prompt, "cls": cls}, sched=sched))

    for p in pend:
        p.done.wait(300.0)
    stop.set()
    poller.join(5.0)

    # windowed aggregation off the scheduled (open-loop) timeline
    n_win = max(1, int(np.ceil(duration_s / window_s)))
    wins = [{"lat": [], "stale": 0, "promoted": 0,
             "by": {"l1": 0, "static": 0, "dynamic": 0,
                    "rewritten": 0, "backend": 0}}
            for _ in range(n_win)]
    lost = 0
    for k, p in enumerate(pend):
        if p.reply is None:
            lost += 1
            continue
        w = wins[min(int((p.sched - start) / window_s), n_win - 1)]
        w["lat"].append(p.recv_t - p.sched)
        by = p.reply["served_by"]
        w["by"][by] = w["by"].get(by, 0) + 1
        w["stale"] += bool(p.reply.get("stale"))
        # dynamic hits serving promoted (static-origin) content — the
        # per-window hit-source attribution splits the dynamic tier by
        # content origin (DESIGN.md §16)
        w["promoted"] += (by in ("dynamic", "rewritten")
                          and bool(p.reply.get("static_origin")))
    windows = []
    for i, w in enumerate(wins):
        m = sum(w["by"].values())
        lat = np.asarray(w["lat"])
        windows.append({
            "t0_s": round(i * window_s, 2),
            "n": m,
            "p50_ms": round(1e3 * float(np.percentile(lat, 50)), 2)
            if len(lat) else None,
            "p99_ms": round(1e3 * float(np.percentile(lat, 99)), 2)
            if len(lat) else None,
            "l1_rate": round(w["by"]["l1"] / m, 3) if m else None,
            "static_rate": round(w["by"]["static"] / m, 3) if m else None,
            "dynamic_rate": round(w["by"]["dynamic"] / m, 3)
            if m else None,
            "rewritten_rate": round(w["by"]["rewritten"] / m, 3)
            if m else None,
            "promoted_rate": round(w["promoted"] / m, 3) if m else None,
            "backend_rate": round(w["by"]["backend"] / m, 3)
            if m else None,
            "stale_rate": round(w["stale"] / m, 3) if m else None,
        })
    lat_all = np.asarray([p.recv_t - p.sched for p in pend
                          if p.reply is not None])
    return {
        "requests": n, "lost": lost, "qps": qps,
        "p50_ms": round(1e3 * float(np.percentile(lat_all, 50)), 2),
        "p99_ms": round(1e3 * float(np.percentile(lat_all, 99)), 2),
        "windows": windows,
        "depth_samples": depth_samples,
        "snapshot": snap_reply,
        # drift = how far the last window's tier mix moved from the
        # first full window's (promotions shifting traffic off backend)
        "hit_rate_drift": _drift(windows),
    }


def _drift(windows):
    full = [w for w in windows if w["n"]]
    if len(full) < 2:
        return None
    a, b = full[0], full[-1]
    return {k: round(b[k] - a[k], 3)
            for k in ("l1_rate", "static_rate", "dynamic_rate",
                      "rewritten_rate", "backend_rate")}


# ---------------------------------------------------------------------------
# restore benchmark (EXPERIMENTS.md: warm restore vs cold rebuild)
# ---------------------------------------------------------------------------

def restore_bench(n_rows: int = 262_144, d: int = 64,
                  capacity: int = 4096) -> dict:
    """Warm snapshot restore vs cold IVF rebuild at a ``n_rows`` static
    tier. Cold = k-means + int8 quantization over the corpus (what a
    restart without persistence pays); warm = reading the packed layout
    off disk, hash-verifying it, and re-wiring it to the live tier."""
    import jax.numpy as jnp

    from benchmarks.common import clustered_cache_workload
    from repro.core.policy import KritesPolicy
    from repro.core.tiers import CacheConfig, StaticTier
    from repro.index.ivf import IVFIndex, build_ivf
    from repro.serving import persist

    rng = np.random.default_rng(0)
    corpus_np, _ = clustered_cache_workload(n_rows, rng, 8, d)
    corpus = jnp.asarray(corpus_np)

    t0 = time.monotonic()
    index = IVFIndex(build_ivf(corpus, corpus_normalized=True))
    index.topk(corpus[:1], 1)   # include first-dispatch in cold cost
    cold_s = time.monotonic() - t0

    static = StaticTier(emb=corpus,
                        cls=jnp.zeros(n_rows, jnp.int32),
                        answer_ref=jnp.arange(n_rows, dtype=jnp.int32))
    cfg = CacheConfig(0.9, 0.85, sigma_min=0.3, capacity=capacity)

    def mk(idx):
        return KritesPolicy(cfg, static, [""] * n_rows,
                            embed_fn=lambda p: np.zeros(d, np.float32),
                            backend_fn=lambda p: "", d=d,
                            judge_fn=lambda **kw: True, n_workers=0,
                            index=idx)

    tmp = tempfile.mkdtemp(prefix="restore-bench-")
    try:
        pol = mk(index)
        t0 = time.monotonic()
        persist.save_snapshot(tmp, pol)
        save_s = time.monotonic() - t0

        fresh = mk(None)
        t0 = time.monotonic()
        rep = persist.restore_policy(fresh, tmp)
        fresh.index.topk(corpus[:1], 1)
        warm_s = time.monotonic() - t0
        assert rep["index"] == "warm", rep
        snap_bytes = sum(f.stat().st_size
                         for f in Path(tmp).rglob("*") if f.is_file())
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"rows": n_rows, "cold_build_s": round(cold_s, 2),
            "snapshot_save_s": round(save_s, 2),
            "warm_restore_s": round(warm_s, 2),
            "speedup": round(cold_s / warm_s, 1),
            "snapshot_mb": round(snap_bytes / 1e6, 1)}


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

SERVICE_ARCH = "qwen3-1.7b-smoke"


def _service_args(snap_dir, capacity=512):
    return ["--snapshot-dir", snap_dir, "--capacity", str(capacity),
            "--arch", SERVICE_ARCH]


def smoke() -> None:
    """CI gate: load -> snapshot -> shutdown -> warm restart -> serve."""
    tmp = tempfile.mkdtemp(prefix="load-smoke-")
    try:
        client = ServeClient(_service_args(tmp))
        res = run_load(client, qps=40, duration_s=3.0, window_s=1.0,
                       snapshot_at_s=1.5)
        rc = client.shutdown()
        assert rc == 0, f"service exit code {rc}"
        assert res["lost"] == 0, f"lost {res['lost']} replies"
        assert res["snapshot"] and res["snapshot"]["ok"], res["snapshot"]
        assert res["depth_samples"], "no stats samples collected"
        t_before = res["snapshot"]["t"]

        client = ServeClient(_service_args(tmp))
        ready = client.ready
        # warm restart: the restored logical clock must resume past the
        # mid-run snapshot, not from zero
        assert ready["t"] >= t_before > 0, ready
        res2 = run_load(client, qps=40, duration_s=1.0, window_s=1.0,
                        seed=1)
        assert res2["lost"] == 0
        # a warm cache serves the same workload without a cold-start
        # backend storm
        w = [x for x in res2["windows"] if x["n"]][0]
        assert w["backend_rate"] <= 0.5, w
        assert client.shutdown() == 0
        print(f"load_service smoke OK: {res['requests']} + "
              f"{res2['requests']} reqs, restart t={ready['t']}, "
              f"restart backend_rate={w['backend_rate']}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(scale: str = "small"):
    """benchmarks.run registry entry."""
    tmp = tempfile.mkdtemp(prefix="load-bench-")
    try:
        dur = 6.0 if scale == "small" else 20.0
        client = ServeClient(_service_args(tmp))
        res = run_load(client, qps=50, duration_s=dur,
                       snapshot_at_s=dur / 2)
        client.shutdown()
        rows = [{
            "name": f"load_service/qps50-{int(dur)}s",
            "us_per_call": round(1e3 * res["p50_ms"], 1),
            "p99_ms": res["p99_ms"], "lost": res["lost"],
            "hit_rate_drift": res["hit_rate_drift"],
            "max_judge_queued": max((s["judge_queued"]
                                     for s in res["depth_samples"]),
                                    default=0),
        }]
        if scale == "full":
            rb = restore_bench()
            rows.append({"name": f"load_service/restore-{rb['rows']}",
                         "us_per_call": round(1e6 * rb["warm_restore_s"],
                                              1), **rb})
        return rows
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--qps", type=float, default=50.0)
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--window", type=float, default=2.0)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--snapshot-dir", default=None,
                    help="persist the service under this dir (default: "
                         "a throwaway tmp dir)")
    ap.add_argument("--snapshot-mid", action="store_true",
                    help="take a snapshot halfway through the run "
                         "(shows its latency cost in the p99 window)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--restore-bench", action="store_true",
                    help="measure warm restore vs cold IVF rebuild at "
                         "a 262144-row static tier (EXPERIMENTS.md)")
    ap.add_argument("--restore-rows", type=int, default=262_144)
    args = ap.parse_args()

    if args.smoke:
        smoke()
        return
    if args.restore_bench:
        print(json.dumps(restore_bench(args.restore_rows), indent=1))
        return

    tmp = None
    snap_dir = args.snapshot_dir
    if snap_dir is None:
        tmp = tempfile.mkdtemp(prefix="load-service-")
        snap_dir = tmp
    try:
        client = ServeClient(_service_args(snap_dir, args.capacity))
        print(f"service up (pid {client.ready.get('pid')}, "
              f"t={client.ready.get('t')})")
        res = run_load(client, args.qps, args.duration,
                       window_s=args.window,
                       snapshot_at_s=args.duration / 2
                       if args.snapshot_mid else None)
        client.shutdown()
        print(f"\n{res['requests']} requests @ {args.qps} qps | "
              f"p50 {res['p50_ms']}ms p99 {res['p99_ms']}ms | "
              f"lost {res['lost']}")
        print(f"{'t0':>6} {'n':>5} {'p50ms':>8} {'p99ms':>8} "
              f"{'static':>7} {'dyn':>6} {'backend':>8}")
        for w in res["windows"]:
            if not w["n"]:
                continue
            print(f"{w['t0_s']:>6} {w['n']:>5} {w['p50_ms']:>8} "
                  f"{w['p99_ms']:>8} {w['static_rate']:>7} "
                  f"{w['dynamic_rate']:>6} {w['backend_rate']:>8}")
        print(f"drift first->last window: {res['hit_rate_drift']}")
        if res["depth_samples"]:
            mx = max(s["judge_queued"] + s["judge_inflight"]
                     for s in res["depth_samples"])
            print(f"judge depth: max {mx}, samples "
                  f"{len(res['depth_samples'])}, final wal_seq "
                  f"{res['depth_samples'][-1]['wal_seq']}")
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
