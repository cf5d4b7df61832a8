"""Sharded serving benchmark: the mesh-aware static-tier lookup
(DESIGN.md §13) swept over shard count x tier size, with a hard
decision-agreement gate against the single-device path.

Two claims are measured:

- **scaling shape** — per-call wall time of the row-sharded exact
  lookup (``sharded_static_lookup``: per-shard fused scan + tiny
  k-candidate merge) at 1 -> 8 shards per tier size. On a real TPU/GPU
  mesh each shard scans 1/S of the rows; the CPU host-device mesh used
  here shares one socket across shards, so the measured speedup is a
  lower bound (host devices still scan their partitions on separate
  threads) and chiefly demonstrates the merge + partition overhead
  stays small enough for the layout to win (see EXPERIMENTS.md).
- **decision agreement** — the merged (score, index) pairs must produce
  exactly the decisions of single-device search on every query
  (agreement 1.0): per-row scores are bit-identical (the dot product is
  over the unpartitioned d axis) and the stable shard merge keeps the
  lowest-index tie rule.

    PYTHONPATH=src python -m benchmarks.sharded_serve [--smoke]

``--smoke`` is the CI entry (scripts/ci.sh): a full serving-path
differential — ``BaselinePolicy``/``KritesPolicy`` with ``mesh=`` vs
single-device on the same trace, scalar and batched — asserting
decision agreement 1.0. Both need 8 devices: run standalone with
``JAX_PLATFORMS=cpu`` for an 8-device host mesh. Registered in
``benchmarks.run``, which gives the CPU backend 8 devices itself.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

SHARDS = (1, 2, 4, 8)
SIZES_SMALL = (65_536, 262_144)
SIZES_FULL = (65_536, 262_144, 1_048_576)
TAU = 0.85
B = 32
D = 64


def _bench(scale: str = "small"):
    import jax
    import jax.numpy as jnp

    from benchmarks.common import (clustered_cache_workload,
                                   decision_agreement, timed_median)
    from repro.index.sharded import sharded_static_lookup
    from repro.kernels.simsearch.ops import cosine_topk
    from repro.launch.mesh import make_shard_mesh

    rng = np.random.default_rng(0)
    rows = []
    for n_rows in (SIZES_FULL if scale == "full" else SIZES_SMALL):
        corpus_np, q_np = clustered_cache_workload(n_rows, rng, B, D)
        corpus, q = jnp.asarray(corpus_np), jnp.asarray(q_np)
        flat_t = timed_median(lambda: cosine_topk(q, corpus, k=1))
        v_f, i_f = jax.device_get(cosine_topk(q, corpus, k=1))
        v_f, i_f = v_f[:, 0], i_f[:, 0]
        for n_shards in SHARDS:
            if n_shards > len(jax.devices()):
                continue
            if n_shards == 1:
                t, v_s, i_s = flat_t, v_f, i_f
            else:
                mesh = make_shard_mesh(n_shards)
                lookup = sharded_static_lookup(mesh, corpus)
                t = timed_median(lambda: lookup(q))
                v_s, i_s = jax.device_get(lookup(q))
            rows.append({
                "name": f"sharded_serve/N{n_rows}_shards{n_shards}",
                "us_per_call": round(1e6 * t, 1),
                "flat_us_per_call": round(1e6 * flat_t, 1),
                "speedup_vs_flat": round(flat_t / t, 2),
                "decision_agreement": decision_agreement(
                    v_f, i_f, v_s, i_s, TAU),
                "B": B, "d": D,
            })
    return rows


def run(scale: str = "small"):
    """Entry for ``benchmarks.run``: the sweep needs ``max(SHARDS)``
    devices. With ``JAX_PLATFORMS=cpu`` in a process that has not
    started jax yet, it re-execs itself in a child with a forced
    host-device mesh; otherwise it runs here, on the devices this
    process has (a child never competes with this process for a
    chip)."""
    from jax._src import xla_bridge

    if os.environ.get("JAX_PLATFORMS") != "cpu" \
            or xla_bridge.backends_are_initialized():
        import jax
        if len(jax.devices()) < max(SHARDS):
            raise RuntimeError(
                f"sharded_serve needs {max(SHARDS)} devices, this "
                f"process has {len(jax.devices())}")
        return _bench(scale)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ,
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sharded_serve", "--json",
         "--scale", scale],
        capture_output=True, text=True, timeout=1800, env=env,
        cwd=str(Path(__file__).resolve().parents[1]))
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-1000:])
    for line in out.stdout.splitlines():
        if line.startswith("ROWS_JSON:"):
            return json.loads(line[len("ROWS_JSON:"):])
    raise RuntimeError("sharded_serve subprocess emitted no rows")


def smoke(n_shards: int = 8, n: int = 160) -> None:
    """CI gate: full serving-path differential, sharded vs single device
    (scalar + batch), asserting decision agreement 1.0."""
    import dataclasses
    import threading

    import jax
    import jax.numpy as jnp

    from repro.core.judge import OracleJudge
    from repro.core.policy import KritesPolicy
    from repro.core.tiers import CacheConfig, make_static_tier
    from repro.data.synth_traces import LMARENA_LIKE, build_benchmark
    from repro.launch.mesh import make_shard_mesh

    assert len(jax.devices()) >= n_shards, \
        (f"smoke needs {n_shards} devices — run standalone with "
         f"JAX_PLATFORMS=cpu for a host-device mesh")
    mesh = make_shard_mesh(n_shards)
    spec = dataclasses.replace(LMARENA_LIKE, n_requests=4000,
                               n_classes=120)
    bench = build_benchmark(spec)
    emb = {f"q{i}": bench.eval_emb[i] for i in range(n)}
    prompts = [f"q{i}" for i in range(n)]
    metas = [{"cls": int(bench.eval_cls[i])} for i in range(n)]
    tier = make_static_tier(jnp.asarray(bench.static_emb),
                            jnp.asarray(bench.static_cls))
    answers = [f"curated-{int(c)}" for c in bench.static_cls]
    texts = [f"canonical prompt {i}" for i in range(len(answers))]
    cfg = CacheConfig(0.92, 0.88, sigma_min=0.0, capacity=128)

    class GatedOracle:
        """Oracle that blocks until the driver opens the gate, so
        promotions land at identical (chunk-boundary) points in both
        policies and the decision streams stay comparable."""

        def __init__(self):
            self.gate = threading.Event()
            self.oracle = OracleJudge(require_texts=True)

        def __call__(self, q_cls, h_cls, **kw):
            self.gate.wait()
            return self.oracle(q_cls, h_cls, **kw)

    def mk(m):
        judge = GatedOracle()
        pol = KritesPolicy(
            cfg, tier, answers, lambda p: emb[p], lambda p: f"gen({p})",
            judge, d=bench.static_emb.shape[1],
            n_workers=1, static_texts=texts, mesh=m,
            embed_batch_fn=lambda ps: np.stack([emb[p] for p in ps]),
            backend_batch_fn=lambda ps: [f"gen({p})" for p in ps])
        return pol, judge

    def drive(pol, judge, batched):
        out = []
        for i in range(0, n, 32):
            chunk = slice(i, i + 32)
            if batched:
                out += pol.serve_batch(prompts[chunk], metas[chunk])
            else:
                out += [pol.serve(p, m) for p, m in
                        zip(prompts[chunk], metas[chunk])]
            judge.gate.set()       # promotions land at chunk boundaries
            pol.pool.drain()
            judge.gate.clear()
        judge.gate.set()
        pol.pool.drain()
        pol.pool.stop()
        return pol, out

    for batched in (False, True):
        p1, r1 = drive(*mk(None), batched)
        p2, r2 = drive(*mk(mesh), batched)
        agree = np.mean([(a.served_by, a.answer, a.static_origin)
                         == (b.served_by, b.answer, b.static_origin)
                         for a, b in zip(r1, r2)])
        mode = "batch" if batched else "scalar"
        assert p1.events == p2.events, f"{mode}: event streams differ"
        assert agree == 1.0, f"{mode}: decision agreement {agree} < 1.0"
        assert p2.stats()["approved"] > 0, f"{mode}: no promotions"
        # the sharded write path must keep host mirrors == device tier
        assert np.array_equal(p2._valid_np, np.asarray(p2.dyn.valid))
        assert np.array_equal(p2._static_origin_np,
                              np.asarray(p2.dyn.static_origin))
        sh = p2.shard_stats()
        assert sh["shards"] == n_shards
        assert sum(sh["shard_occupancy"]) == int(p2._valid_np.sum())
        print(f"[OK] sharded serve smoke ({mode}): shards={n_shards}, "
              f"decision agreement {agree:.3f}, "
              f"approved={p2.stats()['approved']}, "
              f"occupancy={sh['shard_occupancy']}")


if __name__ == "__main__":
    from repro.launch.jax_setup import force_cpu_devices
    force_cpu_devices(max(SHARDS))
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "full"], default="small")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: sharded-vs-single serving "
                         "differential with agreement-1.0 asserts")
    ap.add_argument("--json", action="store_true",
                    help="emit rows as one ROWS_JSON line (subprocess "
                         "protocol for benchmarks.run)")
    a = ap.parse_args()
    if a.smoke:
        smoke()
    elif a.json:
        print("ROWS_JSON:" + json.dumps(_bench(scale=a.scale)))
    else:
        for r in _bench(scale=a.scale):
            print(r)
