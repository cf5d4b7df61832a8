"""Serving launcher: Krites-fronted LLM engine with request batching.

    PYTHONPATH=src python -m repro.launch.serve --requests 200

Wires the full production topology on local devices: embedder -> tiered
cache (KritesPolicy, async judge pool) -> batching frontend -> LLM engine
(prefill + KV decode). ``--arch`` names the engine's config, built at
its published widths (default qwen3-1.7b) with weights drawn from
``--seed``; ``--arch qwen3-1.7b-smoke`` is the 2-layer x 64 variant for
a CPU. :func:`build_service` does the wiring, for this launcher and for
``chip_smoke.py``. ``--index ivf`` (with ``--static-rows N`` to pad
the curated tier to a realistic size) swaps the static lookup for the
IVF quantized ANN index (DESIGN.md §11):

    PYTHONPATH=src python -m repro.launch.serve --requests 200 \
        --index ivf --static-rows 100000

``--shards N`` serves through the mesh-aware path (DESIGN.md §13): both
tiers row-sharded over an N-device 'model' mesh, per-shard fused scans
with a tiny candidate merge, writes scattered to the owning shard. With
``JAX_PLATFORMS=cpu`` it forces
``XLA_FLAGS=--xla_force_host_platform_device_count`` so N host devices
exist; decisions are identical to ``--shards 1``:

    PYTHONPATH=src python -m repro.launch.serve --requests 200 --shards 4

``--fused`` serves both tier decisions in ONE dispatch (DESIGN.md
§15): the static IVF probe and the masked dynamic top-1 run as a
single fused pass (``kernels/fused_serve``) with exact fp32 reranks,
so served scores match the dispatched paths. It replaces both lookups
and is mutually exclusive with ``--index ivf``, ``--dyn-index
segmented`` and ``--shards``:

    PYTHONPATH=src python -m repro.launch.serve --requests 200 --fused

``--snapshot-dir DIR`` makes the service crash-safe (DESIGN.md §14):
on start it restores the newest snapshot (dynamic tier + mirrors + warm
ANN index) and replays the promotion WAL tail past the snapshot's
``wal_seq`` cursor; every approved promotion is journaled
(append-before-upsert) so a SIGKILL at any point loses no verified
promotion. ``--snapshot-every N`` saves periodically; a final snapshot
+ WAL compaction happens on clean shutdown:

    PYTHONPATH=src python -m repro.launch.serve --requests 200 \
        --snapshot-dir /tmp/krites-snaps

``--serve-stdio`` runs the process as a long-lived JSON-lines service
on stdin/stdout (one request or control op per line; consecutive serve
ops are coalesced into one batched call) — the protocol the live load
harness (``benchmarks/load_service.py``) and the crash-recovery tests
drive:

    {"op": "serve", "id": 0, "prompt": "how do i fix my bike", "cls": 0}
    {"op": "stats"} | {"op": "snapshot"} | {"op": "drain"}
    {"op": "shutdown"}
"""
import argparse
import os
import sys
import time

from repro.launch.jax_setup import enable_compile_cache, force_cpu_devices


def build_demo_tier(emb_rows, answers, static_rows: int = 0,
                    index: str = "flat", nprobe: int = 8, mesh=None,
                    texts=None, ivf=None):
    """Shared demo-topology helper (also used by
    ``launch/cache_workload.py --live``): optionally pad the curated
    tier with synthetic entries to ``static_rows`` rows, then build the
    requested static-index object (DESIGN.md §11) — the sharded variant
    (§13) when a ``mesh`` is given. ``texts`` are the curated entries'
    prompt texts (row-aligned; judge payloads carry them). ``ivf`` is
    an IVF already packed over this same tier, reused instead of a
    rebuild (single-device ``index='ivf'`` only).

    Returns (StaticTier, answers, texts, index object or None for
    exact flat).
    """
    import numpy as np

    from repro.core.tiers import make_static_tier

    emb_rows = np.asarray(emb_rows, np.float32)
    answers = list(answers)
    texts = list(texts) if texts is not None else [str(a) for a in answers]
    if static_rows > len(answers):
        # synthetic curated entries: random directions far from the
        # intent cluster, each its own answer class
        pad = np.random.default_rng(7).normal(
            size=(static_rows - len(answers),
                  emb_rows.shape[1])).astype(np.float32)
        emb_rows = np.concatenate([emb_rows, pad])
        answers += [f"[curated] synthetic-{i}" for i in range(len(pad))]
        texts += [f"synthetic prompt {i}" for i in range(len(pad))]
    tier = make_static_tier(emb_rows, np.arange(len(answers)))

    idx_obj = None
    if index == "ivf":
        if mesh is not None:
            from repro.index.sharded import ShardedIVFIndex
            idx_obj = ShardedIVFIndex(tier.emb, mesh, nprobe=nprobe)
        else:
            from repro.index.ivf import IVFIndex, build_ivf
            if ivf is None:
                ivf = build_ivf(tier.emb, corpus_normalized=True)
            idx_obj = IVFIndex(ivf, nprobe=nprobe)
        print(f"static index: {idx_obj.describe()}")
    return tier, answers, texts, idx_obj


def build_dyn_index(dyn_index: str, capacity: int, d: int,
                    seg_rows: int = 4096, compact_every: int = 4):
    """Dynamic-tier lookup strategy for the launchers (DESIGN.md §12):
    'flat' -> None (exact masked scan), 'segmented' -> a SegmentedIndex
    with a ``seg_rows`` tail sealing into int8 segments and a compactor
    merging every ``compact_every`` of them."""
    if dyn_index != "segmented":
        return None
    from repro.index.segmented import SegmentedIndex
    idx = SegmentedIndex(capacity, d, tail_rows=seg_rows,
                         compact_every=compact_every)
    print(f"dynamic index: {idx.describe()}")
    return idx


DEMO_INTENTS = [f"how do i {v} my {n}" for v in
                ("fix", "update", "reset", "clean", "sell")
                for n in ("bike", "laptop", "router", "garden")]
DEMO_PREFIXES = ["", "hey ", "um, ", "please, ", "quick q: "]


def demo_requests(n: int, seed: int = 0):
    """``n`` demo (prompt, intent class) pairs: a random intent behind a
    random filler prefix, so most requests paraphrase a curated one."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        c = int(rng.integers(0, len(DEMO_INTENTS)))
        p = DEMO_PREFIXES[int(rng.integers(0, len(DEMO_PREFIXES)))]
        out.append((p + DEMO_INTENTS[c], c))
    return out


def _serve_stdio(policy, snap_dir, wal) -> None:
    """JSON-lines service loop (DESIGN.md §14): one message per stdin
    line, one JSON reply per line on stdout. Messages are processed in
    arrival order; consecutive ``serve`` ops already queued are
    coalesced into a single ``serve_batch`` call (the stdio twin of the
    router's micro-batcher). Control ops: ``stats`` (with the span and
    counter aggregates, ``repro.tracing.snapshot()``, under ``trace``),
    ``snapshot``, ``drain``, ``shutdown``. Each request's wait from its
    read to the start of its batch is the counter
    ``loop.queue_wait_s``."""
    import json
    import queue as _q
    import threading

    from repro import tracing
    from repro.distributed import checkpoint as ckpt
    from repro.serving import persist

    inq: "_q.Queue[object]" = _q.Queue()

    def _reader():
        for line in sys.stdin:
            line = line.strip()
            if line:
                inq.put((time.perf_counter(), line))
        inq.put(None)

    threading.Thread(target=_reader, daemon=True,
                     name="stdio-reader").start()

    def emit(obj: dict) -> None:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    def _serve_run(msgs: list, read_at: list) -> None:
        t = time.perf_counter()
        for r in read_at:
            tracing.add("loop.queue_wait_s", t - r)
        tracing.add("loop.batch_rows", len(msgs))
        results = policy.serve_batch(
            [m.get("prompt", "") for m in msgs],
            [{"cls": m["cls"]} if "cls" in m else None for m in msgs])
        with tracing.span("loop.reply", rows=len(msgs)):
            for m, r in zip(msgs, results):
                emit({"ok": True, "id": m.get("id"),
                      "served_by": r.served_by,
                      "static_origin": bool(r.static_origin),
                      "similarity": float(r.similarity),
                      "stale": bool(r.meta.get("stale", False)),
                      "bypass": r.meta.get("bypass"),
                      "answer": None if r.answer is None
                      else str(r.answer)})

    emit({"ok": True, "ready": True, "pid": os.getpid(),
          "t": policy.t, "wal_seq":
          wal.seq if wal is not None else None})
    eof = False
    while not eof:
        first = inq.get()
        if first is None:
            break
        batch = [first]
        while True:          # coalesce whatever has already arrived
            try:
                nxt = inq.get_nowait()
            except _q.Empty:
                break
            if nxt is None:
                eof = True
                break
            batch.append(nxt)

        msgs, read_at = [], []
        for t_read, ln in batch:
            try:
                msgs.append(json.loads(ln))
                read_at.append(t_read)
            except ValueError:
                emit({"ok": False, "error": f"bad json: {ln[:80]!r}"})
        i = 0
        while i < len(msgs):
            msg = msgs[i]
            op = msg.get("op", "serve")
            if op == "serve":
                j = i
                while j < len(msgs) and \
                        msgs[j].get("op", "serve") == "serve":
                    j += 1
                _serve_run(msgs[i:j], read_at[i:j])
                i = j
                continue
            if op == "stats":
                s = policy.stats()
                s["t"] = policy.t
                depth = policy.pool.depth()
                s["judge_queued"] = depth["queued"]
                s["judge_inflight"] = depth["inflight"]
                emit({"ok": True, "id": msg.get("id"), "stats": s,
                      "trace": tracing.snapshot()})
            elif op == "snapshot":
                if snap_dir is None:
                    emit({"ok": False, "id": msg.get("id"),
                          "error": "no --snapshot-dir"})
                else:
                    path = persist.save_snapshot(snap_dir, policy)
                    ckpt.prune(snap_dir, keep=3)
                    emit({"ok": True, "id": msg.get("id"),
                          "snapshot": str(path), "t": policy.t,
                          "wal_seq":
                          wal.seq if wal is not None else None})
            elif op == "drain":
                policy.pool.drain(float(msg.get("timeout_s", 30.0)))
                emit({"ok": True, "id": msg.get("id"),
                      "depth": policy.pool.depth()})
            elif op == "shutdown":
                emit({"ok": True, "id": msg.get("id"), "bye": True})
                eof = True
                break
            else:
                emit({"ok": False, "id": msg.get("id"),
                      "error": f"unknown op {op!r}"})
            i += 1


def build_parser() -> argparse.ArgumentParser:
    """The launcher's options (``chip_smoke.py`` parses its phases'
    settings through this same parser)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b",
                    help="LLM backend config: a registered arch at its "
                         "published widths, or '<arch>-smoke' for its "
                         "2-layer x 64 CPU variant")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed the backend's random weights are "
                         "drawn from")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--tau", type=float, default=0.92)
    ap.add_argument("--shards", type=int, default=1,
                    help="serve both tiers row-sharded over this many "
                         "devices (DESIGN.md §13); with JAX_PLATFORMS=cpu "
                         "forces a host-device mesh of that size. 1 = "
                         "the single-device path")
    ap.add_argument("--index", choices=["flat", "ivf"], default="flat",
                    help="static-tier lookup strategy (DESIGN.md §11); "
                         "'ivf' builds the quantized ANN index over the "
                         "tier and injects it into the policy")
    ap.add_argument("--static-rows", type=int, default=0,
                    help="pad the curated tier to this many rows with "
                         "synthetic entries (exercises the ANN path at "
                         "realistic tier sizes)")
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--fused", action="store_true",
                    help="serve through the fused single-pass pipeline "
                         "(DESIGN.md §15): static IVF probe + masked "
                         "dynamic top-1 in ONE kernel dispatch. "
                         "Replaces both tier lookups; incompatible "
                         "with --index ivf, --dyn-index segmented and "
                         "--shards > 1")
    ap.add_argument("--dyn-index", choices=["flat", "segmented"],
                    default="flat",
                    help="dynamic-tier lookup strategy (DESIGN.md §12); "
                         "'segmented' serves dynamic lookups through the "
                         "incremental tail+segments index")
    ap.add_argument("--seg-rows", type=int, default=4096,
                    help="segmented dynamic index: tail capacity, i.e. "
                         "rows absorbed before sealing an int8 segment")
    ap.add_argument("--compact-every", type=int, default=4,
                    help="segmented dynamic index: merge sealed "
                         "segments whenever this many have accumulated")
    ap.add_argument("--capacity", type=int, default=512,
                    help="dynamic-tier capacity")
    ap.add_argument("--l1-capacity", type=int, default=0,
                    help="L1 exact-match front tier size (DESIGN.md "
                         "§16): canonically identical repeat prompts "
                         "are answered from a hashed lookup with no "
                         "embed and no semantic search. 0 = off")
    ap.add_argument("--volatile-bypass", action="store_true",
                    help="route freshness-volatile prompts (keyword "
                         "classifier, DESIGN.md §16) straight to the "
                         "backend with no cache read or write — "
                         "guarantees zero stale serves on that class")
    ap.add_argument("--ttl-volatile", type=int, default=0,
                    help="cache-entry lifetime (request ticks) the "
                         "judge assigns to volatile-class content; "
                         "0 = never expires")
    ap.add_argument("--ttl-stable", type=int, default=0,
                    help="cache-entry lifetime for stable/unknown-"
                         "class content; 0 = never expires")
    ap.add_argument("--rewrite", action="store_true",
                    help="multi-outcome judge pipeline (DESIGN.md §18): "
                         "grey-zone pairs the judge would reject get a "
                         "REWRITE verdict instead; the template "
                         "rewriter tailors the cached answer and the "
                         "variant is promoted keyed to the NEW "
                         "prompt's embedding — served only to later "
                         "repeats, never the triggering request")
    ap.add_argument("--rewrite-rate", type=float, default=1.0,
                    help="rewrite token-bucket refill per judged task "
                         "(bounds rewriter invocations; empty bucket "
                         "degrades the verdict to REJECT)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="crash-safe persistence (DESIGN.md §14): "
                         "restore the newest snapshot on start, replay "
                         "the promotion WAL tail, snapshot on shutdown")
    ap.add_argument("--wal", default=None,
                    help="promotion write-ahead journal path (default: "
                         "<snapshot-dir>/promo.wal when --snapshot-dir "
                         "is set)")
    ap.add_argument("--wal-fsync-every", type=int, default=1,
                    help="fsync the WAL every N appends (1 = every "
                         "approved promotion is durable before its "
                         "upsert)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="save a snapshot every N served requests "
                         "(0 = only at shutdown / on the stdio "
                         "'snapshot' op)")
    ap.add_argument("--adaptive", action="store_true",
                    help="online threshold controller (DESIGN.md §17): "
                         "per-segment tau_static/tau_dynamic operating "
                         "points tuned live by shadow sweeps over the "
                         "recent request window")
    ap.add_argument("--adapt-every", type=int, default=256,
                    help="recorded requests between shadow sweeps")
    ap.add_argument("--adapt-window", type=int, default=1024,
                    help="request-window ring size the shadow sweep "
                         "re-scores (the first sweep waits for a full "
                         "window)")
    ap.add_argument("--adapt-frozen", action="store_true",
                    help="attach the controller (stats, window, "
                         "persistence) but never move thresholds — "
                         "serving stays bit-identical to pinned")
    ap.add_argument("--serve-stdio", action="store_true",
                    help="run as a long-lived JSON-lines service on "
                         "stdin/stdout instead of the demo loop (the "
                         "load harness and recovery tests drive this)")
    return ap


def build_engine(args):
    """The LLM backend ``--arch`` names, its weights drawn from
    ``--seed``. Batches pad to the front end's 8 rows, so one decode
    program serves every batch."""
    from repro.configs import lm_config
    from repro.serving.engine import LLMEngine
    return LLMEngine(lm_config(args.arch), seed=args.seed, max_len=96,
                     min_batch=8)


class Service:
    """A wired serving stack (what :func:`build_service` returns)."""

    def __init__(self, policy, frontend, engine, wal):
        self.policy = policy
        self.frontend = frontend
        self.engine = engine
        self.wal = wal

    def stop(self) -> None:
        """Stop the judge pool and the batching front end and close the
        WAL; the engine (and its compiled programs) stays usable."""
        self.policy.pool.stop()
        self.frontend.stop()
        if self.wal is not None:
            self.wal.close()
            self.wal = None


def build_service(args, *, engine=None, ivf=None) -> Service:
    """Wire the serving stack the parsed ``args`` describe: embedder ->
    tiered cache (KritesPolicy + judge pool, the configured static and
    dynamic lookups) -> batching front end -> LLM engine, restored from
    ``--snapshot-dir`` when one is on disk. ``engine`` reuses an
    already-built backend and ``ivf`` an IVF already packed over this
    tier, so several services in one process pay for each once."""
    import numpy as np
    from repro.core.judge import OracleJudge, template_rewriter
    from repro.core.policy import KritesPolicy
    from repro.core.tiers import CacheConfig
    from repro.embedding.embedder import Embedder
    from repro.launch.mesh import make_shard_mesh
    from repro.serving.engine import BatchingFrontend

    from repro.serving import persist

    mesh = make_shard_mesh(args.shards) if args.shards > 1 else None
    embed = Embedder(d_out=64)
    if engine is None:
        engine = build_engine(args)
    frontend = BatchingFrontend(engine, max_batch=8, max_new_tokens=8)

    snap = None
    if args.snapshot_dir and \
            persist.latest_snapshot(args.snapshot_dir) is not None:
        snap = persist.load_snapshot(args.snapshot_dir)
        print(f"snapshot: step {snap.step} (t={snap.extra['t']}, "
              f"wal_seq={snap.extra['wal_seq']})")

    intents = DEMO_INTENTS
    canon = intents
    # with a snapshot on disk, defer the IVF build: the snapshot's
    # packed index warm-restores in milliseconds when its corpus hash
    # matches the rebuilt tier (persist.load_static_index); the cold
    # build only runs when the snapshot is stale or absent
    warm_ivf = snap is not None and args.index == "ivf" and mesh is None
    tier, answers, texts, index = build_demo_tier(
        np.asarray(embed.batch(canon)), [f"[curated] {p}" for p in canon],
        static_rows=args.static_rows,
        index="flat" if warm_ivf else args.index,
        nprobe=args.nprobe, mesh=mesh, texts=canon, ivf=ivf)
    if warm_ivf:
        index = persist.load_static_index(snap, tier.emb,
                                          nprobe=args.nprobe)
        if index is not None:
            print(f"static index: warm-restored {index.describe()}")
        else:
            from repro.index.ivf import IVFIndex, build_ivf
            index = IVFIndex(build_ivf(tier.emb, corpus_normalized=True),
                             nprobe=args.nprobe)
            print(f"static index: {index.describe()} "
                  "(snapshot index stale/absent — cold rebuild)")

    fused = None
    if args.fused:
        from repro.index.ivf import build_ivf
        from repro.kernels.fused_serve import FusedServe
        fused = FusedServe(ivf if ivf is not None else
                           build_ivf(tier.emb, corpus_normalized=True),
                           nprobe=args.nprobe)
        print(f"serve path: {fused.describe()}")

    dyn_index = args.dyn_index
    if mesh is not None and dyn_index == "segmented":
        print("note: --dyn-index segmented is single-device only; "
              "--shards serves the dynamic tier through the "
              "row-sharded masked scan instead (DESIGN.md §13)")
        dyn_index = "flat"
    wal = None
    wal_path = args.wal or (os.path.join(args.snapshot_dir, "promo.wal")
                            if args.snapshot_dir else None)
    if wal_path:
        from repro.core.promo_wal import PromotionWAL
        wal = PromotionWAL(wal_path, fsync_every=args.wal_fsync_every)

    # freshness subsystem (DESIGN.md §16): keyword staleness-risk
    # classifier feeding the bypass, the judge's TTL verdicts, and the
    # baseline write-back expiry
    freshness = None
    if args.volatile_bypass or args.ttl_volatile or args.ttl_stable:
        from repro.core.freshness import FreshnessPolicy
        freshness = FreshnessPolicy(volatile_bypass=args.volatile_bypass,
                                    ttl_volatile=args.ttl_volatile,
                                    ttl_stable=args.ttl_stable,
                                    ttl_unknown=args.ttl_stable)
        print(f"freshness: bypass={args.volatile_bypass} "
              f"ttl_volatile={args.ttl_volatile} "
              f"ttl_stable={args.ttl_stable}")
    if args.l1_capacity:
        print(f"l1 front tier: {args.l1_capacity} entries")

    cfg = CacheConfig(args.tau, args.tau, sigma_min=0.3,
                      capacity=args.capacity,
                      l1=bool(args.l1_capacity),
                      volatile_bypass=args.volatile_bypass,
                      ttl_volatile=args.ttl_volatile,
                      ttl_stable=args.ttl_stable,
                      rewrite=args.rewrite,
                      rewrite_rate=args.rewrite_rate)
    if args.rewrite:
        print(f"rewrite verdicts: on (rate={args.rewrite_rate}/judged)")
    adaptive = None
    if args.adaptive:
        from repro.core.adaptive import (AdaptiveController,
                                         AdaptiveParams)
        adaptive = AdaptiveController(
            cfg, d=64,
            params=AdaptiveParams(window=args.adapt_window,
                                  adapt_every=args.adapt_every),
            frozen=args.adapt_frozen)
        print(f"adaptive thresholds: window={args.adapt_window} "
              f"every={args.adapt_every} frozen={args.adapt_frozen}")
    # the demo's oracle rewrite model: every would-reject grey-zone
    # pair is tailorable (the rewriter is the deterministic template)
    judge = OracleJudge(freshness=freshness,
                        rewritable=(lambda qc, hc, qt, ht: True)
                        if args.rewrite else None)
    policy = KritesPolicy(cfg, tier, answers, embed,
                          backend_fn=frontend.submit,
                          judge_fn=judge,
                          d=64,
                          backend_batch_fn=frontend.submit_many,
                          index=index, static_texts=texts,
                          mesh=mesh, wal=wal, fused=fused,
                          rewriter=template_rewriter
                          if args.rewrite else None,
                          l1=args.l1_capacity or None,
                          freshness=freshness, adaptive=adaptive,
                          dyn_index=build_dyn_index(
                              dyn_index, cfg.capacity, 64,
                              seg_rows=args.seg_rows,
                              compact_every=args.compact_every))

    # crash recovery (DESIGN.md §14): newest snapshot first, then the
    # journal tail past its wal_seq cursor — promotions journaled after
    # the capture replay idempotently through the same LWW guard
    if snap is not None:
        rep = persist.restore_policy(policy, snap, rebuild="background")
        print(f"restored: t={rep['t']} dyn_live={rep['dyn_live']} "
              f"index={rep['index']} l1={rep['l1_restored']} "
              f"ttl_dropped={rep['ttl_dropped']}")
    if wal_path and os.path.exists(wal_path):
        from repro.core.promo_wal import replay_into
        r = replay_into(policy, wal_path,
                        skip=snap.extra["wal_seq"] if snap else 0)
        if r["replayed"] or not r["clean"]:
            print(f"wal replay: {r['replayed']} promotions "
                  f"(skipped {r['skipped']}, clean={r['clean']})")
    return Service(policy, frontend, engine, wal)


def main() -> None:
    ap = build_parser()
    args = ap.parse_args()
    if args.fused and (args.index != "flat" or args.dyn_index != "flat"
                       or args.shards > 1):
        ap.error("--fused replaces both tier lookups; drop "
                 "--index ivf / --dyn-index segmented / --shards")
    # the host-device count must be set before the CPU backend starts
    if args.shards > 1:
        force_cpu_devices(args.shards)
    enable_compile_cache()
    from repro.serving import persist

    svc = build_service(args)
    policy, wal = svc.policy, svc.wal
    if args.serve_stdio:
        _serve_stdio(policy, args.snapshot_dir, wal)
        if args.snapshot_dir:
            persist.save_snapshot(args.snapshot_dir, policy)
        svc.stop()
        return

    t0 = time.time()
    for i, (p, c) in enumerate(demo_requests(args.requests)):
        policy.serve(p, meta={"cls": c})
        if (i + 1) % 50 == 0:
            s = policy.stats()
            print(f"{i+1:5d} reqs | static-origin "
                  f"{s['static_origin_rate']:.3f} | backend "
                  f"{s['backend_rate']:.3f} | judged {s['judged']}")
        if args.snapshot_dir and args.snapshot_every \
                and (i + 1) % args.snapshot_every == 0:
            path = persist.save_snapshot(args.snapshot_dir, policy)
            from repro.distributed.checkpoint import prune
            prune(args.snapshot_dir, keep=3)
            print(f"snapshot -> {path.name}")
    policy.pool.drain()
    s = policy.stats()
    print(f"\nfinal ({time.time()-t0:.1f}s):")
    for k, v in s.items():
        print(f"  {k:22s} {v}")
    print(f"  {'engine_compiles':22s} {svc.engine.stats.compiles}")
    if policy.dyn_index is not None:
        print(f"  {'dyn_index':22s} {policy.describe_dyn_index()}")
    sh = policy.shard_stats()
    if sh is not None:
        print(f"  {'shards':22s} {sh['shards']}")
        print(f"  {'shard_occupancy':22s} {sh['shard_occupancy']}")
    if args.snapshot_dir:
        # final snapshot, then drop the journal prefix it covers — the
        # classic checkpoint+truncate cycle (safe only with the WAL
        # closed: compaction rewrites the file under a new inode)
        path = persist.save_snapshot(args.snapshot_dir, policy)
        print(f"  {'snapshot':22s} {path}")
        if wal is not None:
            seq, wal_path = wal.seq, wal.path
            wal.close()
            svc.wal = None
            from repro.core.promo_wal import compact
            kept = compact(wal_path, keep_from_seq=seq)
            print(f"  {'wal_compacted':22s} kept {kept} records")
    svc.stop()


if __name__ == "__main__":
    main()
