"""The paper's own serving-path compute as a dry-run workload: batched
Krites cache lookup against a production-sized static tier.

Workload: B concurrent requests x (embed-dim d) queries against a static
tier of S curated entries sharded over 'model' — per-shard fused
simsearch (normalize · GEMM · online top-k) + k-candidate merge. This is
the simsearch kernel's production shape; run it through dryrun-style
lowering with:

    PYTHONPATH=src python -m repro.launch.cache_workload

``--live`` instead runs the same serving path end to end on local
devices: concurrent clients -> CacheRouter micro-batcher ->
KritesPolicy.serve_batch (fused static top-k + masked dynamic lookup +
bulk grey-zone verification) -> batched backend (DESIGN.md §7):

    PYTHONPATH=src python -m repro.launch.cache_workload --live
"""
from repro.launch.jax_setup import force_cpu_devices

# the dry-run lowers against a fake 512-device mesh: with
# JAX_PLATFORMS=cpu the CPU backend provides it (before jax starts)
force_cpu_devices(512)

import json                      # noqa: E402
import time                      # noqa: E402
from pathlib import Path         # noqa: E402

import jax                       # noqa: E402
import jax.numpy as jnp          # noqa: E402

from repro.analysis import roofline as rl                  # noqa: E402
from repro.analysis.hlo_parse import collective_bytes      # noqa: E402
from repro.index.sharded import sharded_cosine_topk        # noqa: E402
from repro.launch.mesh import make_production_mesh         # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def run(B: int = 4096, S: int = 4_194_304, d: int = 64, k: int = 4,
        multi_pod: bool = False) -> dict:
    """4096 in-flight requests against a 4M-entry curated tier."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    q = jax.ShapeDtypeStruct((B, d), jnp.float32)
    corpus = jax.ShapeDtypeStruct((S, d), jnp.float32)

    with mesh:
        c = jax.jit(
            lambda q, c: sharded_cosine_topk(q, c, mesh, k=k)
        ).lower(q, corpus).compile()
    hlo = c.as_text()
    ca = c.cost_analysis()
    mem = rl.memory_summary(c)
    args_b = mem.get("argument_size_in_bytes", 0.0)
    out_b = mem.get("output_size_in_bytes", 0.0)
    roof = rl.Roofline(
        name=f"krites-cache-lookup:B{B}xS{S}", chips=mesh.devices.size,
        hlo_flops=float(ca.get("flops", 0.0)),
        hlo_bytes=args_b + out_b + mem.get("temp_size_in_bytes", 0.0),
        coll_bytes=float(collective_bytes(hlo).get("total", 0)),
        model_flops=2.0 * B * S * d).finalize()
    rec = {"arch": "krites-cache-lookup", "shape": f"B{B}xS{S}xd{d}",
           "mesh": mesh_name, "ok": True, "memory": mem,
           "collective_bytes": collective_bytes(hlo),
           "roofline": roof.to_dict()}
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"krites-cache-lookup__B{B}xS{S}__{mesh_name}.json"
     ).write_text(json.dumps(rec, indent=1))
    print(f"[OK] cache-lookup {mesh_name}: bound={roof.bound} "
          f"step={roof.step_s*1e6:.1f}us compute={roof.compute_s*1e6:.1f}us "
          f"mem={roof.memory_s*1e6:.1f}us coll={roof.collective_s*1e6:.1f}us "
          f"frac={roof.roofline_frac:.2f}")
    return rec


def run_live(n_requests: int = 800, n_clients: int = 8,
             max_batch: int = 32, max_wait_ms: float = 2.0,
             tau: float = 0.92, index: str = "flat",
             static_rows: int = 0, nprobe: int = 8,
             dyn_index: str = "flat", seg_rows: int = 4096,
             compact_every: int = 4, shards: int = 1,
             l1_capacity: int = 0, volatile_bypass: bool = False,
             ttl_volatile: int = 0, ttl_stable: int = 0,
             adaptive: bool = False, adapt_every: int = 256,
             adapt_window: int = 1024, rewrite: bool = False,
             rewrite_rate: float = 1.0) -> dict:
    """Live router-fronted serving demo: the batched serving path under
    concurrent client load, with per-tier hit and latency telemetry.
    ``index='ivf'`` swaps the static lookup for the quantized ANN index
    (padding the tier to ``static_rows`` synthetic entries first);
    ``dyn_index='segmented'`` serves dynamic-tier lookups through the
    incremental tail+segments index (DESIGN.md §12); ``shards > 1``
    serves both tiers row-sharded over a 'model' mesh of that many
    (forced host) devices with shard-routed writes (DESIGN.md §13) —
    decisions identical to single-device."""
    import threading

    import numpy as np

    from repro.core.judge import OracleJudge, template_rewriter
    from repro.core.policy import KritesPolicy
    from repro.core.tiers import CacheConfig
    from repro.embedding.embedder import Embedder
    from repro.launch.mesh import make_shard_mesh
    from repro.launch.serve import build_demo_tier, build_dyn_index
    from repro.serving.router import CacheRouter

    mesh = make_shard_mesh(shards) if shards > 1 else None
    if mesh is not None and dyn_index == "segmented":
        print("note: dyn_index='segmented' is single-device only; "
              "shards>1 uses the row-sharded masked scan (DESIGN.md §13)")
        dyn_index = "flat"
    embed = Embedder(d_out=64)
    intents = [f"how do i {v} my {n}" for v in
               ("fix", "update", "reset", "clean", "sell", "charge")
               for n in ("bike", "laptop", "router", "garden", "phone")]
    tier, answers, texts, idx_obj = build_demo_tier(
        np.asarray(embed.batch(intents)),
        [f"[curated] {p}" for p in intents],
        static_rows=static_rows, index=index, nprobe=nprobe,
        mesh=mesh, texts=intents)

    freshness = None
    if volatile_bypass or ttl_volatile or ttl_stable:
        from repro.core.freshness import FreshnessPolicy
        freshness = FreshnessPolicy(volatile_bypass=volatile_bypass,
                                    ttl_volatile=ttl_volatile,
                                    ttl_stable=ttl_stable,
                                    ttl_unknown=ttl_stable)
    cfg = CacheConfig(tau, tau, sigma_min=0.3, capacity=1024,
                      l1=bool(l1_capacity),
                      volatile_bypass=volatile_bypass,
                      ttl_volatile=ttl_volatile, ttl_stable=ttl_stable,
                      rewrite=rewrite, rewrite_rate=rewrite_rate)
    adaptive_ctl = None
    if adaptive:
        from repro.core.adaptive import (AdaptiveController,
                                         AdaptiveParams)
        adaptive_ctl = AdaptiveController(
            cfg, d=64, params=AdaptiveParams(window=adapt_window,
                                             adapt_every=adapt_every))
    policy = KritesPolicy(
        cfg, tier, answers,
        embed, backend_fn=lambda p: f"generated({p})",
        judge_fn=OracleJudge(
            freshness=freshness,
            rewritable=(lambda qc, hc, qt, ht: True)
            if rewrite else None),
        d=64,
        backend_batch_fn=lambda ps: [f"generated({p})" for p in ps],
        index=idx_obj, static_texts=texts, mesh=mesh,
        rewriter=template_rewriter if rewrite else None,
        l1=l1_capacity or None, freshness=freshness,
        adaptive=adaptive_ctl,
        dyn_index=build_dyn_index(dyn_index, cfg.capacity, 64,
                                  seg_rows=seg_rows,
                                  compact_every=compact_every))
    router = CacheRouter(policy, max_batch=max_batch,
                         max_wait_ms=max_wait_ms)

    prefixes = ["", "hey ", "um, ", "please, ", "quick q: ", "so, "]
    rng = np.random.default_rng(0)
    reqs = [(prefixes[int(rng.integers(len(prefixes)))] + intents[c], c)
            for c in rng.integers(0, len(intents), n_requests)]

    t0 = time.time()

    def client(k):
        for p, c in reqs[k::n_clients]:
            router.submit(p, meta={"cls": int(c)})

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall = time.time() - t0     # serving throughput only — the async
    policy.pool.drain()         # verification drain is off-path

    s = router.stats()
    s["requests_per_s"] = round(n_requests / wall, 1)
    print(f"[OK] live router: {n_requests} reqs from {n_clients} clients "
          f"in {wall:.2f}s ({s['requests_per_s']} req/s)")
    for k, v in s.items():
        print(f"  {k:22s} {v}")
    router.stop()
    policy.pool.stop()
    return s


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--live", action="store_true",
                    help="run the router-fronted live serving demo "
                         "instead of the dry-run lowering")
    ap.add_argument("--requests", type=int, default=800)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--index", choices=["flat", "ivf"], default="flat",
                    help="static-tier lookup strategy for --live "
                         "(DESIGN.md §11)")
    ap.add_argument("--static-rows", type=int, default=0,
                    help="pad the live demo's curated tier to this many "
                         "rows before building the index")
    ap.add_argument("--nprobe", type=int, default=8)
    ap.add_argument("--dyn-index", choices=["flat", "segmented"],
                    default="flat",
                    help="dynamic-tier lookup strategy for --live "
                         "(DESIGN.md §12)")
    ap.add_argument("--seg-rows", type=int, default=4096,
                    help="segmented dynamic index tail capacity")
    ap.add_argument("--compact-every", type=int, default=4,
                    help="merge sealed segments whenever this many "
                         "have accumulated")
    ap.add_argument("--shards", type=int, default=1,
                    help="serve --live through the row-sharded mesh "
                         "path over this many host devices "
                         "(DESIGN.md §13); 1 = single-device")
    ap.add_argument("--l1-capacity", type=int, default=0,
                    help="L1 exact-match front tier size for --live "
                         "(DESIGN.md §16); 0 = off")
    ap.add_argument("--volatile-bypass", action="store_true",
                    help="serve freshness-volatile prompts cache-free "
                         "in --live (DESIGN.md §16)")
    ap.add_argument("--ttl-volatile", type=int, default=0,
                    help="per-entry cache lifetime for volatile "
                         "content in --live (ticks; 0 = never)")
    ap.add_argument("--ttl-stable", type=int, default=0,
                    help="per-entry cache lifetime for stable/unknown "
                         "content in --live (ticks; 0 = never)")
    ap.add_argument("--adaptive", action="store_true",
                    help="attach the online threshold controller to "
                         "--live serving (DESIGN.md §17)")
    ap.add_argument("--adapt-every", type=int, default=256,
                    help="recorded requests between shadow sweeps")
    ap.add_argument("--adapt-window", type=int, default=1024,
                    help="controller request-window ring size")
    ap.add_argument("--rewrite", action="store_true",
                    help="three-outcome judge pipeline in --live "
                         "(DESIGN.md §18): would-reject grey-zone "
                         "pairs are rewritten and promoted keyed to "
                         "the new prompt")
    ap.add_argument("--rewrite-rate", type=float, default=1.0,
                    help="rewrite token-bucket refill per judged task")
    a = ap.parse_args()
    if a.live:
        run_live(n_requests=a.requests, n_clients=a.clients,
                 max_batch=a.max_batch, index=a.index,
                 static_rows=a.static_rows, nprobe=a.nprobe,
                 dyn_index=a.dyn_index, seg_rows=a.seg_rows,
                 compact_every=a.compact_every, shards=a.shards,
                 l1_capacity=a.l1_capacity,
                 volatile_bypass=a.volatile_bypass,
                 ttl_volatile=a.ttl_volatile, ttl_stable=a.ttl_stable,
                 adaptive=a.adaptive, adapt_every=a.adapt_every,
                 adapt_window=a.adapt_window, rewrite=a.rewrite,
                 rewrite_rate=a.rewrite_rate)
    else:
        run(multi_pod=False)
        run(multi_pod=True)
