"""Benchmark harness entry: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale small|full] [--only X]

Registered modules (see each module's docstring for what it reproduces):
``table1``, ``fig2``, ``greyzone_roi``, ``latency_async``,
``verifier_fidelity``, ``kernels``, ``sweep``, ``ann_index``,
``dyn_index``, ``sharded_serve``, ``load_service``, ``fused_serve``,
``l1_freshness``, ``adaptive_thresholds``.

Prints ``name,us_per_call,derived`` CSV rows (derived = remaining fields
as compact JSON) and writes results/benchmarks.json. A module that
raises gets an ``<module>/ERROR`` row and the harness exits non-zero.
With ``JAX_PLATFORMS=cpu`` the CPU backend is given 8 host devices, the
mesh ``sharded_serve`` sweeps.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parent.parent / "results"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "full"], default="small")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names")
    args = ap.parse_args()

    from repro.launch.jax_setup import force_cpu_devices
    force_cpu_devices(8)
    from benchmarks import (adaptive_thresholds, ann_index, dyn_index,
                            fig2, fused_serve, greyzone_roi,
                            kernels_bench, l1_freshness, latency_async,
                            load_service, sharded_serve, sweep, table1,
                            verifier_fidelity)
    modules = {
        "table1": table1, "fig2": fig2, "greyzone_roi": greyzone_roi,
        "latency_async": latency_async,
        "verifier_fidelity": verifier_fidelity,
        "kernels": kernels_bench,
        "sweep": sweep,
        "ann_index": ann_index,
        "dyn_index": dyn_index,
        "sharded_serve": sharded_serve,
        "load_service": load_service,
        "fused_serve": fused_serve,
        "l1_freshness": l1_freshness,
        "adaptive_thresholds": adaptive_thresholds,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    # results/ is gitignored, so it does not exist on fresh clones;
    # create it up front (not just before the final write) so modules
    # that emit their own artifacts can rely on it too
    RESULTS.mkdir(parents=True, exist_ok=True)

    print("name,us_per_call,derived")
    all_rows = []
    failed = []
    for mod_name, mod in modules.items():
        t0 = time.time()
        try:
            rows = mod.run(scale=args.scale)
        except Exception as e:  # noqa: BLE001 — reported, exits 1 below
            failed.append(mod_name)
            rows = [{"name": f"{mod_name}/ERROR", "us_per_call": -1,
                     "error": str(e)[:300]}]
        for r in rows:
            derived = {k: v for k, v in r.items()
                       if k not in ("name", "us_per_call")}
            print(f"{r['name']},{r.get('us_per_call', 0)},"
                  f"\"{json.dumps(derived)}\"")
        all_rows.extend(rows)

    (RESULTS / "benchmarks.json").write_text(json.dumps(all_rows, indent=1))
    if failed:
        raise SystemExit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
