from repro.launch.jax_setup import force_cpu_devices

# lowers against fake 512-device meshes, which the CPU backend provides
# with JAX_PLATFORMS=cpu (set before jax starts)
force_cpu_devices(512)

# --- everything below may import jax -------------------------------------
import argparse        # noqa: E402
import json            # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402
from pathlib import Path  # noqa: E402

import jax             # noqa: E402

from repro.analysis import roofline as rl               # noqa: E402
from repro.analysis.hlo_parse import (collective_bytes,  # noqa: E402
                                      count_collectives)
from repro.configs import ARCHS, all_cells, get_arch, shapes_for  # noqa: E402
from repro.launch.mesh import make_production_mesh      # noqa: E402
from repro.launch.workloads import build_workload       # noqa: E402

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile_costs(wl, mesh) -> dict:
    """Lower+compile one workload; return cost/collective/memory numbers."""
    jitted = jax.jit(wl.fn, in_shardings=wl.in_shardings,
                     out_shardings=wl.out_shardings,
                     donate_argnums=wl.donate_argnums)
    lowered = jitted.lower(*wl.args)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    ca = compiled.cost_analysis()
    mem = rl.memory_summary(compiled)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": collective_bytes(hlo),
        "coll_counts": count_collectives(hlo),
        "mem": mem,
        "hlo": hlo,
    }


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             save_hlo: bool = False) -> dict:
    """Compile the full cell (proves the 512-chip sharding) and, for LM
    archs, two unrolled analysis variants (1- and 2-layer) to correct
    XLA's while-loop cost undercount: cost(L) = fixed + L*per_layer.
    (GNN/recsys workloads are loop-free, so cost_analysis is exact.)
    """
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape}__{mesh_name}"
    out_path = out_dir / f"{cell}.json"
    t0 = time.time()
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "ok": False}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        wl = build_workload(arch, shape, mesh)
        cfg = get_arch(arch)
        is_lm = hasattr(cfg, "n_layers") and hasattr(cfg, "vocab_size")
        with mesh:
            full = _compile_costs(wl, mesh)
            t_compile = time.time() - t0
            mem = dict(full["mem"])
            args_b = mem.get("argument_size_in_bytes", 0.0)
            out_b = mem.get("output_size_in_bytes", 0.0)
            alias_b = mem.get("alias_size_in_bytes", 0.0)
            temp_b = mem.get("temp_size_in_bytes", 0.0)
            residuals = wl.residual_bytes_per_layer * wl.n_loop_layers
            flops = full["flops"]
            coll_total = float(full["coll"].get("total", 0))
            coll_kinds = dict(full["coll"])
            corrected = False
            if is_lm:
                # XLA cost analysis counts while-loop bodies ONCE; lower
                # loop-free 1- and 2-layer variants and extrapolate
                # cost(L) = fixed + L*per_layer (verified experimentally,
                # see EXPERIMENTS.md §Dry-run methodology).
                wl1 = build_workload(arch, shape, mesh,
                                     n_layers_override=1, unroll=True)
                wl2 = build_workload(arch, shape, mesh,
                                     n_layers_override=2, unroll=True)
                c1 = _compile_costs(wl1, mesh)
                c2 = _compile_costs(wl2, mesh)
                L = cfg.n_layers

                def extrap(a, b):
                    per_layer = max(b - a, 0.0)
                    fixed = max(a - per_layer, 0.0)
                    return fixed + L * per_layer
                flops = extrap(c1["flops"], c2["flops"])
                coll_total = extrap(
                    float(c1["coll"].get("total", 0)),
                    float(c2["coll"].get("total", 0)))
                coll_kinds = {
                    k: extrap(float(c1["coll"].get(k, 0)),
                              float(c2["coll"].get(k, 0)))
                    for k in set(c1["coll"]) | set(c2["coll"])}
                # per-layer transient footprint (upper bound: CPU buffer
                # assignment does not reuse across layers)
                t1 = c1["mem"].get("temp_size_in_bytes", 0.0)
                t2 = c2["mem"].get("temp_size_in_bytes", 0.0)
                transient_layer = max(t2 - t1, 0.0)
                mem["transient_per_layer_est"] = transient_layer
                mem["residual_bytes"] = residuals
                mem["peak_bytes_est"] = (args_b + residuals
                                         + transient_layer
                                         + max(out_b - alias_b, 0.0))
                # HBM traffic model: read args + write outputs + residual
                # save/restore. Transients stay in VMEM on TPU (the jnp
                # attention/MoE paths are written flash-style).
                byts = args_b + out_b + 2.0 * residuals
                corrected = True
            else:
                # loop-free: cost_analysis flops are exact; HBM traffic =
                # buffers (temps here are real HBM-resident gathers etc.)
                byts = args_b + out_b + temp_b
                mem["peak_bytes_est"] = (args_b + temp_b
                                         + max(out_b - alias_b, 0.0))

            roof = rl.Roofline(
                name=wl.name, chips=int(mesh.devices.size),
                hlo_flops=flops, hlo_bytes=byts, coll_bytes=coll_total,
                model_flops=wl.model_flops).finalize()
            rec.update({
                "ok": True,
                "compile_s": round(t_compile, 1),
                "corrected_by_unrolled_variants": corrected,
                "raw_cost_analysis": {"flops": full["flops"],
                                      "bytes": full["bytes"]},
                "memory": mem,
                "bytes_per_device": mem.get("peak_bytes_est"),
                "collectives": full["coll_counts"],
                "collective_bytes": coll_kinds,
                "roofline": roof.to_dict(),
            })
            print(f"[OK] {cell}: compile={t_compile:.0f}s "
                  f"bound={roof.bound} step={roof.step_s*1e3:.2f}ms "
                  f"frac={roof.roofline_frac:.3f} "
                  f"mem/dev={mem.get('peak_bytes_est', 0)/2**30:.2f}GiB")
            if save_hlo:
                (out_dir / f"{cell}.hlo.txt").write_text(full["hlo"])
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {cell}: {rec['error'].splitlines()[0][:200]}")
    rec["wall_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None,
                    help="arch id (default: all)")
    ap.add_argument("--shape", default=None,
                    help="shape name (default: all for the arch)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells whose JSON already reports ok")
    args = ap.parse_args()

    out_dir = Path(args.out)
    cells = []
    for a, s in all_cells():
        if args.arch and a != args.arch:
            continue
        if args.shape and s != args.shape:
            continue
        cells.append((a, s))

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    n_ok = n_fail = n_skip = 0
    for a, s in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            jpath = out_dir / f"{a}__{s}__{mesh_name}.json"
            if args.skip_done and jpath.exists():
                try:
                    if json.loads(jpath.read_text()).get("ok"):
                        n_skip += 1
                        continue
                except Exception:
                    pass
            rec = run_cell(a, s, mp, out_dir, save_hlo=args.save_hlo)
            n_ok += bool(rec.get("ok"))
            n_fail += not rec.get("ok")
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed, {n_skip} skipped "
          f"-> {out_dir}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
