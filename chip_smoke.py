#!/usr/bin/env python3
"""Quickest proof that the Krites serve path runs on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded path on four chips

One chip: four phases, one per lookup path the launcher offers — flat
(the default), ``--index ivf``, ``--dyn-index segmented`` and
``--fused``. Each phase builds its service through
``launch/serve.py``'s ``build_service`` at the repo's real deployment
size (a 1,048,576-row curated static tier of width 64, dynamic capacity
4096, the LLM backend at qwen3-1.7b's published widths with weights
drawn from ``--seed``) and drives it in-process the way
``--serve-stdio`` does: ``serve_batch`` over coalesced requests, drawn
like the launcher's demo loop. A first pass sends paraphrases into the
grey zone, the judge pool is drained, and a second pass must hit the
promoted entries in the dynamic tier.

Four chips (``--chips 4``): the ``--shards 4`` path, flat and
``--index ivf`` (``ShardedIVFIndex``), against the one-chip flat and IVF
runs on the same requests in the same process; decisions must be
identical. No other phase runs.

Before each batch the service's own lookups are checked against a
float32 numpy top-1 over the same embeddings and tier: the top-1 row
and its score (to 1e-5) must agree, and the static decision must match
the reference's, except where two candidates — or a score and the
threshold — lie within 1e-5. Each phase prints one JSON line; the last
line is ``{"ok": true, "device": {...}}``. A failed phase, any
disagreement, or a device that is not a TPU exits non-zero without that
line. This process is the only one that touches JAX.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-5          # score agreement, and the tie / threshold margin
BATCH = 32          # requests per coalesced serve_batch call
SEED = 0            # the backend's random weights
SEG_ROWS = 64       # segmented phase: tail rows per seal, few enough
                    # that the second pass looks up sealed segments


class Failed(SystemExit):
    """A check failed: exits non-zero with the message."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def ref_top1(V, E, valid=None, chunk: int = 131072):
    """float32 numpy top-1 of V (B, d) over the rows of E (N, d):
    (score, lowest index of the max, gap to the runner-up). Rows
    outside ``valid`` score -inf."""
    import numpy as np
    best = np.full(V.shape[0], -np.inf, np.float32)
    second = best.copy()
    arg = np.zeros(V.shape[0], np.int64)
    for lo in range(0, E.shape[0], chunk):
        S = V @ E[lo:lo + chunk].T
        if valid is not None:
            S[:, ~valid[lo:lo + chunk]] = -np.inf
        top2 = np.partition(S, S.shape[1] - 2, axis=1)[:, -2:] \
            if S.shape[1] > 1 else np.concatenate([S, S], 1)
        a = S.argmax(1)
        s1, s2 = top2.max(1), top2.min(1)
        take = s1 > best
        second = np.where(take, np.maximum(best, s2),
                          np.maximum(second, s1))
        arg = np.where(take, lo + a, arg)
        best = np.where(take, s1, best)
    with np.errstate(invalid="ignore"):       # -inf - -inf: empty tier
        return best, arg, best - second


def agree(s, i, ref):
    """Per-row agreement of a (score, index) top-1 with the reference."""
    import numpy as np
    rs, ri, gap = ref
    both_empty = np.isneginf(rs) & np.isneginf(s)
    with np.errstate(invalid="ignore"):
        same_idx = (i == ri) | (gap <= TOL)
        err = np.where(both_empty, 0.0, np.abs(s - rs))
    return (same_idx & (err <= TOL)) | both_empty, \
        float(np.max(err, initial=0.0))


def serve_checked(svc, reqs, tau):
    """Serve ``reqs`` in coalesced batches, checking each batch's
    lookups against the numpy reference first. Returns (results,
    agreeing rows, rows checked, max score error)."""
    import numpy as np
    pol = svc.policy
    E_static = np.asarray(pol.static.emb, np.float32)
    results, ok, n, err = [], 0, 0, 0.0
    for lo in range(0, len(reqs), BATCH):
        chunk = reqs[lo:lo + BATCH]
        prompts = [p for p, _ in chunk]
        V = pol._embed_batch(prompts)
        (ss, hi, sd, j), snap = pol.lookup_batch(V)
        Vn = np.asarray(V, np.float32)
        rs = ref_top1(Vn, E_static)
        rd = ref_top1(Vn, np.asarray(snap.emb, np.float32),
                      np.asarray(snap.valid))
        a_s, e_s = agree(ss, hi, rs)
        a_d, e_d = agree(sd, j, rd)
        out = pol.serve_batch(prompts, [{"cls": c} for _, c in chunk])
        # every request is semantic here (no L1, no bypass), so its
        # static decision is exactly "static score >= tau"
        hit = np.array([r.served_by == "static" for r in out])
        a_dec = (hit == (rs[0] >= tau)) | (np.abs(rs[0] - tau) <= TOL)
        rows = a_s & a_d & a_dec
        ok += int(rows.sum())
        n += len(rows)
        err = max(err, e_s, e_d)
        results.extend(out)
        pol.pool.drain()          # promotions land between batches
    return results, ok, n, err


def run_phase(name, svc_argv, reqs, *, engine=None, ivf=None,
              device=None):
    """Build one service, serve ``reqs`` twice, check it and stop it.
    Returns its policy and both passes' results. Its compiles are
    ``repro.tracing``'s count (a persistent-cache load is not one)."""
    import collections

    from repro import tracing
    from repro.launch.serve import build_parser, build_service
    args = build_parser().parse_args(svc_argv)
    n0, s0 = tracing.compiles()
    t0 = time.monotonic()
    svc = build_service(args, engine=engine, ivf=ivf)
    build_s = time.monotonic() - t0
    t0 = time.monotonic()
    r1, ok1, n1, e1 = serve_checked(svc, reqs, args.tau)
    r2, ok2, n2, e2 = serve_checked(svc, reqs, args.tau)
    serve_s = time.monotonic() - t0
    n1c, s1c = tracing.compiles()
    st = svc.policy.stats()
    promoted_hits = sum(r.served_by == "dynamic" and r.static_origin
                        for r in r2)
    line = {
        "phase": name,
        "argv": " ".join(svc_argv),
        "compiles": n1c - n0,
        "compile_s": round(s1c - s0, 3),
        "engine_compiles": svc.engine.stats.compiles,
        "build_s": round(build_s, 3),
        "serve_s": round(serve_s, 3),
        "requests": len(r1) + len(r2),
        "served_by": dict(collections.Counter(r.served_by
                                              for r in r1 + r2)),
        "judged": st["judged"],
        "approved": st["approved"],
        "pass2_promoted_dynamic_hits": promoted_hits,
        "peak_bytes_in_use": (device.memory_stats() or {}).get(
            "peak_bytes_in_use") if device is not None else None,
        "agreement": (ok1 + ok2) / (n1 + n2),
        "max_score_err": max(e1, e2),
    }
    sh = svc.policy.shard_stats()
    if sh is not None:
        line["shard_occupancy"] = sh["shard_occupancy"]
    if svc.policy.dyn_index is not None:
        line["dyn_index"] = svc.policy.describe_dyn_index()
    print(json.dumps(line), flush=True)
    for key in ("judged", "approved", "pass2_promoted_dynamic_hits"):
        if not line[key] > 0:
            raise Failed(f"{name}: {key} = {line[key]}")
    if line["agreement"] != 1.0:
        raise Failed(f"{name}: agreement {line['agreement']} with the "
                     f"numpy reference")
    svc.stop()
    return svc.policy, r1 + r2


def base_argv(opts):
    return ["--arch", opts.arch, "--seed", str(SEED),
            "--static-rows", str(opts.static_rows),
            "--capacity", str(opts.capacity),
            "--nprobe", str(opts.nprobe)]


def run_one_chip(opts, device=None):
    """flat, ivf, segmented and fused phases; the ivf and fused phases
    share one IVF build."""
    from repro.launch.serve import build_engine, build_parser, demo_requests
    base = base_argv(opts)
    engine = build_engine(build_parser().parse_args(base))
    reqs = demo_requests(opts.requests)
    run_phase("flat", base, reqs, engine=engine, device=device)
    pol, _ = run_phase("ivf", base + ["--index", "ivf"], reqs,
                       engine=engine, device=device)
    run_phase("segmented", base + ["--dyn-index", "segmented",
                                   "--seg-rows", str(SEG_ROWS)],
              reqs, engine=engine, device=device)
    run_phase("fused", base + ["--fused"], reqs, engine=engine,
              ivf=pol.index.ivf, device=device)


def run_four_chips(opts, device=None):
    """``--shards 4`` flat and IVF against one-chip flat and IVF on the
    same requests: decisions must be identical."""
    from repro.launch.serve import build_engine, build_parser, demo_requests
    base = base_argv(opts)
    engine = build_engine(build_parser().parse_args(base))
    reqs = demo_requests(opts.requests)
    for index in ("flat", "ivf"):
        runs = {}
        for shards in (1, 4):
            argv = base + ["--index", index, "--shards", str(shards)]
            _, res = run_phase(f"{index}-shards{shards}", argv, reqs,
                               engine=engine, device=device)
            runs[shards] = [(r.served_by, bool(r.static_origin))
                            for r in res]
        diff = sum(a != b for a, b in zip(runs[1], runs[4]))
        print(json.dumps({"compare": index, "requests": len(runs[1]),
                          "decisions_differing": diff}), flush=True)
        if diff:
            raise Failed(f"{index}: {diff} decisions differ between "
                         f"--shards 4 and one chip")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its one-chip "
                         "comparison")
    # the sizes below are the real deployment; tests shrink them
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--static-rows", type=int, default=1 << 20)
    ap.add_argument("--capacity", type=int, default=4096)
    ap.add_argument("--nprobe", type=int, default=512,
                    help="IVF clusters probed per query (ivf and fused "
                         "phases)")
    ap.add_argument("--requests", type=int, default=256)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    opts = parse(argv)
    from repro.launch.jax_setup import enable_compile_cache
    enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Failed(f"no TPU found: JAX platform is "
                     f"{devs[0].platform!r}")
    if len(devs) < opts.chips:
        raise Failed(f"--chips {opts.chips} but JAX sees {len(devs)}")
    run = run_four_chips if opts.chips == 4 else run_one_chip
    run(opts, device=devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
