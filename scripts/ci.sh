#!/usr/bin/env bash
# Tier-1 CI: full test suite (with per-test timeout) + benchmark smokes.
#
#     bash scripts/ci.sh
#
# Mirrors what the README documents: the repo must pass
# `PYTHONPATH=src python -m pytest -x -q`, and the multi-config sweep
# path must run a 16-config grid (DESIGN.md §10). The --timeout flag is honored by pytest-timeout when installed
# and by the SIGALRM fallback in tests/conftest.py otherwise, so one
# wedged test cannot hang CI silently.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
# tier-1 CI runs on the CPU (the multi-device smokes get host devices)
export JAX_PLATFORMS=cpu

echo "== tier-1 tests (per-test timeout 300s) =="
python -m pytest -x -q --timeout=300

echo "== sweep smoke (16-config grid, one dispatch) =="
python -m benchmarks.sweep --configs 16 --no-sequential

echo "== ivf smoke (build + scan + decision-agreement) =="
python -m benchmarks.ann_index --smoke

echo "== segmented dynamic-index smoke (churn + agreement-1.0 gate) =="
python -m benchmarks.dyn_index --smoke

echo "== sharded serving smoke (forced host-device mesh, agreement 1.0) =="
# the multi-device subprocess differential (tests/test_sharded_serve.py)
# runs as part of the tier-1 suite above; this smoke adds the
# benchmark-level serving differential with its agreement-1.0 gate
python -m benchmarks.sharded_serve --smoke

echo "== fused serve smoke (single-pass pipeline, agreement-1.0 gate) =="
# the policy-level differential (tests/test_fused_serve_policy.py) runs
# in the tier-1 suite above; this smoke gates the fused lookup pair
# against the dispatched lookups — hard agreement == 1.0 at a
# full-coverage probe budget (DESIGN.md §15)
python -m benchmarks.fused_serve --smoke

echo "== live service smoke (load -> snapshot -> kill -> warm restart) =="
# the fault-injection matrix (tests/test_crash_recovery.py) runs in the
# tier-1 suite above; this smoke drives the real --serve-stdio process
# over the JSON-lines protocol at a target QPS, snapshots mid-load and
# asserts the restart comes back warm (DESIGN.md §14)
python -m benchmarks.load_service --smoke

echo "== L1 + freshness smoke (bypass -> zero stale, agreement 1.0) =="
# the property/live-policy suite (tests/test_l1_freshness.py) runs in
# tier-1 above; this smoke gates the serving invariants on real
# embedder traffic: volatile bypass => zero stale serves, the L1 front
# tier decision-invisible on non-repeat traffic, and pure repeats
# costing zero embedder calls (DESIGN.md §16)
python -m benchmarks.l1_freshness --smoke

echo "== rewrite verdict smoke (first-seen agreement 1.0, repeats-only) =="
# the three-outcome differentials (tests/test_ref_differential.py,
# tests/test_rewrite_durability.py) run in tier-1 above; this smoke
# gates the rewrite critical-path invariant on a constructed workload:
# (i) first-seen prompt decisions bit-identical to the rewrite-off
# twin (agreement 1.0 — rewriting never changes what the triggering
# request is served), and (ii) rewritten entries served only to later
# repeats (DESIGN.md §18)
python -m benchmarks.greyzone_roi --smoke

echo "== adaptive thresholds smoke (drift recovery + frozen identity) =="
# the controller differentials (tests/test_adaptive.py) run in tier-1
# above; this smoke drives the full Krites pipeline through a traffic
# drift and gates: adaptive post-drift hit rate >= pinned at
# equal-or-lower error, and a frozen controller changing zero
# critical-path decisions (DESIGN.md §17)
python -m benchmarks.adaptive_thresholds --smoke

echo "== CI OK =="
