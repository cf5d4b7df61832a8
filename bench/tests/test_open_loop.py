"""The open-loop arithmetic: latency from the scheduled send, tails over
all requests, failed requests counted as waiting until given up."""
import numpy as np
import pytest

from bench import common
from bench.run import window_metrics


def test_quantile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 1001):
        xs = list(rng.exponential(1.0, n))
        for q in (0.5, 0.95, 0.99):
            assert common.quantile(xs, q) == pytest.approx(
                float(np.percentile(xs, 100 * q)))


def test_latency_is_from_the_schedule():
    # five requests due every 10 ms from t0 = 100; the service stalls and
    # answers all of them at 100.5
    window = [(0.01 * k, f"p{k}", 0) for k in range(5)]
    got = {k: (100.5, {"ok": True, "served_by": "static",
                       "static_origin": True}) for k in range(5)}
    m, served, hits, failed = window_metrics(window, got, 100.0, 101.0)
    lat = [500.0 - 10.0 * k for k in range(5)]
    assert m["p50_ms"] == pytest.approx(float(np.percentile(lat, 50)))
    assert m["p95_ms"] == pytest.approx(float(np.percentile(lat, 95)))
    assert served == {"static": 5} and hits == 5 and failed == 0
    assert m["curated_frac"] == 1.0


def test_misses_failures_and_curated_share():
    window = [(0.0, "a", 0), (0.1, "b", 0), (0.2, "c", 0), (0.3, "d", 0)]
    got = {0: (10.05, {"ok": True, "served_by": "backend"}),
           1: (10.2, {"ok": True, "served_by": "dynamic",
                      "static_origin": True}),
           2: (10.3, {"ok": True, "served_by": "dynamic",
                      "static_origin": False})}
    m, served, hits, failed = window_metrics(window, got, 10.0, 12.0)
    assert failed == 1 and hits == 2
    # the lost request waited from 10.3 until 12.0
    lat = [50.0, 100.0, 100.0, 1700.0]
    assert m["p95_ms"] == pytest.approx(float(np.percentile(lat, 95)))
    assert m["hit_p95_ms"] == pytest.approx(100.0)
    assert m["curated_frac"] == 0.25
