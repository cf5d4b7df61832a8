"""What the parent and the chip process share: where things are, and how
a workload, its configuration and its traffic mix are found by name.
Standard library only (the parent never imports JAX)."""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE = BENCH / ".cache"            # traces, logs, JAX's compile cache
JAX_CACHE = CACHE / "jax"


def benchmark() -> dict:
    path = CHECKOUT / "BENCHMARK.json"
    if not path.is_file():
        raise SystemExit(f"no {path.name} at {CHECKOUT}")
    return json.loads(path.read_text())


def workload(name: str) -> dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            path = CHECKOUT / c["file"]
            if not path.is_file():
                raise SystemExit(f"configuration file {path} is missing")
            return json.loads(path.read_text())
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def reference(conf: dict):
    """The plain reference module that the configuration names under
    its ``reference`` key, loaded from that path in the checkout (its
    contract: ``bench/reference.py``)."""
    path = CHECKOUT / conf["reference"]
    if not path.is_file():
        raise SystemExit(f"reference module {path} is missing")
    return load(path, f"bench_reference_{path.stem}")


def load(path: Path, name: str):
    """The Python module at ``path``, loaded under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def in_len(prompt: str, max_len: int, max_new: int) -> int:
    """The prompt length the backend pads a prompt to: its UTF-8 bytes
    plus BOS and EOS, rounded up to a power of two (at least 16) and
    capped at ``max_len - max_new``."""
    n = max(len(prompt.encode()) + 2, 16)
    return min(1 << (n - 1).bit_length(), max_len - max_new)


def pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def quantile(values, q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (numpy's default method), so a p95 over n requests is the same
    number however the requests were grouped."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
