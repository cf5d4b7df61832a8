"""Operations and bytes the window's work required, from shapes alone:
what the algorithm needs, not what an implementation moves.

- lookups: per batch, the static tier and the dynamic tier each read
  once; 2*d operations per score.
- embedder: 2*(1024*256 + 256*64) operations per embedded row.

The backend's count depends on its architecture, so it comes with the
configuration's reference module (``backend_ops``).
"""
from __future__ import annotations

F32 = 4


def lookup(lk: dict) -> tuple:
    """(operations, bytes) of the window's lookups."""
    N, d, C = lk["static_rows"], lk["d"], lk["capacity"]
    ops = byts = 0.0
    for B in lk["batches"]:
        ops += 2.0 * B * (N + C) * d
        byts += (N + C) * d * F32 + C + B * d * F32
    return ops, byts


def embed(rows: int, n_features: int = 1024, d: int = 64) -> float:
    h = 4 * d
    return 2.0 * rows * (n_features * h + h * d)


def roofline_s(ops: float, byts: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations
    at the bfloat16 peak and the bytes at the memory bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
