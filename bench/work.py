"""Operations and bytes the window's work required, from shapes alone:
what the algorithm needs, not what an implementation moves.

- lookups: per batch, the static tier and the dynamic tier each read
  once; 2*d operations per score.
- embedder: 2*(1024*256 + 256*64) operations per embedded row.
- backend: 2 operations per weight per token through the layers,
  2*d*vocab per logits row, 4*heads*head_dim per (query, key) pair of
  attention.
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


def backend(be: dict, bw: dict) -> float:
    d, H, K = be["hidden_size"], be["num_attention_heads"], \
        be["num_key_value_heads"]
    hd, ff, L, V = be["head_dim"], be["intermediate_size"], \
        be["num_hidden_layers"], be["vocab_size"]
    per_token = L * (d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff)
    tokens = bw["prefill_tokens"] + bw["decode_tokens"]
    logits = bw["rows"] + bw["decode_tokens"]
    return 2.0 * per_token * tokens + 2.0 * d * V * logits \
        + 4.0 * L * H * hd * bw["attn_pairs"]


def roofline_s(ops: float, byts: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations
    at the bfloat16 peak and the bytes at the memory bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
