"""Operation and byte counts from shapes, against hand-computed ones."""
import json
from pathlib import Path

import pytest

from bench import reference, work

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "krites-flat.json"

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_flat_lookup():
    lk = {"batches": [8, 3], "static_rows": 1 << 20,
          "d": 64, "capacity": 4096}
    ops, byts = work.lookup(lk)
    # static: 2*B*N*d, dynamic: 2*B*C*d, per batch
    assert ops == 2 * 11 * (1 << 20) * 64 + 2 * 11 * 4096 * 64
    # the tier read once per batch (fp32), the dynamic tier and its
    # valid bytes, the queries
    assert byts == 2 * ((1 << 20) * 64 * 4 + 4096 * 64 * 4 + 4096) \
        + 11 * 64 * 4
    # memory-bound: 2 x 256 MiB at 819 GB/s
    assert work.roofline_s(ops, byts, PEAKS) == pytest.approx(
        byts / 819e9)


def test_backend_and_embedder():
    be = {"hidden_size": 2048, "num_attention_heads": 16,
          "num_key_value_heads": 8, "head_dim": 128,
          "intermediate_size": 6144, "num_hidden_layers": 28,
          "vocab_size": 151936}
    per_layer = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 \
        + 3 * 2048 * 6144
    assert per_layer == 50_331_648
    bw = {"rows": 8, "prefill_tokens": 8 * 64, "decode_tokens": 8 * 7,
          "attn_pairs": 1000}
    ops = reference.backend_ops(be, bw)
    want = 2 * 28 * per_layer * (512 + 56) + 2 * 2048 * 151936 * (8 + 56) \
        + 4 * 28 * 16 * 128 * 1000
    assert ops == want
    assert work.embed(10) == 2 * 10 * (1024 * 256 + 256 * 64)


def test_backend_ops_of_krites_flat_is_pinned():
    """The count ``step_mfu`` takes for the ``krites-flat`` backend, as
    the harness has always counted it."""
    be = json.loads(CONFIG.read_text())["deployment"]["backend"]
    bw = {"rows": 83, "prefill_tokens": 5312, "decode_tokens": 576,
          "attn_pairs": 211848}
    assert reference.backend_ops(be, bw) == 17054461853696.0
