"""Fused IVF cluster-scan Pallas TPU kernel.

The ANN static-tier lookup hot path (DESIGN.md §11): queries have
already been scored against the K cluster centroids and the top-nprobe
cluster ids per query are handed in as a *scalar-prefetch* argument, so
the BlockSpec index maps can DMA exactly the probed clusters'
quantized codes HBM->VMEM — nothing else of the corpus is touched.

Grid: (B, nprobe) — one step per (query, probed cluster); the probe
axis is innermost. Per step the kernel dequantizes one cluster's int8
codes ((cap, d) block), scores them against the resident query row on
the MXU, and folds the cluster's rows into a running top-C candidate
list carried in VMEM scratch (the online-top-k idiom shared with
``kernels/simsearch``). Candidate ids are *global row ids* (from the
packed layout's ``row_ids``), so the merge's min-index tie-break makes
the output ordering identical to the ``ref.py`` oracle's
(score desc, global id asc); padding slots (row id -1) are masked to
NEG and flushed back as id -1.

Mosaic requires each block's last two dims to be divisible by (8, 128)
or to equal the array's. The per-query and per-cluster rows therefore
travel with a unit middle axis — queries ``(B, 1, d)``, scales and ids
``(K, 1, cap)``, outputs ``(B, 1, C)`` — so every block's last two dims
equal the array's; the wrapper adds and drops that axis, and its
contract stays ``(B, d)`` in, ``(B, C)`` out.

A (1, d) query block underuses the MXU's sublane dimension; batching
queries that probe the same cluster (cluster-grouped dispatch) is the
known follow-up — the layout and scalar-prefetch machinery here
already support it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.simsearch.kernel import BIG_IDX, NEG, _merge_topk


def _kernel(cids_ref, q_ref, codes_ref, scales_ref, ids_ref,
            vals_ref, idx_ref, run_v, run_i, *, n_candidates, nprobe):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG)
        run_i[...] = jnp.full_like(run_i, BIG_IDX)

    q = q_ref[0].astype(jnp.float32)                         # (1, d)
    q = q * jax.lax.rsqrt(
        jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-18))
    c = codes_ref[0].astype(jnp.float32)                     # (cap, d)
    sims = jax.lax.dot_general(
        q, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (1, cap)
    sims = sims * scales_ref[0]
    ids = ids_ref[0]                                         # (1, cap)
    sims = jnp.where(ids < 0, NEG, sims)
    mids = jnp.where(ids < 0, BIG_IDX, ids)

    cand_v = jnp.concatenate([run_v[...], sims], axis=1)
    cand_i = jnp.concatenate([run_i[...], mids], axis=1)
    new_v, new_i = _merge_topk(cand_v, cand_i, n_candidates)
    run_v[...] = new_v
    run_i[...] = new_i

    @pl.when(p == nprobe - 1)
    def _done():
        vals_ref[0] = run_v[...]
        # absent candidates (still NEG) flush as id -1, like the oracle;
        # no real cosine can reach NEG so the test is unambiguous
        idx_ref[0] = jnp.where(run_v[...] == NEG, -1, run_i[...])


@functools.partial(jax.jit,
                   static_argnames=("n_candidates", "interpret"))
def ivf_scan_kernel(queries: jax.Array, cids: jax.Array,
                    codes: jax.Array, scales: jax.Array,
                    row_ids: jax.Array, n_candidates: int = 32,
                    interpret: bool = False):
    """Scan the prefetched clusters. queries (B, d); cids (B, nprobe)
    int32; codes (K, cap, d) int8; scales (K, cap); row_ids (K, cap).

    Returns (approx scores (B, C) fp32, global row ids (B, C) int32).
    """
    B, d = queries.shape
    _, nprobe = cids.shape
    K, cap, _ = codes.shape
    C = n_candidates

    kern = functools.partial(_kernel, n_candidates=C, nprobe=nprobe)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nprobe),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, p, cids: (b, 0, 0)),
            pl.BlockSpec((1, cap, d),
                         lambda b, p, cids: (cids[b, p], 0, 0)),
            pl.BlockSpec((1, 1, cap),
                         lambda b, p, cids: (cids[b, p], 0, 0)),
            pl.BlockSpec((1, 1, cap),
                         lambda b, p, cids: (cids[b, p], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C), lambda b, p, cids: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b, p, cids: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, C), jnp.float32),
            pltpu.VMEM((1, C), jnp.int32),
        ],
    )
    vals, idx = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, C), jnp.int32),
        ],
        interpret=interpret,
    )(cids.astype(jnp.int32), queries[:, None, :], codes,
      scales[:, None, :], row_ids[:, None, :])
    return vals[:, 0], idx[:, 0]
