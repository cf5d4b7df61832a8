"""Fused cosine-similarity top-k Pallas TPU kernel.

The cache-lookup hot path: normalize queries once, stream corpus tiles
HBM->VMEM, score on the MXU, and carry a running top-k in VMEM scratch
across tiles (online top-k — the selection analogue of online softmax).
The (B, N) similarity matrix is never materialized in HBM.

Grid: (N // tile_n,) — one step per corpus tile.
Blocks: queries (B, d) resident; corpus tile (tile_n, d) streamed.
Scratch: running values (B, k_pad) fp32 + indices (B, k_pad) int32.

Top-k merge uses max-reduce + min-index tie-breaking (no gather/sort inside
the kernel — TPU-friendly elementwise/reduce ops only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.0                      # below any cosine similarity
BIG_IDX = 2**30


def _merge_topk(vals, idxs, k):
    """Select top-k (max value, min index on ties) from (B, M) candidates.

    Returns ((B, k) values, (B, k) indices). Pure elementwise/reduce ops.
    """
    out_v, out_i = [], []
    for _ in range(k):
        m = jnp.max(vals, axis=1, keepdims=True)                 # (B, 1)
        sel = vals >= m                                          # ties incl.
        pick = jnp.min(jnp.where(sel, idxs, BIG_IDX), axis=1,
                       keepdims=True)                            # (B, 1)
        out_v.append(m)
        out_i.append(pick)
        vals = jnp.where(idxs == pick, NEG, vals)
    return jnp.concatenate(out_v, 1), jnp.concatenate(out_i, 1)


def _kernel(q_ref, c_ref, vals_ref, idx_ref, run_v, run_i, *, k, tile_n,
            n_tiles, d):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG)
        run_i[...] = jnp.full_like(run_i, BIG_IDX)

    q = q_ref[...].astype(jnp.float32)                           # (B, d)
    c = c_ref[...].astype(jnp.float32)                           # (tile, d)
    qn = q * jax.lax.rsqrt(
        jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-18))
    cn = c * jax.lax.rsqrt(
        jnp.maximum(jnp.sum(c * c, -1, keepdims=True), 1e-18))
    # HIGHEST: at the default precision the MXU takes one bf16 pass
    # and a served score is off by ~1e-3 (measured on a v5e), which
    # moves threshold decisions; these scores are the exact ones
    sims = jax.lax.dot_general(
        qn, cn, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                      # (B, tile)

    gidx = t * tile_n + jax.lax.broadcasted_iota(
        jnp.int32, sims.shape, 1)
    cand_v = jnp.concatenate([run_v[...], sims], axis=1)
    cand_i = jnp.concatenate([run_i[...], gidx], axis=1)
    new_v, new_i = _merge_topk(cand_v, cand_i, k)
    run_v[...] = new_v
    run_i[...] = new_i

    @pl.when(t == n_tiles - 1)
    def _done():
        vals_ref[...] = run_v[...]
        idx_ref[...] = run_i[...]


@functools.partial(jax.jit, static_argnames=("k", "tile_n", "interpret"))
def simsearch(queries: jax.Array, corpus: jax.Array, k: int = 1,
              tile_n: int = 512, interpret: bool = False):
    """Fused cosine top-k. queries (B, d), corpus (N, d).

    N must be a multiple of tile_n (callers pad with zero rows; zero rows
    score 0.0 > NEG but are excluded by callers via masking — see ops.py).
    """
    B, d = queries.shape
    N, _ = corpus.shape
    assert N % tile_n == 0, (N, tile_n)
    n_tiles = N // tile_n

    kern = functools.partial(_kernel, k=k, tile_n=tile_n, n_tiles=n_tiles,
                             d=d)
    vals, idx = pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((B, d), lambda t: (0, 0)),
            pl.BlockSpec((tile_n, d), lambda t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda t: (0, 0)),
            pl.BlockSpec((B, k), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, k), jnp.float32),
            pltpu.VMEM((B, k), jnp.int32),
        ],
        interpret=interpret,
    )(queries, corpus)
    return vals, idx
