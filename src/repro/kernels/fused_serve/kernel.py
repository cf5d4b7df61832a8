"""Fused single-pass serve-pipeline Pallas TPU kernel (DESIGN.md §15).

One dispatch runs *both* halves of a serve decision for a micro-batch:
the static-tier IVF probe (the ``kernels/ivf_scan`` band scan) and the
dynamic-tier masked scan, with the query row resident in VMEM the whole
time. The dispatched path pays two kernel launches and re-stages the
query block for each; here the probed int8 bands and the bf16 dynamic
tiles stream through VMEM around a single resident query.

Grid: (B, nprobe) — one step per (query, probed cluster), the probe
axis innermost, exactly like ``ivf_scan``:

- the top-``nprobe`` cluster ids arrive as a scalar-prefetch argument
  and the BlockSpec index maps fetch exactly the probed clusters' int8
  codes/scales/row_ids; Pallas double-buffers those blocks, so band
  ``p+1`` is in flight while band ``p`` is scored;
- the dynamic tier streams as bf16 ``(capd, d)`` tiles through its own
  manually-DMA'd 2-slot double buffer. Its first tile's DMA is issued
  at the row's *first* probe step and awaited only at its last, so the
  dynamic fetch hides behind the whole static band scan;
- both scans carry running top-C candidate lists (the online-top-k
  idiom of ``kernels/simsearch``) and stay int8/bf16 end-to-end — the
  exact fp32 rerank happens outside the kernel (``ops.fused_serve``)
  inside the same jitted dispatch.

Outputs per row: static candidates ``(C,)`` (approx score, global row
id) and dynamic candidates ``(Cd,)`` (approx score, tier slot), both in
(score desc, id asc) order with padding flushed as (NEG, -1) — the same
contract the ``ref.py`` oracle pins.

Mosaic's layout rules shape the operands: every per-row operand travels
with a unit middle axis — queries ``(B, 1, d)``, band scales/ids
``(K, 1, cap)``, dyn tile ids ``(T, 1, capd)``, outputs ``(B, 1, C)`` —
so each block's last two dims equal the array's, and the DMA'd dyn
tiles are zero-padded to a 128-lane row (a narrower HBM row cannot be
sliced out by DMA). The wrapper keeps the ``(B, d)`` / ``(B, C)``
contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.simsearch.kernel import BIG_IDX, NEG, _merge_topk


def _kernel(cids_ref, q_ref, codes_ref, scales_ref, ids_ref,
            dyn_hbm, dyn_ids_hbm,
            sv_ref, si_ref, dv_ref, di_ref,
            run_v, run_i, dtile_e, dtile_i, sem,
            *, nprobe, n_candidates, n_dyn_candidates, n_dyn_tiles):
    p = pl.program_id(1)

    def dyn_copies(slot, t):
        return (pltpu.make_async_copy(dyn_hbm.at[t],
                                      dtile_e.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(dyn_ids_hbm.at[t],
                                      dtile_i.at[slot], sem.at[1, slot]))

    @pl.when(p == 0)
    def _init():
        run_v[...] = jnp.full_like(run_v, NEG)
        run_i[...] = jnp.full_like(run_i, BIG_IDX)
        # the dynamic stream's first tile starts fetching BEFORE any
        # static band is scored and lands behind the whole band scan
        for c in dyn_copies(0, 0):
            c.start()

    q = q_ref[0].astype(jnp.float32)                         # (1, d)
    q = q * jax.lax.rsqrt(
        jnp.maximum(jnp.sum(q * q, -1, keepdims=True), 1e-18))
    d = q.shape[1]

    codes = codes_ref[0].astype(jnp.float32)                 # (cap, d)
    sims = jax.lax.dot_general(
        q, codes, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (1, cap)
    sims = sims * scales_ref[0]
    ids = ids_ref[0]                                         # (1, cap)
    sims = jnp.where(ids < 0, NEG, sims)
    mids = jnp.where(ids < 0, BIG_IDX, ids)
    rv, ri = _merge_topk(jnp.concatenate([run_v[...], sims], axis=1),
                         jnp.concatenate([run_i[...], mids], axis=1),
                         n_candidates)
    run_v[...] = rv
    run_i[...] = ri

    @pl.when(p == nprobe - 1)
    def _finish():
        sv_ref[0] = rv
        si_ref[0] = jnp.where(rv == NEG, -1, ri)

        def dyn_body(t, carry):
            dv, di = carry
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n_dyn_tiles)
            def _start_next():
                for c in dyn_copies(jax.lax.rem(t + 1, 2), t + 1):
                    c.start()

            for c in dyn_copies(slot, t):
                c.wait()
            tile = dtile_e[slot][:, :d].astype(jnp.float32)  # (capd, d)
            sims = jax.lax.dot_general(
                q, tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (1, capd)
            ids = dtile_i[slot]                              # (1, capd)
            sims = jnp.where(ids < 0, NEG, sims)
            mids = jnp.where(ids < 0, BIG_IDX, ids)
            return _merge_topk(jnp.concatenate([dv, sims], axis=1),
                               jnp.concatenate([di, mids], axis=1),
                               n_dyn_candidates)

        dv = jnp.full((1, n_dyn_candidates), NEG, jnp.float32)
        di = jnp.full((1, n_dyn_candidates), BIG_IDX, jnp.int32)
        dv, di = jax.lax.fori_loop(0, n_dyn_tiles, dyn_body, (dv, di))
        dv_ref[0] = dv
        di_ref[0] = jnp.where(dv == NEG, -1, di)


@functools.partial(jax.jit, static_argnames=("n_candidates",
                                             "n_dyn_candidates",
                                             "interpret"))
def fused_serve_kernel(queries: jax.Array, cids: jax.Array,
                       codes: jax.Array, scales: jax.Array,
                       row_ids: jax.Array, dyn_tiles: jax.Array,
                       dyn_tile_ids: jax.Array, n_candidates: int = 32,
                       n_dyn_candidates: int = 16,
                       interpret: bool = False):
    """Fused static + dynamic candidate generation.

    queries (B, d); cids (B, nprobe) int32; codes (K, cap, d) int8;
    scales (K, cap); row_ids (K, cap); dyn_tiles (T, capd, d) bf16;
    dyn_tile_ids (T, capd) int32 (-1 = invalid/padding slot).

    Returns (static scores (B, C), static row ids (B, C),
             dyn scores (B, Cd), dyn tier slots (B, Cd)).
    """
    B, d = queries.shape
    _, nprobe = cids.shape
    K, cap, _ = codes.shape
    n_dyn_tiles, capd, _ = dyn_tiles.shape
    # a bf16 HBM row narrower than the 128-lane tile cannot be sliced
    # out by DMA, so the dyn tiles stream lane-padded with zero columns
    dp = -(-d // 128) * 128
    C, Cd = n_candidates, n_dyn_candidates

    kern = functools.partial(_kernel, nprobe=nprobe, n_candidates=C,
                             n_dyn_candidates=Cd,
                             n_dyn_tiles=n_dyn_tiles)
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
            # the dyn tiles stay in HBM; the kernel pulls them through
            # its own double buffer
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C), lambda b, p, cids: (b, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda b, p, cids: (b, 0, 0)),
            pl.BlockSpec((1, 1, Cd), lambda b, p, cids: (b, 0, 0)),
            pl.BlockSpec((1, 1, Cd), lambda b, p, cids: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, C), jnp.float32),          # running static
            pltpu.VMEM((1, C), jnp.int32),            # top-C
            pltpu.VMEM((2, capd, dp), jnp.bfloat16),  # dyn tile x2
            pltpu.VMEM((2, 1, capd), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),          # stream x slot
        ],
    )
    sv, si, dv, di = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, C), jnp.int32),
            jax.ShapeDtypeStruct((B, 1, Cd), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Cd), jnp.int32),
        ],
        interpret=interpret,
    )(cids.astype(jnp.int32), queries[:, None, :], codes,
      scales[:, None, :], row_ids[:, None, :],
      jnp.pad(dyn_tiles.astype(jnp.bfloat16),
              ((0, 0), (0, 0), (0, dp - d))),
      dyn_tile_ids[:, None, :])
    return sv[:, 0], si[:, 0], dv[:, 0], di[:, 0]
