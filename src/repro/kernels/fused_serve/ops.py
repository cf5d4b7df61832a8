"""Jitted public wrappers for the fused serve pipeline (DESIGN.md §15).

``fused_serve_probe`` — backend-dispatched candidate generation: one
                        pass emits the static IVF candidates *and* the
                        dynamic-tier candidates (Pallas kernel on TPU,
                        jnp twin elsewhere).
``fused_serve``       — probe + exact fp32 rerank of both candidate
                        lists inside the same jitted computation,
                        emitting ``(s_static, h_idx, s_dyn, j)`` per
                        row in one host round trip. The static pair
                        equals ``ivf_search(k=1)`` and the dynamic pair
                        equals the policies' masked argmax whenever the
                        true best row/slot survives into the candidate
                        set (recall@C / recall@Cd) — ANN only changes
                        which rows get scored, never the served score.
``FusedServe``        — the injectable serve-path object consumed by
                        ``core.tiers.serve_lookup_batch`` and
                        ``core.policy`` (flag-gated fast path).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels.fused_serve import kernel as _kernel
from repro.kernels.fused_serve.ref import NEG, _normalize, select_clusters
from repro.kernels.ivf_scan.ops import _scan_jnp, rerank_exact


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pack_dyn_tiles(dyn_emb: jax.Array, dyn_valid: jax.Array,
                   tile: int):
    """Tile the dynamic tier for streaming: (C, d) fp32 ->
    ((T, tile, d) bf16 tiles, (T, tile) int32 slot ids, -1 where the
    slot is invalid or padding). Capacity is padded up to a tile
    multiple with id -1 rows, which the kernel masks to NEG exactly
    like invalid slots."""
    C, d = dyn_emb.shape
    ids = jnp.where(dyn_valid, jnp.arange(C, dtype=jnp.int32), -1)
    pad = (-C) % tile
    emb = jnp.pad(dyn_emb, ((0, pad), (0, 0))).astype(jnp.bfloat16)
    ids = jnp.pad(ids, (0, pad), constant_values=-1)
    T = (C + pad) // tile
    return emb.reshape(T, tile, d), ids.reshape(T, tile)


def _dyn_scan_jnp(queries, dyn_emb, dyn_valid, n_dyn_candidates):
    """CPU/GPU fast path for the dynamic half: bf16-precision masked
    matmul + ``lax.top_k``, survivors re-ordered to the oracle's
    (score desc, slot asc) contract (the ``_scan_jnp`` idiom)."""
    C = dyn_emb.shape[0]
    q = _normalize(queries)
    e = dyn_emb.astype(jnp.bfloat16).astype(jnp.float32)
    sims = q @ e.T
    ids = jnp.where(dyn_valid, jnp.arange(C, dtype=jnp.int32), -1)
    sims = jnp.where(ids[None, :] < 0, NEG, sims)
    flat_i = jnp.broadcast_to(ids[None, :], sims.shape)
    vals, pos = jax.lax.top_k(sims, n_dyn_candidates)
    cand = jnp.take_along_axis(flat_i, pos, axis=1)
    order = jnp.lexsort((cand, -vals))
    vals = jnp.take_along_axis(vals, order, axis=1)
    cand = jnp.take_along_axis(cand, order, axis=1)
    return vals, jnp.where(vals == NEG, -1, cand).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("nprobe", "n_candidates",
                                    "n_dyn_candidates", "dyn_tile",
                                    "force"))
def fused_serve_probe(queries: jax.Array, centroids: jax.Array,
                      codes: jax.Array, scales: jax.Array,
                      row_ids: jax.Array, dyn_emb: jax.Array,
                      dyn_valid: jax.Array, nprobe: int = 8,
                      n_candidates: int = 32,
                      n_dyn_candidates: int = 16, dyn_tile: int = 512,
                      force: str | None = None):
    """Fused candidate generation for both tiers.

    queries (B, d); centroids (K, d); codes (K, cap, d) int8;
    scales (K, cap); row_ids (K, cap), -1 = padding; dyn_emb (C, d)
    fp32; dyn_valid (C,) bool.
    force: None (auto) | 'pallas' | 'interpret' | 'jnp'.
    Returns (static scores (B, C), static ids (B, C),
             dyn scores (B, Cd), dyn slots (B, Cd)); -1 = absent.
    """
    K, cap, _ = codes.shape
    B = queries.shape[0]
    C_dyn = dyn_emb.shape[0]
    nprobe = min(nprobe, K)
    n_candidates = min(n_candidates, nprobe * cap)
    n_dyn_candidates = min(n_dyn_candidates, C_dyn)
    mode = force or ("pallas" if _on_tpu() else "jnp")
    if mode == "jnp" or B == 0:    # a (0,) Pallas grid has no steps to
        sv, si = _scan_jnp(        # even flush outputs — jnp handles it
            queries, centroids, codes, scales, row_ids, nprobe,
            n_candidates)
        dv, di = _dyn_scan_jnp(queries, dyn_emb, dyn_valid,
                               n_dyn_candidates)
        return sv, si, dv, di
    _, cids = select_clusters(queries, centroids, nprobe)
    tiles, tile_ids = pack_dyn_tiles(dyn_emb, dyn_valid,
                                     min(dyn_tile, C_dyn))
    return _kernel.fused_serve_kernel(
        queries, cids, codes, scales, row_ids, tiles, tile_ids,
        n_candidates, n_dyn_candidates,
        interpret=(mode == "interpret"))


def dyn_rerank_exact(queries: jax.Array, dyn_emb: jax.Array,
                     cand_slots: jax.Array):
    """Exact fp32 top-1 over the dynamic candidates.

    queries (B, d) L2-normalized; dyn_emb (C, d) fp32; cand_slots
    (B, Cd) with -1 marking absent. Returns (score (B,), slot (B,))
    matching the policies' masked argmax contract: lowest slot on
    ties, and the all-invalid tier yields (-inf, 0) exactly like
    ``argmax`` over an all ``-inf`` row.
    """
    safe = jnp.clip(cand_slots, 0, dyn_emb.shape[0] - 1)
    rows = jnp.take(dyn_emb, safe, axis=0)                # (B, Cd, d)
    # fp32 multiply-reduce, as in ivf_scan.ops.rerank_exact
    exact = jnp.sum(rows.astype(jnp.float32) * queries[:, None, :],
                    axis=-1)
    exact = jnp.where(cand_slots < 0, -jnp.inf, exact)
    order = jnp.lexsort((cand_slots, -exact))[:, :1]
    s = jnp.take_along_axis(exact, order, axis=1)[:, 0]
    j = jnp.take_along_axis(cand_slots, order, axis=1)[:, 0]
    return s, jnp.where(jnp.isneginf(s), 0, j).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("nprobe", "n_candidates",
                                    "n_dyn_candidates", "dyn_tile",
                                    "force"))
def fused_serve(queries: jax.Array, corpus: jax.Array,
                centroids: jax.Array, codes: jax.Array,
                scales: jax.Array, row_ids: jax.Array,
                dyn_emb: jax.Array, dyn_valid: jax.Array,
                nprobe: int = 8, n_candidates: int = 32,
                n_dyn_candidates: int = 16, dyn_tile: int = 512,
                force: str | None = None):
    """Full fused serve lookup: probe + exact fp32 rerank, one round
    trip. Returns ``(s_static (B,), h_idx (B,), s_dyn (B,), j (B,))``.
    """
    sv, si, dv, di = fused_serve_probe(
        queries, centroids, codes, scales, row_ids, dyn_emb, dyn_valid,
        nprobe=nprobe, n_candidates=n_candidates,
        n_dyn_candidates=n_dyn_candidates, dyn_tile=dyn_tile,
        force=force)
    q = _normalize(queries)
    ss, hi = rerank_exact(queries, corpus, si, k=1)
    sd, j = dyn_rerank_exact(q, dyn_emb, di)
    return ss[:, 0], hi[:, 0], sd, j


@dataclass(frozen=True)
class FusedServe:
    """Injectable fused serve path: both tier lookups in one dispatch.

    ``ivf`` is the packed static-tier layout (``repro.index.ivf.IVF``).
    Consumed by ``core.tiers.serve_lookup_batch`` and gated into the
    policies via ``KritesPolicy(fused=...)`` / ``launch/serve.py
    --fused`` (default off; the flat/IVF/segmented/mesh paths are
    untouched when absent).
    """
    ivf: object
    nprobe: int = 8
    n_candidates: int = 32
    n_dyn_candidates: int = 16
    dyn_tile: int = 512
    force: str | None = None     # kernel dispatch override (see above)

    def lookup(self, queries: jax.Array, dyn):
        """queries (B, d) L2-normalized; ``dyn`` a ``DynamicTier``.
        Returns (s_static (B,), h_idx (B,), s_dyn (B,), j (B,))."""
        return fused_serve(queries, self.ivf.corpus, self.ivf.centroids,
                           self.ivf.codes, self.ivf.scales,
                           self.ivf.row_ids, dyn.emb, dyn.valid,
                           nprobe=self.nprobe,
                           n_candidates=self.n_candidates,
                           n_dyn_candidates=self.n_dyn_candidates,
                           dyn_tile=self.dyn_tile, force=self.force)

    def describe(self) -> str:
        K, cap, d = self.ivf.codes.shape
        return (f"fused-serve(N={self.ivf.corpus.shape[0]}, K={K}, "
                f"cap={cap}, d={d}, nprobe={self.nprobe}, "
                f"C={self.n_candidates}, Cd={self.n_dyn_candidates})")
