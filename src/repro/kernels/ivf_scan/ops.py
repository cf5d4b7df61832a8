"""Jitted public wrappers for the IVF scan: backend dispatch + rerank.

``ivf_scan``   — centroid selection + probed-cluster int8 scan, emitting
                 top-C (approx score, global row id) candidates.
``ivf_search`` — scan + exact fp32 rerank of the C candidates against
                 the original corpus rows, emitting (score, id) pairs in
                 the same format as ``kernels.simsearch.ops.cosine_topk``.
                 Whenever the true best row is among the candidates
                 (recall@C holds) the served pair equals flat search:
                 the rerank recomputes the very same normalized-fp32 dot
                 the flat path computes, and ties break by lowest global
                 row id in both (DESIGN.md §11).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ivf_scan import kernel as _kernel
from repro.kernels.ivf_scan.ref import NEG, _normalize, select_clusters


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _scan_jnp(queries, centroids, codes, scales, row_ids, nprobe,
              n_candidates):
    """CPU/GPU fast path: gathered int8 band scan + ``lax.top_k``
    selection (a full (score, id) lexsort over every scanned slot
    doubles the scan's wall time). The C survivors are then re-ordered
    to the oracle's (score desc, global id asc) contract, so output
    ordering matches ``ivf_scan_ref`` except when an exact
    approx-score tie straddles the C boundary — the exact rerank makes
    that distinction unobservable in served results."""
    qn = _normalize(queries)
    _, cids = select_clusters(queries, centroids, nprobe)
    g = codes[cids].astype(jnp.float32)                  # (B,P,cap,d)
    sims = jnp.einsum("bpcd,bd->bpc", g, qn) * scales[cids]
    ids = row_ids[cids]
    B = queries.shape[0]
    flat = ids.shape[1] * ids.shape[2]   # explicit: B may be 0, which
    fv = jnp.where(ids < 0, NEG, sims).reshape(B, flat)  # breaks -1
    fi = ids.reshape(B, flat)
    vals, pos = jax.lax.top_k(fv, n_candidates)
    cand = jnp.take_along_axis(fi, pos, axis=1)
    order = jnp.lexsort((cand, -vals))
    return (jnp.take_along_axis(vals, order, axis=1),
            jnp.take_along_axis(cand, order, axis=1).astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("nprobe", "n_candidates", "force"))
def ivf_scan(queries: jax.Array, centroids: jax.Array, codes: jax.Array,
             scales: jax.Array, row_ids: jax.Array, nprobe: int = 8,
             n_candidates: int = 32, force: str | None = None):
    """Approximate candidate generation over the packed IVF layout.

    queries (B, d); centroids (K, d); codes (K, cap, d) int8;
    scales (K, cap); row_ids (K, cap), -1 = padding.
    force: None (auto) | 'pallas' | 'interpret' | 'jnp'.
    Returns (approx scores (B, C), global row ids (B, C), -1 = absent).
    """
    K, cap, _ = codes.shape
    nprobe = min(nprobe, K)
    n_candidates = min(n_candidates, nprobe * cap)
    mode = force or ("pallas" if _on_tpu() else "jnp")
    if mode == "jnp":
        return _scan_jnp(queries, centroids, codes, scales, row_ids,
                         nprobe, n_candidates)
    _, cids = select_clusters(queries, centroids, nprobe)
    return _kernel.ivf_scan_kernel(queries, cids, codes, scales, row_ids,
                                   n_candidates,
                                   interpret=(mode == "interpret"))


def rerank_exact(queries: jax.Array, corpus: jax.Array,
                 cand_ids: jax.Array, k: int):
    """Exact fp32 rerank of scan candidates.

    queries (B, d); corpus (N, d) L2-normalized fp32; cand_ids (B, C)
    with -1 marking absent slots. Returns (scores (B, k), ids (B, k)) —
    bit-equal to flat search on the candidate rows (same normalized
    dot, same lowest-global-id tie-break).
    """
    assert k <= cand_ids.shape[1], \
        f"rerank k={k} exceeds candidate count {cand_ids.shape[1]}"
    q = _normalize(queries)
    safe = jnp.clip(cand_ids, 0, corpus.shape[0] - 1)
    rows = jnp.take(corpus, safe, axis=0)                 # (B, C, d)
    # the served score: an fp32 multiply-reduce, the same per-row dot
    # as the flat masked scan (``index.flat.row_dots``) — exact on a
    # TPU too, where an einsum here took one bf16 MXU pass at some
    # candidate counts (score error ~1e-3 measured on a v5e)
    exact = jnp.sum(rows.astype(jnp.float32) * q[:, None, :], axis=-1)
    exact = jnp.where(cand_ids < 0, -jnp.inf, exact)
    order = jnp.lexsort((cand_ids, -exact))[:, :k]
    return (jnp.take_along_axis(exact, order, axis=1),
            jnp.take_along_axis(cand_ids, order, axis=1).astype(
                jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "n_candidates",
                                    "force"))
def ivf_search(queries: jax.Array, corpus: jax.Array,
               centroids: jax.Array, codes: jax.Array, scales: jax.Array,
               row_ids: jax.Array, k: int = 1, nprobe: int = 8,
               n_candidates: int = 32, force: str | None = None):
    """IVF scan + exact rerank; drop-in (B, k) twin of ``cosine_topk``.

    Requires ``k`` <= the effective candidate count (``n_candidates``
    after the scan's nprobe*cap clamp) — asserted, since silently
    returning fewer than k columns would break fixed-shape consumers
    like the sharded k-candidate merge.
    """
    K, cap, _ = codes.shape
    effective_c = min(n_candidates, min(nprobe, K) * cap)
    assert k <= effective_c, \
        f"k={k} exceeds candidate budget {effective_c} " \
        f"(n_candidates={n_candidates}, nprobe={nprobe}, cap={cap})"
    _, cand = ivf_scan(queries, centroids, codes, scales, row_ids,
                       nprobe=nprobe, n_candidates=n_candidates,
                       force=force)
    return rerank_exact(queries, corpus, cand, k)
