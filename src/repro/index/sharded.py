"""Distributed exact top-k over a corpus sharded across the 'model' axis.

This is the production layout of the Krites static tier (and of recsys
``retrieval_cand``): corpus rows live row-sharded across chips; each shard
computes a local top-k with the fused simsearch kernel, and only the tiny
(k scores, k indices) pairs cross the interconnect for the global merge —
instead of gathering the corpus or the full score matrix.

Implemented with ``shard_map`` + ``jax.lax`` collectives (all_gather of
per-shard top-k). The auto-GSPMD path (see index/flat.py under jit) is the
baseline; this manual-merge version is the optimized variant measured in
§Perf. At million-entry tier sizes the exact per-shard scan itself is
the bottleneck; ``build_sharded_ivf``/``sharded_ivf_topk`` swap it for
the IVF quantized scan + exact rerank (DESIGN.md §11) under the same
tiny k-candidate merge.

The *dynamic* tier has its own twins here (DESIGN.md §13): the
row-sharded masked top-k (``sharded_masked_topk``) mirrors
``index.flat.masked_cosine_topk`` bit for bit — per-shard masked scan,
tiny candidate merge, global slot ids — and the write side
(``sharded_dyn_write`` / ``sharded_bulk_insert`` / ``sharded_touch_many``)
routes every mutation to the owning shard as a shard-local scatter:
non-owners compute an out-of-range local slot and XLA's ``mode="drop"``
scatter discards it, so no collective and no tier gather is ever needed
to write. The merge contract every lookup twin obeys: per-shard
candidates are gathered in shard order and selected with the *stable*
``lax.top_k``, so score ties resolve to the lowest global row/slot id —
exactly the single-device ``argmax``/``top_k`` tie rule. That is what
lets the serving policies (``core/policy.py``) stay decision-for-decision
identical to the single-device path under any shard count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.index.flat import l2_normalize, row_dots
from repro.kernels.simsearch.ops import cosine_topk


def pad_rows(corpus, n_shards: int):
    """Pad a row-sharded corpus to a multiple of ``n_shards`` rows with
    copies of row 0. Safe for top-k serving: a pad row scores exactly
    like the real row 0, and the stable shard merge always prefers the
    earlier (real) occurrence, so a pad index is never returned.
    Works on numpy and jax arrays alike."""
    n = corpus.shape[0]
    pad = (-n) % n_shards
    if pad == 0:
        return corpus
    xp = np if isinstance(corpus, np.ndarray) else jnp
    return xp.concatenate([corpus, xp.repeat(corpus[:1], pad, axis=0)])


def shard_dynamic_tier(tier, mesh, axis: str = "model"):
    """Place every field of a ``tiers.DynamicTier`` row-sharded over
    ``axis`` (emb ``P(axis, None)``, the per-slot metadata ``P(axis)``),
    so the lookup/write twins below run shard-local from the start
    instead of resharding on first use. Capacity must divide the shard
    count."""
    n_shards = mesh.shape[axis]
    assert tier.emb.shape[0] % n_shards == 0, \
        (tier.emb.shape[0], n_shards)

    def put(a):
        spec = P(axis, *([None] * (a.ndim - 1)))
        return jax.device_put(a, jax.sharding.NamedSharding(mesh, spec))

    return jax.tree.map(put, tier)


def sharded_masked_topk(queries: jax.Array, emb: jax.Array,
                        valid: jax.Array, mesh, k: int = 1,
                        axis: str = "model"):
    """Dynamic-tier twin of :func:`sharded_cosine_topk`: masked top-k
    over a row-sharded mutable tier with a global-slot merge.

    queries (B, d) replicated; emb (C, d) and valid (C,) sharded over
    ``axis``. Returns (scores (B, k), global slot ids (B, k)). Scores
    are bit-identical to ``masked_cosine_topk(corpus_normalized=True)``
    (both score through ``row_dots``, whose per-row reduce over the
    unpartitioned d axis does not depend on the shard's size) and the
    stable merge keeps the lowest-slot tie rule, so serving decisions
    match the single-device masked scan exactly. Invalid rows score
    -inf; a fully-invalid tier returns (-inf, 0) on both paths.
    """
    n_shards = mesh.shape[axis]
    rows_per = emb.shape[0] // n_shards
    q = l2_normalize(queries.astype(jnp.float32))

    def local(q, e, m):
        sims = row_dots(q, e)                            # (B, rows_per)
        sims = jnp.where(m[None, :], sims, -jnp.inf)
        vals, idx = jax.lax.top_k(sims, k)
        gidx = idx + jax.lax.axis_index(axis) * rows_per
        all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        all_idx = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        top_v, pos = jax.lax.top_k(all_vals, k)
        return top_v, jnp.take_along_axis(all_idx, pos, axis=1)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(None, None), P(axis, None), P(axis)),
                   out_specs=(P(), P()), check_vma=False)
    return fn(q, emb, valid)


def _owned_slots(slots, axis: str, rows_per: int):
    """Map global slot ids to shard-local rows; slots owned elsewhere
    become ``rows_per`` (out of range), which a ``mode='drop'`` scatter
    silently discards — the shard-routing trick behind every write twin
    below. Guards against negative-index wraparound explicitly."""
    lo = jax.lax.axis_index(axis) * rows_per
    s = jnp.asarray(slots, jnp.int32)
    owned = jnp.logical_and(s >= lo, s < lo + rows_per)
    return jnp.where(owned, s - lo, rows_per)


def sharded_dyn_write(tier, slot, q, cls, answer_ref, static_origin, now,
                      mesh, axis: str = "model", last_used=None,
                      expires=0):
    """Shard-routed twin of ``tiers._write``: one slot write (an async
    promotion) landing only on the owning shard. All operands are
    replicated scalars except the tier itself; no collective runs. Like the single-device twin, ``now`` stamps
    ``written_at`` (the LWW clock — enqueue time for promotions) and
    ``last_used`` defaults to it unless the caller passes the live
    clock so a delayed promotion lands LRU-warm."""
    rows_per = tier.emb.shape[0] // mesh.shape[axis]

    def local(emb, c, ar, so, va, lu, wa, xp, slot, q, cls, answer_ref,
              static_origin, now, lu_now, exp):
        ls = _owned_slots(slot, axis, rows_per)
        return (emb.at[ls].set(q, mode="drop"),
                c.at[ls].set(cls.astype(jnp.int32), mode="drop"),
                ar.at[ls].set(answer_ref.astype(jnp.int32), mode="drop"),
                so.at[ls].set(static_origin, mode="drop"),
                va.at[ls].set(True, mode="drop"),
                lu.at[ls].set(lu_now, mode="drop"),
                wa.at[ls].set(now, mode="drop"),
                xp.at[ls].set(exp, mode="drop"))

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(axis), P(), P(None), P(), P(),
                  P(), P(), P(), P()),
        out_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis),
                   P(axis), P(axis), P(axis)),
        check_vma=False)
    emb, c, ar, so, va, lu, wa, xp = fn(
        tier.emb, tier.cls, tier.answer_ref, tier.static_origin,
        tier.valid, tier.last_used, tier.written_at, tier.expires_at,
        jnp.asarray(slot, jnp.int32), q, jnp.asarray(cls),
        jnp.asarray(answer_ref), jnp.asarray(static_origin),
        jnp.asarray(now, jnp.int32),
        jnp.asarray(now if last_used is None else last_used, jnp.int32),
        jnp.asarray(expires, jnp.int32))
    return tier._replace(emb=emb, cls=c, answer_ref=ar, static_origin=so,
                         valid=va, last_used=lu, written_at=wa,
                         expires_at=xp)


def sharded_bulk_insert(tier, V, slots, rows, ts, cls, mesh,
                        axis: str = "model", exps=None):
    """Shard-routed twin of the policy's batched ``_bulk_insert``: a
    whole micro-batch of backend inserts scattered in one fused update
    per field, each landing only on the owning shard (``last_used`` is
    left to the batched touch, exactly like the single-device twin).
    ``slots``/``rows``/``ts``/``cls`` are replicated, padded the same
    way as single-device (duplicate scatters of identical values are
    benign)."""
    rows_per = tier.emb.shape[0] // mesh.shape[axis]

    def local(emb, c, ar, so, va, wa, xp, V, slots, rows, ts, cls, exps):
        ls = _owned_slots(slots, axis, rows_per)
        return (emb.at[ls].set(V[rows], mode="drop"),
                c.at[ls].set(cls, mode="drop"),
                ar.at[ls].set(jnp.int32(-1), mode="drop"),
                so.at[ls].set(False, mode="drop"),
                va.at[ls].set(True, mode="drop"),
                wa.at[ls].set(ts, mode="drop"),
                xp.at[ls].set(exps, mode="drop"))

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(None, None), P(None), P(None),
                  P(None), P(None), P(None)),
        out_specs=(P(axis, None), P(axis), P(axis), P(axis), P(axis),
                   P(axis), P(axis)),
        check_vma=False)
    if exps is None:
        exps = np.zeros(np.asarray(slots).shape[0], np.int32)
    emb, c, ar, so, va, wa, xp = fn(
        tier.emb, tier.cls, tier.answer_ref, tier.static_origin,
        tier.valid, tier.written_at, tier.expires_at, V,
        jnp.asarray(slots, jnp.int32), jnp.asarray(rows, jnp.int32),
        jnp.asarray(ts, jnp.int32), jnp.asarray(cls, jnp.int32),
        jnp.asarray(exps, jnp.int32))
    return tier._replace(emb=emb, cls=c, answer_ref=ar, static_origin=so,
                         valid=va, written_at=wa, expires_at=xp)


def sharded_touch_many(tier, slots, nows, mesh, axis: str = "model"):
    """Shard-routed twin of ``tiers.touch_many``: LRU clock scatter for
    a batch of hits, owner-local. Callers deduplicate ``slots`` (latest
    ``now`` wins) exactly as on the single-device path."""
    rows_per = tier.emb.shape[0] // mesh.shape[axis]

    def local(lu, slots, nows):
        ls = _owned_slots(slots, axis, rows_per)
        return lu.at[ls].set(nows, mode="drop")

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis), P(None), P(None)),
                   out_specs=P(axis), check_vma=False)
    return tier._replace(last_used=fn(
        tier.last_used, jnp.asarray(slots, jnp.int32),
        jnp.asarray(nows, jnp.int32)))


def sharded_cosine_topk(queries: jax.Array, corpus: jax.Array, mesh,
                        k: int = 4, axis: str = "model",
                        force: str | None = None):
    """queries (B, d) replicated; corpus (N, d) sharded over ``axis``.

    Returns (scores (B, k), global indices (B, k)).
    """
    n_shards = mesh.shape[axis]
    N = corpus.shape[0]
    shard_rows = N // n_shards

    def local(q, c):
        vals, idx = cosine_topk(q, c, k=k, force=force)
        shard_id = jax.lax.axis_index(axis)
        gidx = idx + shard_id * shard_rows
        # gather the candidate sets from every shard: (n_shards*k,) each
        all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        all_idx = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        top_v, pos = jax.lax.top_k(all_vals, k)
        top_i = jnp.take_along_axis(all_idx, pos, axis=1)
        return top_v, top_i

    other = [a for a in mesh.axis_names if a != axis]
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(*([None] * queries.ndim)), P(axis, None)),
        out_specs=(P(), P()),
        check_vma=False)
    return fn(queries, corpus)


def sharded_topk_scores(u: jax.Array, cand_vecs: jax.Array,
                        cand_ids: jax.Array, mesh, k: int = 100,
                        axis: str = "model"):
    """Distributed retrieval scoring: raw-dot top-k with per-shard
    selection + tiny merge (recsys `retrieval_cand` / cache lookup).

    u: (B, d) or (B, I, d) (multi-interest: max over I) — replicated.
    cand_vecs (N, d), cand_ids (N,) — sharded over ``axis``.
    """
    def local(uq, c, ids):
        if uq.ndim == 3:
            scores = jnp.einsum("bid,nd->bin", uq, c).max(axis=1)
        else:
            scores = jnp.einsum("bd,nd->bn", uq, c)
        vals, idx = jax.lax.top_k(scores.astype(jnp.float32), k)
        gids = jnp.take(ids, idx)
        # merge: gather the k candidates from every shard (k*n_shards
        # scalars — instead of gathering the N-row corpus or scores)
        all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        all_gids = jax.lax.all_gather(gids, axis, axis=1, tiled=True)
        top_v, pos = jax.lax.top_k(all_vals, k)
        return top_v, jnp.take_along_axis(all_gids, pos, axis=1)

    uspec = P(*([None] * u.ndim))
    fn = shard_map(local, mesh=mesh,
                   in_specs=(uspec, P(axis, None), P(axis)),
                   out_specs=(P(), P()), check_vma=False)
    return fn(u, cand_vecs, cand_ids)


def sharded_topk_local_candidates(u: jax.Array, table: jax.Array,
                                  cand_ids: jax.Array, mesh, k: int = 100,
                                  axis: str = "model"):
    """Retrieval with *range-partitioned* candidates (production layout:
    each shard's candidate list references rows it owns, as in sharded
    ANN/DLRM serving). The embedding gather is then shard-LOCAL; the only
    collective is the k-candidate merge (KBs).

    table (V, d) row-sharded over ``axis``; cand_ids (N,) sharded over
    ``axis`` with values in the owning shard's row range.
    """
    V = table.shape[0]
    n_shards = mesh.shape[axis]
    rows_per = V // n_shards

    def local(uq, tab, ids):
        local_rows = ids - jax.lax.axis_index(axis) * rows_per
        c = jnp.take(tab, jnp.clip(local_rows, 0, rows_per - 1), axis=0)
        if uq.ndim == 3:
            scores = jnp.einsum("bid,nd->bin", uq, c).max(axis=1)
        else:
            scores = jnp.einsum("bd,nd->bn", uq, c)
        vals, idx = jax.lax.top_k(scores.astype(jnp.float32), k)
        gids = jnp.take(ids, idx)
        all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        all_gids = jax.lax.all_gather(gids, axis, axis=1, tiled=True)
        top_v, pos = jax.lax.top_k(all_vals, k)
        return top_v, jnp.take_along_axis(all_gids, pos, axis=1)

    uspec = P(*([None] * u.ndim))
    fn = shard_map(local, mesh=mesh,
                   in_specs=(uspec, P(axis, None), P(axis)),
                   out_specs=(P(), P()), check_vma=False)
    return fn(u, table, cand_ids)


def build_sharded_ivf(corpus, n_shards: int, n_clusters: int | None = None,
                      **build_kw):
    """Per-shard IVF over a row-partitioned corpus (DESIGN.md §11).

    Shard ``s`` owns the contiguous row range ``[s*N/S, (s+1)*N/S)`` and
    gets its own sub-index (centroids trained on its rows only, local
    row ids). The per-shard layouts are padded to a common band
    capacity and stacked on a leading shard axis, so the whole index
    shards over ``P(axis, ...)`` like the corpus itself.
    """
    import numpy as np

    from repro.index.ivf import IVF, build_ivf

    corpus = np.asarray(corpus, np.float32)
    N = corpus.shape[0]
    assert N % n_shards == 0, (N, n_shards)
    rows_per = N // n_shards
    parts = [build_ivf(corpus[s * rows_per:(s + 1) * rows_per],
                       n_clusters=n_clusters, **build_kw)
             for s in range(n_shards)]
    cap = max(p.codes.shape[1] for p in parts)

    def pad_band(a, fill):
        a = np.asarray(a)
        short = cap - a.shape[1]
        if not short:
            return a
        width = [(0, 0), (0, short)] + [(0, 0)] * (a.ndim - 2)
        return np.pad(a, width, constant_values=fill)

    return IVF(
        centroids=jnp.stack([jnp.asarray(p.centroids) for p in parts]),
        codes=jnp.asarray(np.stack([pad_band(p.codes, 0)
                                    for p in parts])),
        scales=jnp.asarray(np.stack([pad_band(p.scales, 0)
                                     for p in parts])),
        row_ids=jnp.asarray(np.stack([pad_band(p.row_ids, -1)
                                      for p in parts])),
        corpus=jnp.stack([jnp.asarray(p.corpus) for p in parts]))


def sharded_ivf_topk(queries: jax.Array, sivf, mesh, k: int = 1,
                     axis: str = "model", nprobe: int = 8,
                     n_candidates: int = 32, force: str | None = None):
    """ANN twin of :func:`sharded_cosine_topk`: per-shard IVF scan +
    exact rerank over the shard's own rows, then the same tiny
    k-candidate all-gather merge — only (k scores, k global ids) pairs
    cross the interconnect.

    queries (B, d) replicated; ``sivf`` a stacked :func:`build_sharded_ivf`
    index whose leading axis is sharded over ``axis``.
    Returns (scores (B, k), global row indices (B, k)).
    """
    from repro.kernels.ivf_scan.ops import ivf_search

    rows_per = sivf.corpus.shape[1]

    def local(q, cent, codes, scales, ids, corp):
        vals, lids = ivf_search(q, corp[0], cent[0], codes[0], scales[0],
                                ids[0], k=k, nprobe=nprobe,
                                n_candidates=n_candidates, force=force)
        shard_id = jax.lax.axis_index(axis)
        gids = jnp.where(lids >= 0, lids + shard_id * rows_per, -1)
        all_vals = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        all_gids = jax.lax.all_gather(gids, axis, axis=1, tiled=True)
        top_v, pos = jax.lax.top_k(all_vals, k)
        return top_v, jnp.take_along_axis(all_gids, pos, axis=1)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), P(axis, None, None),
                  P(axis, None, None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(P(), P()), check_vma=False)
    return fn(queries, sivf.centroids, sivf.codes, sivf.scales,
              sivf.row_ids, sivf.corpus)


def sharded_ivf_lookup(mesh, sivf, axis: str = "model", nprobe: int = 8,
                       n_candidates: int = 32):
    """ANN twin of :func:`sharded_static_lookup`: a jitted
    (queries) -> (best_sim, best_idx) closure over a sharded IVF index
    kept on device — the serving-path static lookup at million-entry
    scale."""
    def spec(a):
        return jax.sharding.NamedSharding(
            mesh, P(axis, *([None] * (a.ndim - 1))))

    sivf = jax.tree.map(lambda a: jax.device_put(a, spec(a)), sivf)

    @jax.jit
    def lookup(queries):
        v, i = sharded_ivf_topk(queries, sivf, mesh, k=1, axis=axis,
                                nprobe=nprobe, n_candidates=n_candidates)
        return v[:, 0], i[:, 0]
    return lookup


class ShardedIVFIndex:
    """Injectable static-tier index (the ``topk(queries, k)`` +
    ``describe()`` protocol of ``index.ivf.IVFIndex``) serving lookups
    through the per-shard IVF scan + exact rerank + tiny k-candidate
    merge on a device mesh (DESIGN.md §13).

    Drop it into ``BaselinePolicy``/``KritesPolicy`` via ``index=`` and
    the serving path routes its static top-1 through
    :func:`sharded_ivf_topk` with no further policy changes. The corpus
    is padded to a shard multiple with copies of row 0
    (:func:`pad_rows`) whose layout entries are then tombstoned
    (row id -1, the scan's padding convention) — so no ``k`` can return
    a global id >= the real row count. ``nprobe`` is clamped to the
    per-shard cluster count, so "full probe" configs stay
    exact-rerank-equal to flat search on every shard layout.
    """

    def __init__(self, corpus, mesh, axis: str = "model", nprobe: int = 8,
                 n_candidates: int = 32, n_clusters: int | None = None,
                 **build_kw):
        self.mesh, self.axis = mesh, axis
        self.n_shards = mesh.shape[axis]
        c = np.asarray(corpus, np.float32)
        self.n_rows = c.shape[0]
        padded = pad_rows(c, self.n_shards)
        sivf = build_sharded_ivf(padded, self.n_shards,
                                 n_clusters=n_clusters, **build_kw)
        if padded.shape[0] != self.n_rows:
            # tombstone the pad duplicates (they may span several
            # trailing shards when pad > rows_per): -1 row ids are the
            # scan's padding convention, so no k can ever surface a
            # phantom global id >= n_rows
            rows_per = padded.shape[0] // self.n_shards
            ids = np.asarray(sivf.row_ids).copy()     # (S, K, cap) local
            for s in range(self.n_shards):
                gids = np.where(ids[s] >= 0, ids[s] + s * rows_per, -1)
                ids[s] = np.where(gids >= self.n_rows, -1, ids[s])
            sivf = sivf._replace(row_ids=jnp.asarray(ids))
        self.nprobe = min(nprobe, sivf.centroids.shape[1])
        self.n_candidates = n_candidates

        def spec(a):
            return jax.sharding.NamedSharding(
                mesh, P(axis, *([None] * (a.ndim - 1))))

        self.sivf = jax.tree.map(lambda a: jax.device_put(a, spec(a)),
                                 sivf)
        self._fns: dict = {}          # k -> jitted lookup

    def topk(self, queries: jax.Array, k: int = 1):
        """queries (B, d) L2-normalized -> (scores (B, k), global row
        indices (B, k))."""
        fn = self._fns.get(k)
        if fn is None:
            fn = jax.jit(lambda q: sharded_ivf_topk(
                q, self.sivf, self.mesh, k=k, axis=self.axis,
                nprobe=self.nprobe, n_candidates=self.n_candidates))
            self._fns[k] = fn
        return fn(queries)

    def describe(self) -> str:
        K = int(self.sivf.centroids.shape[1])
        return (f"sharded-ivf(N={self.n_rows}, shards={self.n_shards}, "
                f"K/shard={K}, nprobe={self.nprobe}, "
                f"C={self.n_candidates})")


def sharded_static_lookup(mesh, static_emb: jax.Array, axis: str = "model"):
    """Returns a jitted (queries) -> (best_sim, best_idx) closure over a
    corpus kept sharded on device — the serving-path static lookup."""
    sharding = jax.sharding.NamedSharding(mesh, P(axis, None))
    corpus = jax.device_put(static_emb, sharding)

    @jax.jit
    def lookup(queries):
        v, i = sharded_cosine_topk(queries, corpus, mesh, k=1, axis=axis)
        return v[:, 0], i[:, 0]
    return lookup
