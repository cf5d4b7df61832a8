"""Exact flat vector index: normalize + matmul + top-k.

This is the single-device form of the cache lookup (the paper's serving
hot path) and of recsys `retrieval_cand`. On TPU the fused Pallas
``simsearch`` kernel takes over via :mod:`repro.kernels.simsearch.ops`;
this jnp path is its oracle twin and the CPU/dry-run implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def l2_normalize(x: jax.Array, eps: float = 1e-9) -> jax.Array:
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), eps)


def cosine_topk(queries: jax.Array, corpus: jax.Array, k: int = 1,
                corpus_normalized: bool = False):
    """Cosine similarity top-k.

    queries (B, d), corpus (N, d) -> (scores (B, k), idx (B, k)).
    """
    q = l2_normalize(queries.astype(jnp.float32))
    c = corpus.astype(jnp.float32)
    if not corpus_normalized:
        c = l2_normalize(c)
    sims = q @ c.T
    return jax.lax.top_k(sims, k)


def topk_scores(queries: jax.Array, cand_vecs: jax.Array,
                cand_ids: jax.Array, k: int):
    """Raw-dot retrieval scoring: (B, d) x (N, d) -> top-k (scores, ids)."""
    scores = jnp.einsum("bd,nd->bn", queries, cand_vecs)
    vals, idx = jax.lax.top_k(scores.astype(jnp.float32), k)
    return vals, jnp.take(cand_ids, idx)


def row_dots(q: jax.Array, c: jax.Array) -> jax.Array:
    """(B, d) x (N, d) -> (B, N) fp32 dot products as a per-row
    multiply-reduce over d. Each score depends only on its own two rows
    and d, never on N, so any row partition of ``c`` (the row-sharded
    twin in ``index/sharded.py``) reproduces it bit for bit — a matmul's
    accumulation order follows its shape on some backends — and it is
    full fp32 on every backend, with no reduced-precision MXU pass."""
    return jnp.sum(q[:, None, :] * c[None, :, :], axis=-1)


def masked_cosine_topk(queries: jax.Array, corpus: jax.Array,
                       valid: jax.Array, k: int = 1,
                       corpus_normalized: bool = False):
    """Cosine top-k over a partially-valid corpus (the dynamic tier).

    valid (N,) bool — invalid rows score -inf. ``corpus_normalized``
    mirrors :func:`cosine_topk`: the dynamic tier's rows are already
    L2-normalized on insert (`core/tiers.py`), so the serving hot path
    passes True and skips a full-corpus renormalization per lookup.
    Scores come from :func:`row_dots`.
    """
    q = l2_normalize(queries.astype(jnp.float32))
    c = corpus.astype(jnp.float32)
    if not corpus_normalized:
        c = l2_normalize(c)
    sims = row_dots(q, c)
    sims = jnp.where(valid[None, :], sims, -jnp.inf)
    return jax.lax.top_k(sims, k)


class FlatIndex:
    """Exact flat search behind the injectable index protocol
    (``topk(queries, k)`` + ``describe()`` — see ``index/ivf.py``).
    Wraps the fused ``kernels/simsearch`` path over a fixed corpus.

    ``corpus_normalized`` only skips the one-time normalization at
    construction; the fused path re-normalizes internally on every
    call either way (in-kernel on TPU, in the jnp oracle elsewhere),
    which keeps it safe for arbitrary corpora.
    """

    def __init__(self, corpus: jax.Array, corpus_normalized: bool = False,
                 force: str | None = None):
        c = jnp.asarray(corpus, jnp.float32)
        self.corpus = c if corpus_normalized else l2_normalize(c)
        self.force = force

    def topk(self, queries: jax.Array, k: int = 1):
        """queries (B, d) L2-normalized -> (scores (B, k), idx (B, k))."""
        from repro.kernels.simsearch.ops import cosine_topk as fused
        return fused(queries, self.corpus, k=k, force=self.force)

    def describe(self) -> str:
        n, d = self.corpus.shape
        return f"flat(N={n}, d={d})"
