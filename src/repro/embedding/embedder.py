"""Prompt embedder Φ for the live serving stack.

Production semantic caches use a sentence-embedding model; offline we
build Φ from (a) a hashing character-n-gram featurizer (host side, no
weights to download) and (b) a small fixed-seed JAX MLP encoder with
L2-normalized output. Same-intent prompts built from shared templates map
to nearby vectors, which is the property the cache needs.

For trace-driven evaluation the benchmark embeddings are used directly
(as in the paper); this module serves the end-to-end examples and the
serving engine.
"""
from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing


def _ngrams(text: str, lo: int = 2, hi: int = 4):
    t = re.sub(r"\s+", " ", text.lower().strip())
    for n in range(lo, hi + 1):
        for i in range(max(len(t) - n + 1, 0)):
            yield t[i:i + n]
    for w in t.split(" "):
        yield "w:" + w


def hash_features(text: str, n_features: int = 1024) -> np.ndarray:
    """Signed feature hashing of char n-grams + words."""
    x = np.zeros((n_features,), np.float32)
    for g in _ngrams(text):
        h = int.from_bytes(
            hashlib.blake2s(g.encode(), digest_size=8).digest(), "little")
        idx = h % n_features
        sign = 1.0 if (h >> 63) & 1 else -1.0
        x[idx] += sign
    n = np.linalg.norm(x)
    return x / n if n > 0 else x


@dataclass
class Embedder:
    d_out: int = 64
    n_features: int = 1024
    seed: int = 7

    def __post_init__(self):
        key = jax.random.PRNGKey(self.seed)
        k1, k2 = jax.random.split(key)
        h = 4 * self.d_out
        self.w1 = jax.random.normal(k1, (self.n_features, h)) \
            * (self.n_features ** -0.5)
        self.w2 = jax.random.normal(k2, (h, self.d_out)) * (h ** -0.5)
        self._fwd = jax.jit(self._forward)

    def _forward(self, feats: jax.Array) -> jax.Array:
        z = jnp.tanh(feats @ self.w1) @ self.w2
        return z / jnp.maximum(jnp.linalg.norm(z, axis=-1, keepdims=True),
                               1e-9)

    def __call__(self, text: str) -> np.ndarray:
        feats = jnp.asarray(hash_features(text, self.n_features))
        return np.asarray(self._fwd(feats[None])[0])

    def batch(self, texts) -> np.ndarray:
        """Embed ``texts``: host n-gram hashing (span ``embed.featurize``),
        then the MLP on the device through the copy back to the host
        (span ``embed.encode``)."""
        texts = list(texts)
        with tracing.span("embed.featurize", rows=len(texts)):
            feats = np.stack([hash_features(t, self.n_features)
                              for t in texts])
        with tracing.span("embed.encode", rows=len(texts)):
            return np.asarray(self._fwd(jnp.asarray(feats)))
