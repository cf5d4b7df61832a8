"""The plain reference of the ``krites-flat`` configuration, written
from its documented semantics. It imports nothing of the program and
takes nothing the program made: weights are drawn again from the seed,
the curated tier is embedded again from its texts.

A configuration names its reference module by path under its
``reference`` key, and the harness loads it from there
(``common.reference``). Such a module provides what ``bench/check.py``
and the metric readers call:

- ``FP32`` and ``CONTROL``, the names of the reference's precision and
  of the control's, one precision down;
- ``Embedder``, ``normalize``, ``static_tier``, ``top2`` and
  ``top2_host``: the embedder, the static tier and the cosine top-1;
- ``lm_weights(be, seed)``: the backend's weights, drawn from ``seed``
  for the configuration's ``backend`` block ``be``;
- ``lm_gaps(be, w, seqs, starts, served, modes)``: per mode, the gap
  of each served token's logit below the reference's best;
- ``backend_ops(be, bw)``: the operations the backend's work ``bw``
  (``bench/child.backend_work``) required, for ``step_mfu``.

It imports JAX only inside functions: the parent process, which never
imports JAX, loads it for its operation count. A module for another
backend architecture may ``from bench.reference import *`` and replace
only the backend functions.

This module's parts:

- the embedder: signed feature hashing of character 2-, 3- and
  4-grams and words (blake2s, 8 bytes), a 1024 -> 256 -> 64 MLP with a
  tanh, weights N(0, 1/fan_in) from PRNGKey(7), L2-normalized output;
- the static tier: the curated rows, then N(0, 1) padding rows from
  numpy's default_rng(7), every row L2-normalized;
- lookups: cosine top-1, lowest index on ties;
- qwen3-1.7b: pre-norm decoder, RMSNorm (eps 1e-6), q/k RMSNorm per
  head, rotate-half RoPE (theta 1e6), causal grouped-query attention,
  SwiGLU, untied unembedding; weights truncated N(0, 1) on [-3, 3]
  scaled by fan_in^-1/2 (the embedding unscaled) and rounded to
  bfloat16, drawn from PRNGKey(seed) in the order of the checkpoint's
  sorted layer-leaf names. Its operations: 2 per weight per token
  through the layers, 2*d*vocab per logits row, 4*heads*head_dim per
  (query, key) pair of attention.

Everything computes in float32 (``precision=HIGHEST`` on a TPU). The
control computes the same in the next precision down: int8 operands in
every matrix product of the embedder and the backend (one absmax scale
per operand), bfloat16x3 (``HIGH``) for the lookups.
"""
from __future__ import annotations

import functools
import hashlib
import re

import numpy as np

FP32, CONTROL = "fp32", "control"


# ---------------------------------------------------------------- embedder

def hash_features(text: str, n_features: int = 1024) -> np.ndarray:
    t = re.sub(r"\s+", " ", text.lower().strip())
    grams = [t[i:i + n] for n in (2, 3, 4)
             for i in range(max(len(t) - n + 1, 0))]
    grams += ["w:" + w for w in t.split(" ")]
    x = np.zeros(n_features, np.float32)
    for g in grams:
        h = int.from_bytes(hashlib.blake2s(g.encode(), digest_size=8)
                           .digest(), "little")
        x[h % n_features] += 1.0 if (h >> 63) & 1 else -1.0
    n = float(np.linalg.norm(x))
    return x / n if n > 0 else x


def _q8(x):
    """Round to int8 with one symmetric absmax scale for the whole
    operand and back (the operand of a plain int8 matrix product)."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x)) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _mm(a, b, mode):
    import jax
    import jax.numpy as jnp
    if mode == CONTROL:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _mlp(mode):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda f, w1, w2: _mm(jnp.tanh(_mm(f, w1, mode)), w2,
                                         mode))


class Embedder:
    def __init__(self, d_out: int = 64, n_features: int = 1024,
                 seed: int = 7):
        import jax
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        h = 4 * d_out
        self.n_features = n_features
        self.w1 = jax.random.normal(k1, (n_features, h)) \
            * (n_features ** -0.5)
        self.w2 = jax.random.normal(k2, (h, d_out)) * (h ** -0.5)
        self._feats: dict = {}

    def feats(self, texts):
        out = []
        for t in texts:
            f = self._feats.get(t)
            if f is None:
                f = self._feats[t] = hash_features(t, self.n_features)
            out.append(f)
        return np.stack(out) if out else np.zeros((0, self.n_features),
                                                  np.float32)

    def __call__(self, texts, mode: str = FP32) -> np.ndarray:
        n = len(texts)
        if not n:
            return np.zeros((0, self.w2.shape[1]), np.float32)
        f = self.feats(texts)
        # rows padded to a power of two: one compiled program per bucket
        # (zero rows leave the control's int8 absmax scale as it was)
        f = np.pad(f, ((0, (1 << (n - 1).bit_length()) - n), (0, 0)))
        z = np.asarray(_mlp(mode)(f, self.w1, self.w2), np.float32)[:n]
        return normalize(z)


def normalize(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


def static_tier(head_emb: np.ndarray, static_rows: int) -> np.ndarray:
    """Curated rows, then the deployment's synthetic padding."""
    d = head_emb.shape[1]
    rows = [np.asarray(head_emb, np.float32)]
    if static_rows > len(head_emb):
        rows.append(np.random.default_rng(7).normal(
            size=(static_rows - len(head_emb), d)).astype(np.float32))
    return normalize(np.concatenate(rows))


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest, ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.view(np.float32)


def dots(V: np.ndarray, E: np.ndarray, mode: str = FP32) -> np.ndarray:
    """V @ E.T on the host: float32, or for the control the three
    bfloat16 products of ``Precision.HIGH``."""
    V = np.asarray(V, np.float32)
    E = np.asarray(E, np.float32)
    if mode == FP32:
        return V @ E.T
    vh, eh = _bf16(V), _bf16(E)
    vl, el = _bf16(V - vh), _bf16(E - eh)
    return vh @ eh.T + vh @ el.T + vl @ eh.T


def top2_host(V, E, mode: str = FP32):
    """Cosine top-1 of each row of V over the rows of E: (best score,
    lowest index of the best, runner-up score)."""
    B = len(V)
    if len(E) == 0:
        inf = np.full(B, -np.inf, np.float32)
        return inf, np.zeros(B, np.int64), inf.copy()
    S = dots(V, E, mode)
    arg = S.argmax(axis=1)
    best = S[np.arange(B), arg]
    if S.shape[1] > 1:
        S[np.arange(B), arg] = -np.inf
        second = S.max(axis=1)
    else:
        second = np.full(B, -np.inf, np.float32)
    return best, arg, second


@functools.lru_cache(maxsize=None)
def _top2_block(prec):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def blk(V, e):
        s = jnp.matmul(V, e.T, precision=prec)
        return jax.lax.top_k(s, 2)
    return blk


def top2(V: np.ndarray, E: np.ndarray, mode: str = FP32,
         chunk: int = 1 << 17):
    """``top2_host`` over a large E on the device, in blocks of rows."""
    import jax
    import jax.numpy as jnp
    prec = jax.lax.Precision.HIGHEST if mode == FP32 \
        else jax.lax.Precision.HIGH
    B = V.shape[0]
    best = np.full(B, -np.inf, np.float32)
    second = best.copy()
    arg = np.zeros(B, np.int64)
    if B == 0 or E.shape[0] < 2:
        return top2_host(V, E, mode)
    Bp = 1 << (B - 1).bit_length()
    Vd = jnp.asarray(np.pad(np.asarray(V, np.float32), ((0, Bp - B), (0, 0))))
    blk = _top2_block(prec)
    for lo in range(0, E.shape[0], chunk):
        e = E[lo:lo + chunk]
        if len(e) < 2:
            e = E[lo - 1:lo + 1]
            lo -= 1
        v2, i2 = (np.asarray(x)[:B] for x in blk(Vd, jnp.asarray(e)))
        s1, a1, s2 = v2[:, 0], lo + i2[:, 0], v2[:, 1]
        take = s1 > best
        second = np.where(take, np.maximum(best, s2),
                          np.maximum(second, s1))
        arg = np.where(take, a1, arg)
        best = np.where(take, s1, best)
    return best, arg, second


# ---------------------------------------------------------------- backend

_DENSE = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def _leaf_shapes(c: dict) -> dict:
    d, h = c["hidden_size"], c["head_dim"]
    shapes = {"wq": (d, c["num_attention_heads"] * h),
              "wk": (d, c["num_key_value_heads"] * h),
              "wv": (d, c["num_key_value_heads"] * h),
              "wo": (c["num_attention_heads"] * h, d),
              "ln1": (d,), "ln2": (d,), "q_norm": (h,), "k_norm": (h,),
              "wg": (d, c["intermediate_size"]),
              "wu": (d, c["intermediate_size"]),
              "wd": (c["intermediate_size"], d)}
    return shapes


def backend_ops(be: dict, bw: dict) -> float:
    """Operations the backend's work required: ``bw`` counts the rows,
    the prompt tokens as the engine pads them, the decode tokens and the
    (query, key) pairs of attention."""
    d, H, K = be["hidden_size"], be["num_attention_heads"], \
        be["num_key_value_heads"]
    hd, ff, L, V = be["head_dim"], be["intermediate_size"], \
        be["num_hidden_layers"], be["vocab_size"]
    per_token = L * (d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff)
    tokens = bw["prefill_tokens"] + bw["decode_tokens"]
    logits = bw["rows"] + bw["decode_tokens"]
    return 2.0 * per_token * tokens + 2.0 * d * V * logits \
        + 4.0 * L * H * hd * bw["attn_pairs"]


def lm_weights(c: dict, seed: int) -> dict:
    """The backend's bfloat16 weights, drawn from ``seed``."""
    import jax
    import jax.numpy as jnp
    L = int(c["num_hidden_layers"])
    shapes = _leaf_shapes(c)

    def init(key):
        keys = jax.random.split(key, 4)
        lkeys = jax.random.split(keys[0], len(shapes))
        out = {}
        for lk, (name, shp) in zip(lkeys, sorted(shapes.items())):
            full = (L, *shp)
            if name in _DENSE:
                out[name] = (jax.random.truncated_normal(
                    lk, -3.0, 3.0, full, jnp.float32)
                    * full[-2] ** -0.5).astype(jnp.bfloat16)
        V, d = int(c["vocab_size"]), int(c["hidden_size"])
        out["embed"] = jax.random.truncated_normal(
            keys[1], -3.0, 3.0, (V, d), jnp.float32).astype(jnp.bfloat16)
        out["unembed"] = (jax.random.truncated_normal(
            keys[2], -3.0, 3.0, (d, V), jnp.float32)
            * d ** -0.5).astype(jnp.bfloat16)
        return out

    return jax.jit(init)(jax.random.PRNGKey(int(seed)))


def _rms(x, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x (N, S, H, D), positions 0..S-1, rotate-half."""
    import jax.numpy as jnp
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def lm_gaps(c: dict, w: dict, seqs, starts, served, modes=(FP32,)):
    """Run the backend over each token sequence and, at each served
    position, read how far the served token's logit lies below the
    reference's best.

    ``seqs[i]`` is the whole sequence (prompt as the engine pads it,
    then the served tokens but the last); ``starts[i]`` the position
    whose logits chose the first served token; ``served[i]`` the served
    tokens. Returns, per mode, the gaps of the served tokens
    (reference = float32) and, for the control mode, the gap of the
    token the control puts first at the same positions.
    """
    import jax
    import jax.numpy as jnp
    L = int(c["num_hidden_layers"])
    H, K = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    D, eps = int(c["head_dim"]), float(c["rms_norm_eps"])
    theta = float(c["rope_theta"])
    S = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    hi = jax.lax.Precision.HIGHEST

    @functools.partial(jax.jit, static_argnums=2)
    def layer(x, p, mode_ctl):
        mode = CONTROL if mode_ctl else FP32
        N = x.shape[0]
        h = _rms(x, eps)
        q = _mm(h, p["wq"], mode).reshape(N, S, H, D)
        k = _mm(h, p["wk"], mode).reshape(N, S, K, D)
        v = _mm(h, p["wv"], mode).reshape(N, S, K, D)
        q, k = _rope(_rms(q, eps), theta), _rope(_rms(k, eps), theta)
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=hi) * D ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v,
                       precision=hi).reshape(N, S, H * D)
        x = x + _mm(o, p["wo"], mode)
        h = _rms(x, eps)
        g = _mm(h, p["wg"], mode)
        u = _mm(h, p["wu"], mode)
        return x + _mm(jax.nn.silu(g) * u, p["wd"], mode)

    # positions to read: (row, position, served token)
    rows, pos, tok = [], [], []
    for i, (st, sv) in enumerate(zip(starts, served)):
        for k, t in enumerate(sv):
            rows.append(i)
            pos.append(st + k)
            tok.append(int(t))
    rows, pos, tok = (np.asarray(a) for a in (rows, pos, tok))
    out = {}
    logits_of = {}
    for mode in modes:
        x = w["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for layer_i in range(L):
            p = {n: w[n][layer_i].astype(jnp.float32) for n in _DENSE}
            x = layer(x, p, mode == CONTROL)
        hsel = _rms(x[jnp.asarray(rows), jnp.asarray(pos)], eps)
        logits_of[mode] = _unembed(hsel, w["unembed"], mode)
    ref = logits_of[FP32]
    best = ref.max(axis=1)
    out[FP32] = best - ref[np.arange(len(tok)), tok]
    if CONTROL in logits_of:
        first = logits_of[CONTROL].argmax(axis=1)
        out[CONTROL] = best - ref[np.arange(len(tok)), first]
    return out


@functools.lru_cache(maxsize=None)
def _logits(mode):
    import jax
    return jax.jit(lambda a, W: _mm(a, W, mode))


def _unembed(h, wu, mode, chunk: int = 256):
    import jax.numpy as jnp
    W = wu.astype(jnp.float32)
    n = h.shape[0]
    h = jnp.pad(h, ((0, -n % chunk), (0, 0)))
    parts = [np.asarray(_logits(mode)(h[lo:lo + chunk], W))
             for lo in range(0, h.shape[0], chunk)]
    return np.concatenate(parts)[:n] if parts \
        else np.zeros((0, W.shape[1]), np.float32)
