"""One generator for every traffic mix: ``bench/traffic/<mix>.json``.

A mix file holds parameters only. The class process is the one of
``synth_traces.LMARENA_LIKE`` (vCache SemCacheLMArena, arXiv:2502.03771),
copied here and rendered to text: equivalence classes with Zipf
popularity, hierarchical topics, several verbatim phrasings per class,
and a share of confusable classes that differ from another class by a
word or two. Prompts are text because the served embedder hashes
character n-grams.

What the seed decides and what it does not: the class universe, the
history (and so the curated head and the requests replayed into the
dynamic tier during set-up), the window's multiset of requests (class,
phrasing) and its multiset of inter-arrival gaps all come from the
mix's ``structure_seed``; ``--seed`` only orders the window's requests
and gaps. So every seed sends the same prompts with the same gaps, in
another order.

Pure numpy and the standard library: the parent process, which must
not touch JAX, runs this too.
"""
from __future__ import annotations

import json
import math
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parent
MIX_DIR = ROOT / "traffic"

_CONS = "bcdfghjklmnprstvwz"
_VOWELS = "aeiou"
_PREFIXES = ["", "hey, ", "please ", "quick question: ", "can you tell me ",
             "i need help: ", "so ", "hi! ", "ok, ", "question - "]
_SUFFIXES = ["", "?", " thanks", " please", " asap", "??", " - thank you",
             " :)", " (urgent)", "."]


def load_mix(name: str) -> dict:
    path = MIX_DIR / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no traffic mix {name!r} (looked for {path})")
    mix = json.loads(path.read_text())
    mix.setdefault("name", name)
    return mix


def _u32(*parts) -> int:
    return zlib.crc32(repr(parts).encode()) & 0xFFFFFFFF


@dataclass
class Traffic:
    """What one run sends. ``head`` are the curated rows (class, text);
    ``warm`` the history suffix replayed during set-up; ``window`` the
    timed requests (offset seconds from the window start, text, class).
    A novel request has a class no other request shares (>= n_classes).
    """
    head: List[Tuple[int, str]]
    warm: List[Tuple[str, int]]
    window: List[Tuple[float, str, int]]
    n_classes: int
    rate: float
    history: int = 0
    lengths: dict = field(default_factory=dict)

    def judge_class(self, c: int, static_rows: int) -> int:
        """The class a request carries to the judge: its class's curated
        row (the static tier's class ids are row numbers), or an id no
        row of a tier of ``static_rows`` rows (at least the head) has."""
        if not hasattr(self, "_row"):
            self._row = {k: r for r, (k, _) in enumerate(self.head)}
        return self._row.get(c, max(static_rows, len(self.head)) + c)


class _Universe:
    """Class texts and phrasings, fixed by the mix's structure seed."""

    def __init__(self, mix: dict):
        self.mix = mix
        rng = np.random.default_rng(int(mix["structure_seed"]))
        words = set()
        while len(words) < int(mix.get("lexicon", 6000)):
            n = int(rng.integers(1, 4))
            words.add("".join(_CONS[rng.integers(len(_CONS))]
                              + _VOWELS[rng.integers(len(_VOWELS))]
                              for _ in range(n))
                      + (_CONS[rng.integers(len(_CONS))]
                         if rng.random() < 0.5 else ""))
        self.lex = sorted(words)
        n_cls = int(mix["classes"])
        n_top = int(mix["topics"])
        tw = int(mix.get("topic_words", 40))
        self.topic_words = [rng.choice(len(self.lex), tw, replace=False)
                            for _ in range(n_top)]
        self.topic = rng.integers(0, n_top, n_cls)
        lo, hi = int(mix["chars_lo"]), int(mix["chars_hi"])
        # heavy-tailed class lengths: log-normal, clipped to the range
        ln = np.exp(rng.normal(math.log(float(mix["chars_median"])),
                               float(mix["chars_sigma"]), n_cls))
        self.target = np.clip(ln, lo, hi).astype(int)
        self.n_phr = rng.integers(int(mix["min_phrasings"]),
                                  int(mix["max_phrasings"]) + 1, n_cls)
        n_conf = int(float(mix["confusable_frac"]) * n_cls)
        self.conf_src = np.full(n_cls, -1)
        if n_conf:
            dup = rng.choice(n_cls, n_conf, replace=False)
            self.conf_src[dup] = rng.integers(0, n_cls, n_conf)
        self._base: dict = {}

    def _words(self, rng, topic: int, n: int) -> List[str]:
        tw = self.topic_words[topic]
        out = []
        for _ in range(n):
            if rng.random() < 0.6:
                out.append(self.lex[tw[rng.integers(len(tw))]])
            else:
                out.append(self.lex[rng.integers(len(self.lex))])
        return out

    def base(self, c: int) -> List[str]:
        """The class's canonical word list (question, then context)."""
        if c in self._base:
            return self._base[c]
        src = int(self.conf_src[c])
        if src >= 0 and src != c:
            # confusable: another class's text with a few words swapped
            words = list(self._plain(src))
            rng = np.random.default_rng(_u32("conf", c))
            k = min(int(self.mix.get("confusable_swaps", 2)), len(words))
            for i in rng.choice(min(len(words), 10), k, replace=False):
                words[int(i)] = self._words(rng, int(self.topic[c]), 1)[0]
        else:
            words = self._plain(c)
        self._base[c] = words
        return words

    def _plain(self, c: int) -> List[str]:
        rng = np.random.default_rng(_u32("cls", c))
        words: List[str] = []
        n = 0
        target = int(self.target[c])
        while n < target:
            w = self._words(rng, int(self.topic[c]), 1)[0]
            words.append(w)
            n += len(w) + 1
        return words

    def phrasing(self, c: int, p: int) -> str:
        """Phrasing ``p`` of class ``c``: the canonical text (p = 0) or
        a deterministic edit of it (filler prefix and suffix, a word of
        the question swapped or dropped)."""
        words = list(self.base(c))
        if p == 0:
            return " ".join(words)
        rng = np.random.default_rng(_u32("phr", c, p))
        edits = int(self.mix.get("phrasing_edits", 2))
        for _ in range(int(rng.integers(1, edits + 1))):
            if len(words) > 2 and rng.random() < 0.5:
                i = int(rng.integers(0, min(len(words), 8)))
                words[i] = self._words(rng, int(self.topic[c]), 1)[0]
            elif len(words) > 3:
                del words[int(rng.integers(0, min(len(words), 8)))]
        text = " ".join(words)
        text = _PREFIXES[int(rng.integers(len(_PREFIXES)))] + text \
            + _SUFFIXES[int(rng.integers(len(_SUFFIXES)))]
        if rng.random() < 0.2:
            text = text.capitalize()
        return text

    def novel(self, k: int) -> str:
        """A one-off prompt no other request repeats."""
        rng = np.random.default_rng(_u32("novel", k))
        lo, hi = int(self.mix["chars_lo"]), int(self.mix["chars_hi"])
        target = int(rng.integers(lo, hi + 1))
        words: List[str] = []
        n = 0
        while n < target:
            w = self.lex[rng.integers(len(self.lex))]
            words.append(w)
            n += len(w) + 1
        return " ".join(words)


def generate(mix: dict, seed: int, seconds: float,
             rate: float | None = None) -> Traffic:
    """The traffic of one run: ``rate`` (default: the mix's) requests
    per second for ``seconds``, after the mix's history prefix. The
    history, and so the curated head and the warm tier, is the same for
    every seed; the seed orders the window's requests and gaps."""
    rate = float(mix["rate_per_s"] if rate is None else rate)
    uni = _Universe(mix)
    srng = np.random.default_rng(int(mix["structure_seed"]) + 1)
    n_cls = int(mix["classes"])
    n_hist = int(mix["history_requests"])
    n_win = max(1, int(round(rate * seconds)))
    ranks = np.arange(1, n_cls + 1, dtype=np.float64)
    probs = ranks ** -float(mix["zipf_s"])
    probs /= probs.sum()
    perm = srng.permutation(n_cls)

    def draw(n):
        cls = perm[srng.choice(n_cls, size=n, p=probs)]
        kc = uni.n_phr[cls].astype(np.float64)
        phr = np.minimum(np.floor(kc * srng.random(n) ** float(
            mix["phrasing_zipf"])), kc - 1).astype(np.int64)
        novel = srng.random(n) < float(mix.get("novel_share", 0.0))
        return cls, phr, novel

    h_cls, h_phr, h_nov = draw(n_hist)
    w_cls, w_phr, w_nov = draw(n_win)
    gaps = srng.exponential(1.0, n_win)
    rng = np.random.default_rng(int(seed) % (2 ** 63))
    order = rng.permutation(n_win)
    w_cls, w_phr, w_nov = w_cls[order], w_phr[order], w_nov[order]
    g = gaps[rng.permutation(n_win)]

    def request(cls, phr, nov, i, k) -> Tuple[str, int]:
        if nov[i]:
            return uni.novel(k), n_cls + k
        c = int(cls[i])
        return uni.phrasing(c, int(phr[i])), c

    # the curated head (paper §4.1): the classes covering ``coverage``
    # of the history, each by its shortest phrasing seen there
    seen = h_cls[~h_nov]
    classes, counts = np.unique(seen, return_counts=True)
    top = np.argsort(-counts, kind="stable")
    cum = np.cumsum(counts[top]) / max(len(seen), 1)
    take = int(min(np.searchsorted(cum, float(mix["coverage"])) + 1,
                   len(classes)))
    head_set = set(int(c) for c in classes[top[:take]])
    best: dict = {}
    for i in range(n_hist):
        c = int(h_cls[i])
        if h_nov[i] or c not in head_set:
            continue
        text = uni.phrasing(c, int(h_phr[i]))
        if c not in best or len(text) < len(best[c]):
            best[c] = text
    head = sorted(best.items())

    n_warm = min(int(mix["warm_requests"]), n_hist)
    warm = [request(h_cls, h_phr, h_nov, i, i)
            for i in range(n_hist - n_warm, n_hist)]
    t = np.cumsum(g) - g[0]
    t = t * (seconds / (t[-1] + g[-1])) if n_win > 1 else t
    window = [(float(t[k]), *request(w_cls, w_phr, w_nov, k,
                                     n_hist + int(order[k])))
              for k in range(n_win)]
    lens = [len(p) for _, p, _ in window]
    return Traffic(head=head, warm=warm, window=window, n_classes=n_cls,
                   rate=rate, history=n_hist,
                   lengths={"min": min(lens), "max": max(lens),
                            "mean": float(np.mean(lens))})
