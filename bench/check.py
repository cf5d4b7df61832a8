"""The comparison that decides ``correct``.

Once the window has closed and the program's device state is freed,
the reference (the module the configuration names under ``reference``,
loaded by ``common.reference``) is run over what the timed path
produced:

- the batches drawn from the seed while the window ran, each with the
  dynamic tier it was served against (taken inside the policy's lock,
  as its lookup read it and as its inserts left it): every request's
  embedding, its static and dynamic top-1, the decision at ``tau`` and
  the answer served;
- a sample of the backend rows the window served, drawn from the seed
  and always holding the longest prompt: the served tokens, against a
  float32 forward of the backend over each prompt and its tokens.

The reference decides each request of a batch in order, as the policy
does: static hit at ``tau``, else a dynamic hit at ``tau`` over the
tier before the batch (less the rows the batch's misses overwrote,
plus the batch's earlier misses), else a miss. The tier's rows are
embedded again from the texts whose served embeddings they hold.

Four numbers, each beside its limit from the configuration's
``limits``:

- ``embed_err``: the largest gap, over the requests checked, between a
  component of the served embedding and the reference's;
- ``score_err``: the largest gap between a served similarity and the
  reference's score of the same decision;
- ``decision_errors``: requests whose tier, row or answer differs from
  the reference's, where neither a runner-up nor ``tau`` lies within
  ``decision_margin`` of the reference's scores, and batches whose
  inserts do not match their misses;
- ``logit_gap``: the widest gap by which a served token's logit lies
  below the reference's best at its position.

With ``--control 1`` the control takes the program's place: the
reference in the next precision down (the module's ``CONTROL``) is read
over the same requests and tokens, its numbers are held to the same
limits and decide ``correct``, which must come out false. The
program's numbers are kept beside them under ``program``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from bench import common

HITS = ("dynamic", "rewritten")
EOS, BOS, OFFSET = 2, 1, 3


@dataclass
class Decision:
    kind: str            # static | dynamic | backend
    sim: float           # the score the decision was taken on
    s_static: float
    s2_static: float
    a_static: int
    s_dyn: float
    s2_dyn: float
    dyn_text: Optional[str]
    dyn_slot: int        # -1: a row this batch inserted


def run(conf: dict, dep: dict, mix: dict, tr, got: dict, args) -> dict:
    import time
    t0 = time.monotonic()
    limits = {k: float(v) for k, v in conf["limits"].items()}
    R = common.reference(conf)
    modes = (R.FP32, R.CONTROL) if args.control else (R.FP32,)
    ctx = _Context(R, dep, tr, got, limits)
    nums = {m: {"embed_err": 0.0, "score_err": 0.0, "decision_errors": 0}
            for m in modes}
    counts = {"batches": 0, "requests": 0, "near_tau": 0}
    for s in got["samples"]:
        ctx.check_batch(s, modes, nums, counts)
    t_cache = time.monotonic() - t0
    rng = np.random.default_rng([int(args.seed) % 2 ** 63, 2])
    gaps = _check_backend(R, dep, got["engine_calls"], rng, modes,
                          int(mix.get("check_backend_rows", 48)),
                          int(args.seed))
    counts["tokens"] = gaps.pop("n", 0)
    counts["seconds_cache"] = t_cache
    counts["seconds_backend"] = time.monotonic() - t0 - t_cache
    for m in modes:
        nums[m]["logit_gap"] = gaps.get(m, 0.0)
    # the numbers compared are those the configuration gives a limit,
    # read from the program or, with --control 1, from the control
    judged = nums[R.CONTROL] if args.control else nums[R.FP32]
    keys = [k for k in judged if k in limits]
    ok = counts["requests"] > 0 and counts["tokens"] > 0 and all(
        judged[k] <= limits[k] for k in keys)
    out = {"correct": bool(ok),
           "numbers": {k: [judged[k], limits[k]] for k in keys},
           "counts": counts, "unchecked": {
               k: v for k, v in judged.items() if k not in limits}}
    if args.control:
        out["program"] = {k: nums[R.FP32][k] for k in keys}
    return out


class _Context:
    def __init__(self, R, dep, tr, got, limits):
        self.R = R
        self.tau = float(dep["tau"])
        self.margin = limits["decision_margin"]
        self.emb = self.R.Embedder(d_out=int(dep["embedding_dim"]))
        self.static_rows = int(dep["static_rows"])
        self.head = [t for _, t in tr.head]
        self.row_of_cls = {c: k for k, (c, _) in enumerate(tr.head)}
        self.cls_of = {p: c for p, c in tr.warm}
        self.cls_of.update({p: c for _, p, c in tr.window})
        self.emb_of = got["emb_of"]
        known = list(self.emb_of.items())
        self.texts = [p for p, _ in known]
        self.keys = self.R.normalize(np.stack([v for _, v in known]))
        self.tiers: dict = {}
        self._static: dict = {}
        self._slot_emb: dict = {}
        self.all_prompts = [p for s in got["samples"] for p in s["prompts"]]

    def tier(self, mode):
        if mode not in self.tiers:
            self.tiers[mode] = self.R.static_tier(
                self.emb(self.head, mode), self.static_rows)
        return self.tiers[mode]

    def answer_static(self, row: int) -> str:
        H = len(self.head)
        return f"[curated] {self.head[row]}" if row < H \
            else f"[curated] synthetic-{row - H}"

    def answer_promoted(self, text) -> Optional[str]:
        """A promoted row serves the curated answer of the class of the
        prompt that was promoted."""
        r = self.row_of_cls.get(self.cls_of.get(text))
        return None if r is None else f"[curated] {self.head[r]}"

    def slot_texts(self, rows):
        """The text whose served embedding each tier row holds (None
        where no served embedding matches)."""
        if len(rows) == 0:
            return []
        best, arg, _ = self.R.top2(self.R.normalize(rows), self.keys)
        return [self.texts[int(a)] if b >= 1.0 - 1e-4 else None
                for b, a in zip(best, arg)]

    def static_top2(self, prompts, mode):
        """Static top-1 of every sampled request, in one pass."""
        if mode not in self._static:
            V = self.emb(prompts, mode)
            self._static[mode] = (V, *self.R.top2(V, self.tier(mode), mode))
        return self._static[mode]

    def decide(self, prompts, V, stat, mode, slots, D, texts, new):
        """The reference's decisions over one batch, in row order."""
        ss, sa, s2 = stat
        out, ins = [], []
        for i in range(len(prompts)):
            gone = set(new[:len(ins)].tolist())
            keep = np.array([int(x) not in gone for x in slots], bool)
            cand = [D[keep]] + ([V[ins]] if ins else [])
            C = np.concatenate(cand) if len(cand) > 1 else cand[0]
            ds, da, d2 = self.R.top2_host(V[i:i + 1], C, mode)
            j = int(da[0])
            kept = np.nonzero(keep)[0]
            if j < len(kept):
                slot, text = int(slots[kept[j]]), texts[kept[j]]
            else:
                slot, text = -1, prompts[ins[j - len(kept)]] if ins \
                    else None
            if ss[i] >= self.tau:
                kind, sim = "static", float(ss[i])
            elif ds[0] >= self.tau:
                kind, sim = "dynamic", float(ds[0])
            else:
                kind, sim = "backend", float(ds[0])
                ins.append(i)
            out.append(Decision(kind, sim, float(ss[i]), float(s2[i]),
                                int(sa[i]), float(ds[0]), float(d2[0]),
                                text, slot))
        return out

    def check_batch(self, s, modes, nums, counts):
        prompts, served = s["prompts"], s["served"]
        before, after = s["before"], s["after"]
        valid = np.nonzero(before["valid"])[0]
        texts = self.slot_texts(before["emb"][valid])
        known = np.array([t is not None for t in texts], bool)
        slots = valid[known]
        texts = [t for t in texts if t is not None]
        origin = before["static_origin"]
        # the batch's inserts, in row order (each miss takes one row)
        new = np.nonzero(after["written_at"] != before["written_at"])[0]
        new = new[np.argsort(after["written_at"][new], kind="stable")]
        n_miss = sum(r[0] == "backend" for r in served)
        counts["batches"] += 1
        counts["requests"] += len(prompts)
        Vp = self.R.normalize(np.stack([self.emb_of[p] for p in prompts]))
        lo = counts["requests"] - len(prompts)
        ref = None
        for m in modes:
            Vall, sa_, sb_, sc_ = self.static_top2(self.all_prompts, m)
            sl = slice(lo, lo + len(prompts))
            V = Vall[sl]
            D = self.slot_emb(texts, m)
            dec = self.decide(prompts, V, (sa_[sl], sb_[sl], sc_[sl]), m,
                              slots, D, texts, new)
            if m == self.R.FP32:
                ref, Vref = dec, V
                nums[m]["embed_err"] = max(nums[m]["embed_err"],
                                           float(np.abs(Vp - Vref).max()))
                if len(new) != n_miss or not known.all():
                    nums[m]["decision_errors"] += 1
                got = [(r[0], float(r[2]), r[3], bool(r[1]))
                       for r in served]
            else:
                nums[m]["embed_err"] = max(nums[m]["embed_err"],
                                           float(np.abs(V - Vref).max()))
                got = [(d.kind, d.sim, self._answer_of(d, origin),
                        d.kind == "static" or (
                            d.dyn_slot >= 0 and bool(origin[d.dyn_slot])))
                       for d in dec]
            self.compare(ref, got, nums[m], counts, m == self.R.FP32)

    def slot_emb(self, texts, mode):
        """Reference embeddings of tier-row texts, each embedded once."""
        cache = self._slot_emb.setdefault(mode, {})
        todo = sorted({t for t in texts if t not in cache})
        for t, v in zip(todo, self.emb(todo, mode)):
            cache[t] = v
        d = self.keys.shape[1]
        return np.stack([cache[t] for t in texts]) if texts \
            else np.zeros((0, d), np.float32)

    def _answer_of(self, d: Decision, origin):
        if d.kind == "static":
            return self.answer_static(d.a_static)
        if d.kind == "dynamic" and d.dyn_slot >= 0 \
                and origin[d.dyn_slot]:
            return self.answer_promoted(d.dyn_text)
        return None

    def compare(self, ref, got, nums, counts, count_near):
        tau, m = self.tau, self.margin
        for want, (kind, sim, ans, from_static) in zip(ref, got):
            kind = "dynamic" if kind in HITS else kind
            near = abs(want.s_static - tau) <= m or (
                want.kind != "static" and abs(want.s_dyn - tau) <= m)
            if near:
                counts["near_tau"] += count_near
                continue
            if kind != want.kind:
                nums["decision_errors"] += 1
                continue
            if np.isfinite(sim) and np.isfinite(want.sim):
                nums["score_err"] = max(nums["score_err"],
                                        abs(sim - want.sim))
            if kind == "static" and want.s_static - want.s2_static > m:
                if ans != self.answer_static(want.a_static):
                    nums["decision_errors"] += 1
            elif kind == "dynamic" and from_static \
                    and want.s_dyn - want.s2_dyn > m:
                if ans != self.answer_promoted(want.dyn_text):
                    nums["decision_errors"] += 1


def _check_backend(R, dep, calls, rng, modes, n_rows: int, seed: int):
    """Reference logits over a sample of the served backend rows."""
    be = dep["backend"]
    max_len, max_new = int(be["max_len"]), int(be["max_new_tokens"])
    rows = []
    for c in calls:
        n = max(common.in_len(p, max_len, max_new) for p in c["prompts"])
        toks = np.stack(c["tokens"])           # (steps, batch)
        for b, p in enumerate(c["prompts"]):
            served = []
            for t in toks[:max_new, b]:
                served.append(int(t))
                if int(t) == EOS:              # EOS ends the answer
                    break
            rows.append((p, n, served))
    if not rows:
        return {"n": 0}
    longest = max(range(len(rows)), key=lambda i: len(rows[i][0].encode()))
    rest = [i for i in range(len(rows)) if i != longest]
    k = min(n_rows - 1, len(rest))
    pick = [longest] + sorted(rng.choice(rest, k, replace=False).tolist()
                              if k else [])
    seqs, starts, served = [], [], []
    for i in pick:
        p, n, sv = rows[i]
        ids = [BOS] + [b + OFFSET for b in p.encode()] + [EOS]
        ids = ids[:n] + [0] * max(0, n - len(ids))
        seqs.append(ids + sv[:-1])
        starts.append(n - 1)
        served.append(sv)
    from bench.child import weight_seed
    w = R.lm_weights(be, weight_seed(seed))
    gaps = R.lm_gaps(be, w, seqs, starts, served, modes)
    out = {m: float(np.max(g)) for m, g in gaps.items()}
    out["n"] = int(sum(len(s) for s in served))
    return out
