"""The chip process of one benchmark run (started by ``bench/run.py``).

It builds the cell's deployment from the program's public builders,
wired as ``launch/serve.build_service`` wires them, with the curated
rows taken from the traffic's history; warms it up; prints the service
loop's ``ready`` line; and then runs the program's own JSON-lines loop,
``launch/serve._serve_stdio``, on stdin/stdout until the parent sends
``shutdown``. The parent times the requests; this process records the
spans and counters the per-layer metrics read, and after the window
runs the reference comparison that decides ``correct``.

Timing wrappers sit only on the policy's injected dependencies
(``embed_batch_fn``, ``backend_batch_fn``, ``judge_fn``) and on a thin
proxy around ``serve_batch``; with ``--trace 1`` each is also a
``jax.profiler.TraceAnnotation``. No program file is changed. Two
observation hooks feed the reference check: the engine's sampled tokens
(``LLMEngine._sample``) and the dynamic tier each sampled batch saw,
taken inside the policy's own lock by wrappers on the two tier calls it
makes there (``_dyn_topk``, the batch's lookup, and
``_bulk_insert_fn``, its inserts). The policy's lock is left as it is:
promotions overlap the embedder and the static scan as they do in the
program.

Its last stdout line is ``{"child": {...}}``; everything it prints
during set-up goes to stderr.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

from bench import common, traffic  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Recorder:
    """Host-clock spans and counters from the benchmark's wrappers."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.on = False               # only the window is recorded
        self.spans: dict = {}         # name -> [calls, seconds, rows]
        self.compiles = 0
        self.loads = 0                # programs loaded from the cache
        self.sites: list = []         # call sites of compiles in the window
        self.lock = threading.Lock()

    def span(self, name: str, fn, rows: int = 0):
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                t0 = time.perf_counter()
                out = fn()
        else:
            t0 = time.perf_counter()
            out = fn()
        dt = time.perf_counter() - t0
        if self.on:
            with self.lock:
                s = self.spans.setdefault(name, [0, 0.0, 0])
                s[0] += 1
                s[1] += dt
                s[2] += rows
        return out


class TierWatch:
    """The dynamic tier a batch's lookup read and the one its inserts
    produced, recorded by wrappers on the policy's ``_dyn_topk`` and
    ``_bulk_insert_fn``. ``serve_batch`` calls both inside ``dyn_lock``,
    so no promotion lands between the two readings. Only the serving
    thread records, and only while a batch it watches is being served."""

    def __init__(self, policy):
        self._local = threading.local()
        topk, insert = policy._dyn_topk, policy._bulk_insert_fn

        def dyn_topk(dyn, q):
            w = self._watching()
            if w is not None:
                w["before"] = w["after"] = dyn
            return topk(dyn, q)

        def bulk_insert(dyn, *a, **k):
            out = insert(dyn, *a, **k)
            w = self._watching()
            if w is not None:
                w["after"] = out
            return out

        policy._dyn_topk = dyn_topk
        policy._bulk_insert_fn = bulk_insert

    def _watching(self):
        return getattr(self._local, "w", None)

    def watch(self, on: bool) -> dict:
        w = {"before": None, "after": None} if on else None
        self._local.w = w
        return w


class PolicyProxy:
    """What ``_serve_stdio`` drives: the policy, with ``serve_batch``
    spanned and, for batches drawn from the seed, the dynamic tier
    before and after the batch kept for the reference check. The
    program's loop sends whatever has queued as one batch; a call with
    more than the mix's ``max_batch`` rows, whose sizes set-up did not
    compile, is served as consecutive batches of at most that many."""

    def __init__(self, policy, watch: TierWatch, rec: Recorder, rng,
                 p_sample: float, max_samples: int, max_batch: int):
        self._policy = policy
        self._watch = watch
        self._rec = rec
        self._rng = rng
        self._p = p_sample
        self._max = max_samples
        self._max_batch = max_batch
        self.samples: list = []
        self.batches: list = []      # rows of each call in the window
        self.split = 0               # calls served as several batches

    def __getattr__(self, name):
        return getattr(self._policy, name)

    def serve_batch(self, prompts, metas=None):
        mb = self._max_batch
        if len(prompts) > mb:
            self.split += 1
            metas = list(metas) if metas is not None \
                else [None] * len(prompts)
            out = []
            for i in range(0, len(prompts), mb):
                out += self.serve_batch(prompts[i:i + mb], metas[i:i + mb])
            return out
        pol = self._policy
        take = self._rec.on and len(self.samples) < self._max \
            and self._rng.random() < self._p
        seen = self._watch.watch(take)
        try:
            out = self._rec.span("serve_batch",
                                 lambda: pol.serve_batch(prompts, metas),
                                 rows=len(prompts))
        finally:
            self._watch.watch(False)
        if self._rec.on:
            self.batches.append(len(prompts))
        if take and seen["before"] is not None:
            self.samples.append({
                "prompts": list(prompts),
                "before": seen["before"], "after": seen["after"],
                "served": [(r.served_by, bool(r.static_origin),
                            float(r.similarity),
                            None if r.answer is None else str(r.answer))
                           for r in out]})
        return out


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--smoke", type=int, default=0,
                    help="tests only: run on the CPU at the sizes of the "
                         "configuration's 'smoke' block")
    ap.add_argument("--fault", default="",
                    help="tests only: break the timed path "
                         "(token|answer|state)")
    return ap.parse_args(argv)


_T0 = time.monotonic()


def _log(*a):
    print(f"[{time.monotonic() - _T0:8.3f}]", *a, file=sys.stderr,
          flush=True)


def main(argv=None):
    args = parse(argv)
    wl = common.workload(args.workload)
    conf = common.config(wl["config"])
    dep = dict(conf["deployment"])
    if args.smoke:
        dep.update(conf.get("smoke", {}))
    mix = traffic.load_mix(wl["traffic"])
    if args.smoke:
        mix.update(mix.get("smoke", {}))

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(common.JAX_CACHE))
    Path(os.environ["JAX_COMPILATION_CACHE_DIR"]).mkdir(parents=True,
                                                        exist_ok=True)
    from repro.launch.jax_setup import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # every program of the cell stays cached (an environment may cap the
    # cache below what one cell compiles, and its LRU then evicts them)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if not args.smoke and devs[0].platform != "tpu":
        _log(f"child: no TPU: JAX platform is {devs[0].platform!r}")
        sys.exit(3)
    if len(devs) < int(wl["chips"]):
        _log(f"child: the cell needs {wl['chips']} chips, JAX sees "
             f"{len(devs)}")
        sys.exit(3)

    rec = Recorder(bool(args.trace))

    def on_hit(event, **_):
        if event == CACHE_HIT_EVENT:      # a load, not a compile
            rec.compiles -= 1
            rec.loads += 1
    jax.monitoring.register_event_listener(on_hit)

    def on_compile(event, secs, **_):
        if event == COMPILE_EVENT:        # also fires on a cache load
            rec.compiles += 1
            if rec.on:               # where a compile in the window came from
                import traceback
                site = [f"{Path(f.filename).name}:{f.lineno}"
                        for f in traceback.extract_stack()
                        if "/repro/" in f.filename or "/bench/" in f.filename]
                rec.sites.append(" < ".join(site[-3:][::-1]))
    jax.monitoring.register_event_duration_secs_listener(on_compile)

    t_start = time.monotonic()
    real_stdout = sys.stdout
    sys.stdout = sys.stderr           # builders print; the loop owns stdout
    tr = traffic.generate(mix, args.seed, args.seconds, args.rate)
    _log(f"child: traffic {time.monotonic() - t_start:.3f} s")
    svc = build(dep, tr, args, rec)
    _log(f"child: built {time.monotonic() - t_start:.3f} s, "
         f"{rec.compiles} compiles")
    svc["max_batch"] = int(mix["max_batch"])
    warm_up(svc, dep, tr, rec)
    svc["engine_calls"].clear()
    setup_s = time.monotonic() - t_start
    compiles_setup = rec.compiles
    _log(f"child: set-up {setup_s:.3f} s, {compiles_setup} compiles, "
         f"{rec.loads} cache loads, "
         f"head {len(tr.head)} rows, warm {len(tr.warm)} requests, "
         f"pool {vars(svc['policy'].pool.stats)}")

    import numpy as np
    proxy = PolicyProxy(svc["policy"], svc["watch"], rec,
                        np.random.default_rng([int(args.seed) % 2**63, 1]),
                        float(mix.get("check_batch_share", 0.25)),
                        int(mix.get("check_batches", 48)),
                        int(svc["max_batch"]))
    trace_dir = common.CACHE / "trace" / f"{args.workload}.{args.seed}"
    if args.trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: TraceMe events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), create_perfetto_trace=True,
                                 profiler_options=opts)
    from repro.launch.serve import _serve_stdio
    sys.stdout = real_stdout
    pool0 = dict(vars(svc["policy"].pool.stats))
    rec.on = True
    c0 = rec.compiles
    t0 = time.monotonic()
    _serve_stdio(proxy, None, None)
    window_s = time.monotonic() - t0
    rec.on = False
    window_compiles = rec.compiles - c0
    if args.trace:
        jax.profiler.stop_trace()
    sys.stdout = sys.stderr

    pol = svc["policy"]
    pool_stats = {k: v - pool0.get(k, 0)
                  for k, v in vars(pol.pool.stats).items()}
    eng = svc["engine"].stats
    mem = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    pol.pool.stop()
    svc["frontend"].stop()
    out = {
        "setup_s": setup_s, "compiles_setup": compiles_setup,
        "cache_loads_setup": rec.loads,
        "window_compiles": window_compiles, "window_s": window_s,
        "window_compile_sites": sorted(set(rec.sites))[:10],
        "spans": rec.spans, "pool": pool_stats,
        "engine": {"batches": eng.batches, "prefills": eng.prefills,
                   "decode_steps": eng.decode_steps,
                   "generated_tokens": eng.generated_tokens},
        "batch_rows": proxy.batches, "split_calls": proxy.split,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": mem},
        "trace_dir": str(trace_dir) if args.trace else None,
        "lookup": lookup_work(svc, dep, proxy),
        "backend_work": backend_work(svc["engine_calls"], dep),
    }
    check_in = collect_for_check(svc, proxy, tr)
    svc.clear()
    jax.clear_caches()
    del pol, proxy
    gc.collect()
    from bench import check
    t1 = time.monotonic()
    out["check"] = check.run(conf, dep, mix, tr, check_in, args)
    out["check_s"] = time.monotonic() - t1
    real_stdout.write(json.dumps({"child": out}) + "\n")
    real_stdout.flush()


def build(dep: dict, tr, args, rec: Recorder) -> dict:
    """The deployment, wired as ``launch/serve.build_service`` wires it;
    the curated rows are the traffic's head."""
    import functools

    import jax
    import numpy as np
    from repro.configs import lm_config
    from repro.core.judge import OracleJudge
    from repro.core.policy import KritesPolicy
    from repro.core.tiers import CacheConfig
    from repro.embedding.embedder import Embedder
    from repro.launch.serve import build_demo_tier
    from repro.models import transformer as tr_model
    from repro.serving.engine import BatchingFrontend, LLMEngine

    be = dep["backend"]
    lm = lm_config(be["arch"])
    check_backend_keys(be, lm)
    # the weights, drawn from the seed on the device in one jitted call
    params = jax.jit(functools.partial(tr_model.init_params, lm))(
        jax.random.PRNGKey(weight_seed(args.seed)))
    engine = LLMEngine(lm, params=params, max_len=int(be["max_len"]),
                       min_batch=int(be["batch"]))
    frontend = BatchingFrontend(engine, max_batch=int(be["batch"]),
                                max_new_tokens=int(be["max_new_tokens"]))
    d = int(dep["embedding_dim"])
    embed = Embedder(d_out=d)

    head_texts = [t for _, t in tr.head]
    tier, answers, texts, _ = build_demo_tier(
        np.asarray(embed.batch(head_texts)),
        [f"[curated] {t}" for t in head_texts],
        static_rows=int(dep["static_rows"]), index="flat",
        texts=head_texts)
    if dep["lookup"] != "flat":
        raise SystemExit(f"child: lookup path {dep['lookup']!r} is not "
                         f"wired; this harness builds the flat path")
    cfg = CacheConfig(float(dep["tau"]), float(dep["tau"]),
                      sigma_min=float(dep["sigma_min"]),
                      capacity=int(dep["dynamic_capacity"]))
    judge = OracleJudge()
    svc = {"engine": engine, "frontend": frontend, "embed": embed,
           "tier": tier, "backend_stub": False,
           "embedded": [], "engine_calls": []}

    def embed_batch(prompts):
        prompts = list(prompts)
        out = rec.span("embed", lambda: embed.batch(prompts),
                       rows=len(prompts))
        svc["embedded"].append((prompts, out))
        return out

    def backend_batch(prompts):
        if svc["backend_stub"]:
            return [""] * len(prompts)
        return rec.span("backend", lambda: frontend.submit_many(prompts),
                        rows=len(prompts))

    def judge_fn(**ja):
        return rec.span("judge", lambda: judge(**ja))

    policy = KritesPolicy(cfg, tier, answers, embed,
                          backend_fn=frontend.submit, judge_fn=judge_fn,
                          d=d, embed_batch_fn=embed_batch,
                          backend_batch_fn=backend_batch,
                          static_texts=texts,
                          n_workers=int(dep["judge_workers"]))
    svc["policy"] = policy

    # observation hook: the tokens the engine serves, per engine batch
    gen, sample = engine.generate_batch, engine._sample
    calls = svc["engine_calls"]

    def generate_batch(prompts, max_new_tokens=32):
        calls.append({"prompts": list(prompts), "tokens": []})
        return gen(prompts, max_new_tokens)

    def sample_fn(logits):
        tok = sample(logits)
        if args.fault == "token" and len(calls) % 2 == 0:
            tok = np.array(tok)       # a host copy, served in its place
            tok[0] = (int(tok[0]) + 1) % lm.vocab_size
        calls[-1]["tokens"].append(tok.copy())
        return tok

    engine.generate_batch = generate_batch
    engine._sample = sample_fn
    if args.fault == "answer":
        serve_static = policy._serve_static
        policy._serve_static = lambda idx: serve_static(
            (int(idx) + 1) % len(answers))
    if args.fault == "state":         # misses leave the dynamic tier as it was
        policy._bulk_insert_fn = lambda dyn, *a, **k: dyn
    svc["watch"] = TierWatch(policy)
    return svc


RUN_KEYS = ("arch", "max_len", "max_new_tokens", "batch")


def published_keys(lm) -> dict:
    """The program's backend configuration (an ``LMConfig``) under the
    names of the published ``config.json``. Where the program provides
    its own ``repro.configs.published_keys``, ``check_backend_keys``
    takes that one instead, so that keys of a new architecture are
    named where the program defines it."""
    out = {"num_hidden_layers": lm.n_layers, "hidden_size": lm.d_model,
           "num_attention_heads": lm.n_heads,
           "num_key_value_heads": lm.n_kv_heads, "head_dim": lm.head_dim,
           "intermediate_size": lm.d_ff, "vocab_size": lm.vocab_size,
           "rope_theta": lm.rope_theta, "rms_norm_eps": lm.norm_eps,
           "qk_norm": lm.qk_norm, "tie_word_embeddings": lm.tie_embeddings,
           "dtype": lm.dtype}
    if lm.moe is not None:
        m = lm.moe
        out.update(num_experts=m.n_experts, num_experts_per_tok=m.top_k,
                   moe_intermediate_size=m.d_ff_expert,
                   n_shared_experts=m.n_shared_experts,
                   shared_expert_intermediate_size=m.n_shared_experts
                   * m.d_ff_expert)
    return out


def check_backend_keys(be: dict, lm) -> None:
    """Stop unless every key of the configuration's ``backend`` block,
    but the run's own (``RUN_KEYS``), is one the program reports for
    ``lm``, with an equal value."""
    import repro.configs
    have = getattr(repro.configs, "published_keys", published_keys)(lm)
    for key, want in be.items():
        if key in RUN_KEYS:
            continue
        if key not in have:
            raise SystemExit(f"child: backend {be['arch']} does not report "
                             f"{key}, which the configuration states")
        if have[key] != want:
            raise SystemExit(f"child: backend {be['arch']} has {key}="
                             f"{have[key]!r}, the configuration states "
                             f"{key}={want!r}")


def weight_seed(seed: int) -> int:
    """The backend's PRNG seed: any whole number folded into 31 bits."""
    return int(seed) % (2 ** 31 - 1)


def warm_up(svc: dict, dep: dict, tr, rec: Recorder) -> None:
    """Compile every shape the window can use, and bring the dynamic
    tier to the state the traffic's history gives it."""
    import jax.numpy as jnp
    be = dep["backend"]
    pol, frontend = svc["policy"], svc["frontend"]
    max_len, max_new = int(be["max_len"]), int(be["max_new_tokens"])
    # the engine: one batch per prompt-length bucket the window can send
    by_len: dict = {}
    for _, p, _ in tr.window:
        by_len.setdefault(common.in_len(p, max_len, max_new), p)
    for n, p in sorted(by_len.items()):
        frontend.submit_many([p] * int(be["batch"]))
    _log(f"child: engine warm at {sorted(by_len)}: {rec.compiles} compiles")
    # every batch size up to the mix's largest: the embedder and the
    # normalization compile per size, the lookups per power of two
    max_b = int(svc["max_batch"])
    for b in range(1, max_b + 1):
        v = pol._embed_batch([""] * b)
        if common.pow2(b) != b:
            jnp.pad(v, ((0, common.pow2(b) - b), (0, 0)))
    _log(f"child: batch sizes 1..{max_b} warm: {rec.compiles} compiles")
    # the history: served through serve_batch with the backend stubbed,
    # in batches of every power of two up to the largest, so the tier
    # fills as the traffic would fill it and each bucket compiles
    svc["backend_stub"] = True
    rows = int(dep["static_rows"])
    sizes, b = [], 1
    while b <= max_b:
        sizes.append(b)
        b *= 2
    i, k = 0, 0
    while i < len(tr.warm):
        n = sizes[k % len(sizes)]
        chunk = tr.warm[i:i + n]
        pol.serve_batch([p for p, _ in chunk],
                        [{"cls": tr.judge_class(c, rows)} for _, c in chunk])
        i += n
        k += 1
    for n in sizes[k:]:            # buckets the history was too short for
        chunk = (tr.warm * (n // max(len(tr.warm), 1) + 1))[:n]
        pol.serve_batch([p for p, _ in chunk],
                        [{"cls": tr.judge_class(c, rows)} for _, c in chunk])
    pol.pool.drain()
    _log(f"child: history replayed: {rec.compiles} compiles")
    svc["backend_stub"] = False
    # the batch-end scatters at every bucket, with the dtypes
    # serve_batch passes, on the tier value (the result is dropped)
    import numpy as np
    d = int(dep["embedding_dim"])
    for b in sizes:
        v = jnp.zeros((b, d), jnp.float32)
        idx = np.zeros(b, np.int64)
        i32 = np.zeros(b, np.int32)
        pol._bulk_insert_fn(pol.dyn, v, idx, idx, i32, i32, exps=i32)
        pol._touch_many(pol.dyn, idx, np.zeros(b, np.int64))
    # the rare repair path: a batch's insert evicts a row's snapshot
    # best, and the row is taken from the batch's padded matrix (one
    # indexing program per bucket)
    snap = pol.dyn
    for b in sizes:
        pol._snap_best_excluding(snap, jnp.zeros((b, d), jnp.float32)[b - 1],
                                 {0})
    svc["engine_calls"].clear()
    # a few real backend batches through the policy, then drain
    real = [p for _, p, _ in tr.window[:int(be["batch"])]]
    pol.serve_batch(real, [{"cls": -1}] * len(real))
    pol.pool.drain()


def lookup_work(svc: dict, dep: dict, proxy: PolicyProxy) -> dict:
    """What the window's lookups had to read, from shapes: the batch
    sizes, the static tier and the dynamic tier."""
    return {"batches": list(proxy.batches),
            "static_rows": int(svc["tier"].emb.shape[0]),
            "d": int(svc["tier"].emb.shape[1]),
            "capacity": int(dep["dynamic_capacity"])}


def backend_work(calls: list, dep: dict) -> dict:
    """Tokens the window's engine batches had to process: each row's
    prompt as the engine pads it, and each served token but the first
    (which the prefill's logits chose)."""
    be = dep["backend"]
    max_len, max_new = int(be["max_len"]), int(be["max_new_tokens"])
    prefill = decode = rows = 0
    attn = 0          # (query, key) pairs, for the attention's share
    for c in calls:
        if not c["tokens"]:
            continue
        n = max(common.in_len(p, max_len, max_new) for p in c["prompts"])
        for b in range(len(c["prompts"])):
            k = 0
            for t in c["tokens"][:max_new]:
                k += 1
                if int(t[b]) == 2:
                    break
            rows += 1
            prefill += n
            decode += k - 1
            attn += n * (n + 1) // 2 + sum(n + j for j in range(1, k))
    return {"rows": rows, "prefill_tokens": prefill,
            "decode_tokens": decode, "attn_pairs": attn}


def collect_for_check(svc: dict, proxy: PolicyProxy, tr) -> dict:
    """Host copies of what the reference compares, so the program's
    device state can be freed before the reference runs."""
    import jax
    import numpy as np
    samples = []
    for s in proxy.samples:
        b, a = jax.device_get((s["before"], s["after"]))
        samples.append({**s, "before": {
            "emb": np.asarray(b.emb), "valid": np.asarray(b.valid),
            "written_at": np.asarray(b.written_at),
            "static_origin": np.asarray(b.static_origin)},
            "after": {"written_at": np.asarray(a.written_at),
                      "valid": np.asarray(a.valid)}})
    emb_of = {}
    for prompts, out in svc["embedded"]:
        for p, v in zip(prompts, np.asarray(out, np.float32)):
            emb_of[p] = v
    return {"samples": samples, "emb_of": emb_of,
            "engine_calls": [c for c in svc["engine_calls"]
                             if c["tokens"]]}


if __name__ == "__main__":
    main()
