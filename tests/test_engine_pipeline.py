"""``LLMEngine``'s pipelined decode loop: the host reads each step's
tokens one step behind the device and dispatches only steps whose tokens
are served. Greedy output must equal a synchronous step-by-step decode
written here from ``transformer.prefill`` / ``decode_step`` and a host
argmax, and a wrapper of ``LLMEngine._sample`` (as the benchmark's
harness installs one) must see each served token position once."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import lm_config
from repro.data.tokenizer import EOS
from repro.models import transformer as tr
from repro.serving.engine import LLMEngine, _bucket

ARCHS = ["qwen3-1.7b-smoke", "qwen2-moe-a2.7b-smoke"]
MAX_LEN, MIN_BATCH = 96, 4
PROMPTS = {
    # 3 rows padded to 4; a 16-token prompt bucket
    "short3": ["hello", "world!", "fix my bike"],
    # 5 rows padded to 8; a 32-token bucket, ragged lengths
    "long5": ["how do i fix my bike chain", "a longer prompt here",
              "quick q: what is rope?", "x", "why is the sky blue?"],
}
_engines: dict = {}


@pytest.fixture(autouse=True)
def fresh():
    tracing.snapshot(reset=True)
    yield


def _engine(arch: str, eos_first: bool = False) -> LLMEngine:
    key = (arch, eos_first)
    if key not in _engines:
        cfg = lm_config(arch)
        params = tr.init_params(cfg, jax.random.PRNGKey(0))
        if eos_first:
            params = _eos_first(cfg, params)
        _engines[key] = LLMEngine(cfg, params, max_len=MAX_LEN,
                                  min_batch=MIN_BATCH)
    return _engines[key]


def _eos_first(cfg, params):
    """Every position's residual stream carries a large constant on
    feature 0 and the EOS column of the unembedding reads it, so every
    row's first token is EOS."""
    p = dict(params)
    p["embed"] = params["embed"].at[:, 0].add(100.0)
    if cfg.tie_embeddings:
        p["embed"] = p["embed"].at[EOS, 0].add(1000.0)
    else:
        p["unembed"] = params["unembed"].at[:, EOS].set(0.0) \
            .at[0, EOS].set(100.0)
    return p


def _reference(eng: LLMEngine, prompts, max_new: int, alter=None):
    """Synchronous greedy decode: each step's tokens are read on the host
    before the next step runs; the served ids of each real row.
    ``alter(position, tokens)`` may edit a position's tokens in place
    before they are served and decoded from."""
    cfg, params = eng.cfg, eng.params
    B, Bp = len(prompts), _bucket(len(prompts), MIN_BATCH)
    in_len = _bucket(max(len(p.encode()) + 2 for p in prompts), 16)
    in_len = min(in_len, MAX_LEN - max_new)
    toks = np.stack([eng.tok.encode(p, max_len=in_len) for p in prompts])
    toks = np.concatenate([toks, np.repeat(toks[:1], Bp - B, 0)])
    prefill = jax.jit(lambda w, t: tr.prefill(cfg, w, t, max_len=MAX_LEN))
    decode = jax.jit(lambda w, c, t: tr.decode_step(cfg, w, c, t))
    logits, cache = prefill(params, jnp.asarray(toks))
    tok = np.argmax(np.asarray(logits), -1).astype(np.int32)
    out = [[] for _ in range(B)]
    done = np.zeros(B, bool)
    for step in range(max_new):
        if alter is not None:
            alter(step, tok)
        for b in range(B):
            if not done[b]:
                out[b].append(int(tok[b]))
                done[b] = tok[b] == EOS
        if done.all() or step == max_new - 1:
            break
        logits, cache = decode(params, cache, jnp.asarray(tok))
        tok = np.argmax(np.asarray(logits), -1).astype(np.int32)
    return out


def _served(eng: LLMEngine, monkeypatch, prompts, max_new: int):
    """Run ``generate_batch``; the texts and the id lists it decoded."""
    ids, decode = [], eng.tok.decode
    monkeypatch.setattr(eng.tok, "decode",
                        lambda o: ids.append(list(o)) or decode(o))
    texts = eng.generate_batch(prompts, max_new_tokens=max_new)
    return texts, ids


def _counter(name: str) -> int:
    return tracing.snapshot()["counters"].get(name, {"n": 0})["n"]


@pytest.mark.parametrize("max_new", [1, 2, 8])
@pytest.mark.parametrize("prompts", sorted(PROMPTS))
@pytest.mark.parametrize("arch", ARCHS)
def test_greedy_matches_synchronous_decode(arch, prompts, max_new,
                                           monkeypatch):
    eng = _engine(arch)
    ps = PROMPTS[prompts]
    steps0 = eng.stats.decode_steps
    texts, ids = _served(eng, monkeypatch, ps, max_new)
    want = _reference(eng, ps, max_new)
    assert ids == want
    assert texts == [eng.tok.decode(o) for o in want]
    steps = eng.stats.decode_steps - steps0
    longest = max(len(o) for o in want)
    # one step per served position after the first, plus at most the
    # one in flight when every row had ended
    unserved = _counter("engine.decode_steps_unserved")
    assert steps == longest - 1 + unserved
    assert unserved == (longest < max_new)
    assert _counter("engine.decode_steps_ahead") == steps


@pytest.mark.parametrize("max_new", [1, 2, 8])
@pytest.mark.parametrize("arch", ARCHS)
def test_rows_ending_together_stop_the_batch(arch, max_new, monkeypatch):
    eng = _engine(arch, eos_first=True)
    ps = PROMPTS["long5"]
    steps0 = eng.stats.decode_steps
    texts, ids = _served(eng, monkeypatch, ps, max_new)
    assert ids == _reference(eng, ps, max_new) == [[EOS]] * len(ps)
    assert texts == [""] * len(ps)
    # only the step dispatched before the prefill's token was read
    steps = eng.stats.decode_steps - steps0
    assert steps == _counter("engine.decode_steps_unserved") \
        == int(max_new > 1) <= 1


@pytest.mark.parametrize("arch", ARCHS)
def test_sample_wrapper_sees_each_served_position(arch, monkeypatch):
    """The benchmark's harness wraps ``_sample``, keeps ``tok.copy()``
    and returns the engine's own array; it later stacks what it kept
    and reads it element by element."""
    eng, max_new = _engine(arch), 8
    ps = PROMPTS["short3"]
    kept, sample = [], eng._sample

    def sample_fn(logits):
        tok = sample(logits)
        kept.append(tok.copy())
        return tok

    monkeypatch.setattr(eng, "_sample", sample_fn)
    steps0 = eng.stats.decode_steps
    _, ids = _served(eng, monkeypatch, ps, max_new)
    assert all(len(o) == max_new for o in ids)      # a full-length batch
    assert len(kept) == 1 + (max_new - 1)
    toks = np.stack(kept)
    assert toks.shape == (max_new, _bucket(len(ps), MIN_BATCH))
    for b, o in enumerate(ids):
        assert [int(t[b]) for t in kept] == o == toks[:, b].tolist()
    steps = eng.stats.decode_steps - steps0
    assert steps == max_new - 1
    assert _counter("engine.decode_steps_ahead") == steps
    assert _counter("engine.decode_steps_unserved") == 0


@pytest.mark.parametrize("arch", ARCHS)
def test_sample_wrapper_edit_is_served_and_decoded_from(arch, monkeypatch):
    """The harness's ``--fault token`` edits a row's token in a copy it
    returns; the engine serves the edit and the next step takes it."""
    eng, max_new, k = _engine(arch), 8, 2
    ps = PROMPTS["short3"]
    V = eng.cfg.vocab_size
    calls, sample = [0], eng._sample

    def sample_fn(logits):
        tok = sample(logits)
        calls[0] += 1
        if calls[0] == k + 1:
            tok = tok.copy()
            tok[0] = (int(tok[0]) + 1) % V
        return tok

    def alter(position, tok):
        if position == k:
            tok[0] = (int(tok[0]) + 1) % V

    monkeypatch.setattr(eng, "_sample", sample_fn)
    steps0 = eng.stats.decode_steps
    _, ids = _served(eng, monkeypatch, ps, max_new)
    want = _reference(eng, ps, max_new, alter)
    assert ids == want != _reference(eng, ps, max_new)
    steps = eng.stats.decode_steps - steps0
    # the edited tokens were read before the step that takes them
    assert _counter("engine.decode_steps_ahead") == steps - 1
