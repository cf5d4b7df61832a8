"""The backend keys a configuration states are checked against the
program's own configuration, under the published names: every key but
the run's own, with no fixed table in the harness."""
import json
from pathlib import Path

import pytest

from bench import child
from repro import configs
from repro.configs import LM_ARCHS, lm_config

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "krites-flat.json"
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "qk_norm": "qk_norm", "tie_word_embeddings": "tie_embeddings",
          "dtype": "dtype"}
MOE_FIELDS = {"num_experts": "n_experts", "num_experts_per_tok": "top_k",
              "moe_intermediate_size": "d_ff_expert",
              "n_shared_experts": "n_shared_experts"}
ARCHS = sorted(LM_ARCHS) + [f"{a}-smoke" for a in sorted(LM_ARCHS)]


def _backend(block: str) -> dict:
    return json.loads(CONFIG.read_text())[block]["backend"]


@pytest.mark.parametrize("arch", ARCHS)
def test_published_keys_give_back_the_config(arch):
    lm = lm_config(arch)
    keys = child.published_keys(lm)
    want = {k: getattr(lm, a) for k, a in FIELDS.items()}
    if lm.moe is not None:
        want.update({k: getattr(lm.moe, a) for k, a in MOE_FIELDS.items()})
        want["shared_expert_intermediate_size"] = \
            lm.moe.n_shared_experts * lm.moe.d_ff_expert
    assert keys == want


@pytest.mark.parametrize("arch,block", [("qwen3-1.7b", "deployment"),
                                        ("qwen3-1.7b-smoke", "smoke")])
def test_published_keys_are_the_configuration(arch, block):
    be = _backend(block)
    assert be["arch"] == arch
    stated = {k: v for k, v in be.items() if k not in child.RUN_KEYS}
    assert child.published_keys(lm_config(arch)) == stated
    child.check_backend_keys(be, lm_config(arch))


@pytest.mark.parametrize("key,value", [("rope_theta", 10000.0),
                                       ("num_experts", 64)])
def test_a_key_the_program_does_not_match_stops_the_child(key, value):
    be = dict(_backend("deployment"), **{key: value})
    with pytest.raises(SystemExit, match=key):
        child.check_backend_keys(be, lm_config(be["arch"]))


def test_the_programs_own_published_keys_are_preferred(monkeypatch):
    be = _backend("deployment")
    lm = lm_config(be["arch"])
    own = dict(child.published_keys(lm), sliding_window=1024)
    monkeypatch.setattr(configs, "published_keys", lambda _: own,
                        raising=False)
    child.check_backend_keys(dict(be, sliding_window=1024), lm)
    with pytest.raises(SystemExit, match="sliding_window"):
        child.check_backend_keys(dict(be, sliding_window=512), lm)
