"""Process-level JAX setup (launch/jax_setup.py), the launcher's arch
resolution, and the engine's bounded set of compiled shapes."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import jax_setup

ROOT = Path(__file__).resolve().parents[1]


def test_default_cache_dir_is_fixed_in_the_repo():
    assert jax_setup.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_lands_in_one_place(tmp_path, env_set):
    """With JAX_COMPILATION_CACHE_DIR set, entries go there and the
    default stays empty; without it they go to the default (redirected
    here so the test leaves the checkout alone)."""
    env_dir, default = tmp_path / "env", tmp_path / "default"
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu",
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = textwrap.dedent(f"""
        from pathlib import Path
        from repro.launch import jax_setup
        jax_setup.DEFAULT_CACHE_DIR = Path({str(default)!r})
        print(jax_setup.enable_compile_cache())
        import jax, jax.numpy as jnp
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    used, unused = (env_dir, default) if env_set else (default, env_dir)
    assert out.stdout.strip() == str(used)
    assert any(used.iterdir())
    assert not unused.exists()


def test_force_cpu_devices_only_on_cpu(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_foo=1 --xla_force_host_platform_device_count=2")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert not jax_setup.force_cpu_devices(8)
    assert os.environ["XLA_FLAGS"].endswith("device_count=2")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert jax_setup.force_cpu_devices(8)
    assert os.environ["XLA_FLAGS"] == \
        "--xla_foo=1  --xla_force_host_platform_device_count=8"


def test_lm_config_names_published_and_smoke_widths():
    from repro.configs import lm_config
    full = lm_config("qwen3-1.7b")
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.d_ff, full.vocab_size, full.dtype) == \
        (28, 2048, 16, 8, 6144, 151936, "bfloat16")
    smoke = lm_config("qwen3-1.7b-smoke")
    assert (smoke.name, smoke.n_layers, smoke.d_model) == \
        ("qwen3-1.7b-smoke", 2, 64)
    with pytest.raises(KeyError):
        lm_config("no-such-arch")
    with pytest.raises(TypeError):
        lm_config("sasrec")


def test_engine_compiles_a_bounded_set_of_shapes():
    """Ragged batches and prompt lengths pad to power-of-two buckets:
    with ``min_batch=8`` a front end's <= 8-row batches share one
    decode program and one prefill program per length bucket."""
    from repro.configs import lm_config
    from repro.serving.engine import LLMEngine
    eng = LLMEngine(lm_config("qwen3-1.7b-smoke"), max_len=96,
                    min_batch=8)
    short, longer = "how do i fix my bike", "quick q: " * 4 + "fix my bike"
    outs = [eng.generate_batch(ps, max_new_tokens=2) for ps in
            ([short], [short, longer], [longer] * 5, [short] * 8)]
    assert [len(o) for o in outs] == [1, 2, 5, 8]
    assert eng.stats.prefills == 16
    assert eng.stats.compiles == 3        # 2 prompt buckets + 1 decode
    assert eng._prefill._cache_size() + eng._decode._cache_size() == 3
