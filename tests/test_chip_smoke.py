"""chip_smoke.py's phases at a small size on the CPU: the same phases and
checks the chip run uses (reference agreement, judged/approved counts,
promoted hits on the second pass), and its refusal to report anything
without a TPU."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(out):
    return [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]


def test_one_chip_phases_pass_on_cpu(smoke, capsys):
    opts = smoke.parse(["--arch", "qwen3-1.7b-smoke", "--static-rows",
                        "16384", "--capacity", "512", "--nprobe", "256",
                        "--requests", "128"])
    smoke.run_one_chip(opts)
    lines = _lines(capsys.readouterr().out)
    assert [ln["phase"] for ln in lines] == ["flat", "ivf", "segmented",
                                             "fused"]
    for ln in lines:
        assert ln["agreement"] == 1.0
        assert ln["judged"] > 0 and ln["approved"] > 0
        assert ln["pass2_promoted_dynamic_hits"] > 0
        assert ln["engine_compiles"] <= 3
    # the lookup path changes nothing a request sees
    assert len({json.dumps(ln["served_by"], sort_keys=True)
                for ln in lines}) == 1
    assert "seals=0" not in lines[2]["dyn_index"]


def test_reference_flags_a_wrong_top1(smoke):
    import numpy as np
    rng = np.random.default_rng(0)
    E = rng.normal(size=(300, 8)).astype(np.float32)
    V = rng.normal(size=(4, 8)).astype(np.float32)
    ref = smoke.ref_top1(V, E, chunk=128)
    S = V @ E.T
    assert np.array_equal(ref[1], S.argmax(1))
    ok, err = smoke.agree(S.max(1), S.argmax(1), ref)
    assert ok.all() and err <= smoke.TOL
    wrong = (S.argmax(1) + 1) % 300
    ok, _ = smoke.agree(S[np.arange(4), wrong], wrong, ref)
    assert not ok.any()


def test_refuses_without_a_tpu(smoke, capsys, monkeypatch, tmp_path):
    # main() turns the compile cache on first: keep it out of the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert "no TPU found" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
