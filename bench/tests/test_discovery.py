"""The harness finds a configuration, its reference module, a traffic
mix and a per-layer metric by name: a later change adds files and edits
none."""
import json
import shutil
from pathlib import Path

from bench import common, reference, run, traffic

BENCH = Path(__file__).resolve().parents[1]


def _copy(tmp_path, monkeypatch) -> Path:
    """The benchmark's files in a checkout of their own."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "tests", "testdata", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    monkeypatch.setattr(common, "CHECKOUT", tmp_path)
    monkeypatch.setattr(traffic, "MIX_DIR", bench / "traffic")
    monkeypatch.setattr(run, "BENCH", bench)
    return bench


def test_new_files_are_found(tmp_path, monkeypatch):
    bench = _copy(tmp_path, monkeypatch)
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs" / "krites-flat.json").read_text())
    cfg["name"] = "krites-flat-2m"
    cfg["deployment"]["static_rows"] = 1 << 21
    (bench / "configs" / "krites-flat-2m.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "conv.json").read_text())
    mix["rate_per_s"] = 7
    (bench / "traffic" / "faq7.json").write_text(json.dumps(mix))
    (bench / "metrics" / "answer_chars.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bm["configs"].append({"name": "krites-flat-2m", "source": "x",
                          "file": "bench/configs/krites-flat-2m.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "flat-2m.faq7",
                            "config": "krites-flat-2m",
                            "traffic": "faq7", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "answer_chars", "unit": "chars",
                            "better": "lower", "source": "program_counter",
                            "layer": "service loop", "moves": "p50_ms",
                            "workloads": ["flat-2m.faq7"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    wl = common.workload("flat-2m.faq7")
    assert common.config(wl["config"])["deployment"]["static_rows"] == 1 << 21
    assert traffic.load_mix(wl["traffic"])["rate_per_s"] == 7
    assert run._reader("answer_chars")({}) == 42.0
    metric = bm["per_layer"][-1]
    assert run._applies(metric, wl, common.benchmark())
    assert not run._applies(metric, common.workload("flat.conv"),
                            common.benchmark())


def test_a_configuration_brings_its_reference(tmp_path, monkeypatch):
    """A configuration of another backend architecture names its own
    reference module; the comparison and ``step_mfu`` take the backend's
    functions and its operation count from there."""
    bench = _copy(tmp_path, monkeypatch)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "reference_toy.py").write_text(
        "from bench.reference import *  # noqa: F401,F403\n\n\n"
        "def backend_ops(be, bw):\n"
        "    return 1e12 * bw[\"rows\"]\n")
    cfg = json.loads((bench / "configs" / "krites-flat.json").read_text())
    cfg["name"] = "toy"
    cfg["reference"] = "bench/reference_toy.py"
    (bench / "configs" / "toy.json").write_text(json.dumps(cfg))
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy", "source": "x",
                          "file": "bench/configs/toy.json",
                          "reduced": [], "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    R = common.reference(common.config("toy"))
    assert Path(R.__file__) == bench / "reference_toy.py"
    assert R.lm_gaps is reference.lm_gaps and R.FP32 == reference.FP32
    flat = common.reference(common.config("krites-flat"))
    assert Path(flat.__file__) == bench / "reference.py"

    child = {"spans": {"serve_batch": [1, 2.0, 3]},
             "lookup": {"batches": [], "static_rows": 0, "d": 64,
                        "capacity": 0},
             "backend_work": {"rows": 3, "prefill_tokens": 48,
                              "decode_tokens": 21, "attn_pairs": 500}}
    ctx = {"child": child, "deployment": cfg["deployment"],
           "peaks": {"bf16_flops_per_s": 1e12}}
    step_mfu = run._reader("step_mfu")
    # 3 rows at 1e12 operations each over 2 s at 1e12 per second
    assert step_mfu({**ctx, "reference": R}) == 150.0
    assert step_mfu({**ctx, "reference": flat}) == 100.0 * \
        reference.backend_ops(cfg["deployment"]["backend"],
                              child["backend_work"]) / 2e12
    assert all(p.read_bytes() == b for p, b in before.items())
