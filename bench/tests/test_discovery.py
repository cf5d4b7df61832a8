"""The harness finds a configuration, a traffic mix and a per-layer
metric by name: a later change adds files and edits none."""
import json
import shutil
from pathlib import Path

from bench import common, run, traffic

BENCH = Path(__file__).resolve().parents[1]


def test_new_files_are_found(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".cache", "tests", "testdata", "__pycache__"))
    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
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
    monkeypatch.setattr(common, "CHECKOUT", tmp_path)
    monkeypatch.setattr(traffic, "MIX_DIR", bench / "traffic")
    monkeypatch.setattr(run, "BENCH", bench)

    wl = common.workload("flat-2m.faq7")
    assert common.config(wl["config"])["deployment"]["static_rows"] == 1 << 21
    assert traffic.load_mix(wl["traffic"])["rate_per_s"] == 7
    assert run._reader("answer_chars")({}) == 42.0
    metric = bm["per_layer"][-1]
    assert run._applies(metric, wl, common.benchmark())
    assert not run._applies(metric, common.workload("flat.conv"),
                            common.benchmark())
