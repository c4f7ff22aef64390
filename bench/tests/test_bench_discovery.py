"""The harness finds a configuration, a traffic mix and a metric by name: a
cell added with files of its own runs with no edit to a file that is
there."""
import json
import shutil
from pathlib import Path

import run as harness

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"


def test_added_files_are_found_by_name(tmp_path):
    root = tmp_path
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "traffic").mkdir()
    shutil.copy(FIX / "tiny-ctr-qwen.json", root / "bench" / "configs")
    shutil.copy(FIX / "tiny.hits.json", root / "bench" / "traffic")
    spec = json.loads((FIX / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "fixture_metric", "unit": "req", "better": "higher",
        "source": "program_counter", "layer": "scheduler", "moves": "hit_p50_ms",
        "workloads": ["tiny.hits"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench" / "metrics" / "fixture_metric.py").write_text(
        'UNIT = "req"\n\n\ndef read(run):\n    return len(run.served)\n')
    picked = harness.load_spec(root, "tiny.hits")
    assert picked["config"]["file"] == "bench/configs/tiny-ctr-qwen.json"
    assert (root / picked["config"]["file"]).exists()
    assert (root / "bench" / "traffic" / f"{picked['cell']['traffic']}.json").exists()
    names = [m["name"] for m in harness.metric_names(spec, "tiny.hits", trace=True)]
    assert "fixture_metric" in names
    assert harness.load_reader(root, "fixture_metric").read(
        type("R", (), {"served": [1, 2, 3]})()) == 3
    e2e = [m["name"] for m in harness.metric_names(spec, "tiny.hits", trace=False)]
    assert e2e == ["hit_p50_ms", "setup_s"]
    # a cell that the metric does not list does not report it
    spec["workloads"].append(dict(spec["workloads"][0], name="tiny.other"))
    assert "fixture_metric" not in [
        m["name"] for m in harness.metric_names(spec, "tiny.other", trace=True)]


def test_every_named_file_exists():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and "limits" in cfg
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in spec["end_to_end"] + spec["per_layer"]:
        r = harness.load_reader(ROOT, m["name"])
        assert r.UNIT == m["unit"]
