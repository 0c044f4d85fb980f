"""A new configuration, traffic mix and metric are found by name: files
and entries only, no harness file edited."""

import json
import shutil
from pathlib import Path

from harness import spec

BENCH = Path(__file__).resolve().parents[1]


def test_new_cell_found_by_name(tmp_path):
    root = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (root / sub).mkdir(parents=True)
    shutil.copy(BENCH / "configs" / "qwen3_reference.py",
                root / "configs" / "qwen3_reference.py")
    config = json.loads((BENCH / "configs" / "qwen3_1_7b-dense.json")
                        .read_text())
    config["name"] = "new_model"
    (root / "configs" / "new_model.json").write_text(json.dumps(config))
    (root / "traffic" / "bursty.json").write_text(json.dumps(
        {"kind": "serve", "rate_rps": 3.0, "warmup_s": 1, "trace_s": 1}))
    (root / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return run['records']['x'] * 2\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "run_seconds": 10,
        "workloads": [{"name": "new_model.bursty", "config": "new_model",
                       "traffic": "bursty", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "out_tok_s", "unit": "tokens/s",
             "workloads": ["new_model.bursty"]},
            {"name": "train_tok_s", "unit": "tokens/s",
             "workloads": ["other"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "new_metric", "unit": "%", "moves": "out_tok_s",
             "workloads": ["new_model.bursty"]},
            {"name": "not_here", "unit": "%", "moves": "train_tok_s"}]}))
    cell = spec.load_cell("new_model.bursty", root)
    assert cell.config["name"] == "new_model"
    assert cell.mix["rate_rps"] == 3.0
    assert [m["name"] for m in cell.end_to_end] == ["out_tok_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert cell.reader("new_metric")({"records": {"x": 21}}) == 42
    assert hasattr(cell.reference(), "token_gaps")


def test_every_listed_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
