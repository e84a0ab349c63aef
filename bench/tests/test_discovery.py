"""Cells, configurations, traffic mixes and metrics are found by name: a
new file of each kind is picked up with no edit to any file already
there."""
import json
import shutil

import pytest

from bench.lib import spec


@pytest.fixture
def tree(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_every_cell_of_the_benchmark_loads():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.config["num_hidden_layers"] > 0
        assert cell.traffic["kind"] == "markov"
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_new_files_are_found_by_name(tree):
    b = tree / "bench"
    cfg = json.loads((b / "configs" / "qwen2_0_5b.json").read_text())
    (b / "configs" / "qwen2_0_5b-l12.json").write_text(
        json.dumps({**cfg, "num_hidden_layers": 12}))
    (b / "traffic" / "markov_b2s4096.json").write_text(json.dumps(
        {"kind": "markov", "batch": 2, "seq": 4096, "branching": 4}))
    work = json.loads((b / "workloads" / "qwen2-fpft-b8s512.json")
                      .read_text())
    (b / "workloads" / "qwen2-l12-fpft-b2s4096.json").write_text(json.dumps(
        {**work, "config": "qwen2_0_5b-l12", "traffic": "markov_b2s4096"}))
    (b / "metrics" / "window_steps.py").write_text(
        "def read(facts):\n    return len(facts['window_groups'])\n")
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "qwen2-l12-fpft-b2s4096", "config": "qwen2_0_5b-l12",
         "traffic": "markov_b2s4096", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "window_steps", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "step (core/strategy.py)",
         "moves": "train_tokens_per_s",
         "workloads": ["qwen2-l12-fpft-b2s4096"]})

    cell = spec.cell("qwen2-l12-fpft-b2s4096", bench, b)
    assert cell.config["num_hidden_layers"] == 12
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (2, 4096)
    # a metric with a list of cells applies to those cells only
    assert [m["name"] for m in cell.per_layer] == ["window_steps"]
    read = spec.metric_reader("window_steps", b)
    assert read({"window_groups": [None, None]}) == 2


def test_a_cell_file_that_disagrees_with_the_benchmark_is_refused(tree):
    b = tree / "bench"
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "other"
    with pytest.raises(ValueError):
        spec.cell(bench["workloads"][0]["name"], bench, b)
    with pytest.raises(KeyError):
        spec.cell("no-such-cell", bench, b)
