"""Cells, configurations, model families, traffic mixes and metrics are
found by name: a new file of each kind is picked up with no edit to any
file already there."""
import dataclasses
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench.lib import reference as R
from bench.lib import spec
from bench.tests.tiny import tiny, tiny_cell

SEED = 2 ** 31 + 977


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
    # a metric with a list of cells applies to those cells only, one
    # without a list to every cell: the whole step's readings too
    assert [m["name"] for m in cell.per_layer] == [
        "step_mfu", "device_idle_share", "window_steps"]
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s", "peak_hbm_gib", "setup_s"}
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


def test_a_copy_of_the_dense_family_under_another_name_reads_the_same(tree):
    b = tree / "bench"
    shutil.copy(b / "families" / "dense.py", b / "families" / "dense_copy.py")
    path = b / "configs" / "internlm2_1_8b-l16.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "family": "dense_copy"}))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    name = "internlm2-l16-hift-b8s512"
    copy = tiny(spec.cell(name, bench, b))
    assert Path(copy.family.__file__).name == "dense_copy.py"
    got, want = R.replay(copy, SEED), R.replay(tiny_cell(name), SEED)
    for k in ("loss", "grad", "change", "moment"):
        assert got[k] == want[k], k
    assert got["grad_sample"].keys() == want["grad_sample"].keys()
    for k, v in want["grad_sample"].items():
        assert np.array_equal(got["grad_sample"][k], v), k


@pytest.mark.parametrize("family", [None, "no_such_family", "partial"])
def test_a_configuration_without_a_whole_family_is_refused(tree, family):
    b = tree / "bench"
    (b / "families" / "partial.py").write_text(
        "def units(c):\n    return []\n")
    path = b / "configs" / "qwen2_0_5b.json"
    cfg = json.loads(path.read_text())
    del cfg["family"]
    path.write_text(json.dumps(cfg if family is None
                               else {**cfg, "family": family}))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    with pytest.raises(ValueError, match="lacks" if family == "partial"
                       else "families"):
        spec.cell("qwen2-fpft-b8s512", bench, b)


# two stacks of residual SiLU MLP blocks: one leading wide block
# ("first", as a leading dense layer before an expert stack) and a
# narrower stack ("layers")
TWO_STACKS = '''
import math

import jax
import jax.numpy as jnp

from bench.lib.reference import F32, rms


def arch_config(c):
    raise ValueError("the program runs no such model")


def param_shapes(c):
    d, v, n = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    wide, ff = c["first_size"], c["intermediate_size"]
    return {"embed/tok": ((v, d), 0.02),
            "first/w_in": ((1, d, wide), 1 / math.sqrt(d)),
            "first/w_out": ((1, wide, d), 1 / math.sqrt(wide)),
            "layers/w_in": ((n, d, ff), 1 / math.sqrt(d)),
            "layers/w_out": ((n, ff, d), 1 / math.sqrt(ff)),
            "head/final_norm/scale": ((d,), None),
            "head/w": ((d, v), 1 / math.sqrt(d))}


def units(c):
    return ([("embed", None), ("first", 0)]
            + [("layers", i) for i in range(c["num_hidden_layers"])]
            + [("head", None)])


def stacked(c):
    return ("first", "layers")


def loss(c, nm, params, tokens, unit=None, active=None):
    if unit is not None:
        key, i = unit
        params = {**params, key: active if i is None else jax.tree.map(
            lambda full, a: full.astype(a.dtype).at[i].set(a),
            params[key], active)}
    h = params["embed"]["tok"][tokens].astype(nm.act)
    for key in stacked(c):
        for i in range(params[key]["w_in"].shape[0]):
            u = nm.dot("bsd,df->bsf", h, params[key]["w_in"][i])
            u = jax.nn.silu(u.astype(F32)).astype(nm.act)
            h = h + nm.dot("bsf,fd->bsd", u,
                           params[key]["w_out"][i]).astype(nm.act)
    h = rms(h, params["head"]["final_norm"]["scale"], 1e-6, nm.act)
    logits = nm.dot("bsd,dv->bsv", h[:, :-1], params["head"]["w"])
    logits = logits.astype(F32)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


def step_flops(c, batch, seq, group=None):
    return 6 * batch * seq * sum(math.prod(s) for s, _ in
                                 param_shapes(c).values())
'''


def test_a_family_of_two_stacks_names_every_unit(tree):
    b = tree / "bench"
    (b / "families" / "two_stacks.py").write_text(TWO_STACKS)
    (b / "configs" / "two_stacks.json").write_text(json.dumps(
        {"family": "two_stacks", "hidden_size": 32, "first_size": 96,
         "intermediate_size": 48, "num_hidden_layers": 3,
         "vocab_size": 128}))
    work = json.loads((b / "workloads" / "internlm2-l16-hift-b8s512.json")
                      .read_text())
    (b / "workloads" / "two_stacks-hift.json").write_text(
        json.dumps({**work, "config": "two_stacks"}))
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "two_stacks-hift", "config": "two_stacks",
         "traffic": "markov_b8s512", "chips": 1, "why": "test"})
    cell = spec.cell("two_stacks-hift", bench, b)
    cell = dataclasses.replace(cell, traffic={**cell.traffic, "batch": 4,
                                              "seq": 32})
    units = cell.family.units(cell.config)
    assert R.sweep_length(cell) == len(units) == 6
    shapes = cell.family.param_shapes(cell.config)
    assert shapes["first/w_in"][0][2] > shapes["layers/w_in"][0][2]

    out = R.replay(cell, SEED)
    assert len(out["loss"]) == R.first_steps(cell) == 2 * len(units)
    want = {p if i is None else f"{p}[{i}]"
            for key, i in units for p in shapes if p.split("/")[0] == key}
    assert {"first/w_in[0]", "layers/w_out[2]", "head/w"} <= want
    for reading in ("grad", "grad_sample", "change", "moment"):
        assert set(out[reading]) == want, reading
    assert all(np.isfinite(v) and v > 0 for v in out["change"].values())
