"""The reduction from a trace to metrics, on a small recorded trace."""
import json
from pathlib import Path

import pytest

from bench.lib import trace as T

DATA = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture
def tr():
    return json.loads(DATA.read_text())


def test_union_merges_overlaps_and_clips_to_the_window():
    got = T.union([(900, 1100), (1050, 1200), (1300, 1400), (1950, 2500)],
                  1000, 2000)
    assert got == [[1000, 1200], [1300, 1400], [1950, 2000]]
    assert T.covered_ns([(900, 1100), (1050, 1200)], 1000, 2000) == 200


def test_busy_and_idle_share_count_only_the_ops_line(tr):
    # ops cover [1000,1100] [1150,1300] [1500,1600] [1900,2000]: 450 ns;
    # the module event on its own line does not count
    assert T.busy_ns(tr) == 450
    assert T.window_ns(tr) == 1000
    assert T.idle_share(tr) == pytest.approx(0.55)


def test_top_ops_sum_clipped_time_by_name(tr):
    assert T.top_ops(tr) == [["fusion.1", 300e-9], ["_adamw_kernel", 100e-9],
                             ["convolution.2", 100e-9]]
    assert T.top_ops(tr, n=1) == [["fusion.1", 300e-9]]


def test_idle_gaps_go_to_the_innermost_open_host_span(tr):
    assert T.gaps(tr) == [(1100, 1150), (1300, 1500), (1600, 1900)]
    # gap midpoints: 1125 and 1400 in the train step, 1750 in the loss
    # read-back; the window span itself names nothing
    assert T.idle_gaps(tr) == [["bench.train_step g0", 250e-9],
                               ["bench.loss_readback", 300e-9]][::-1]
    assert T.span_at(tr, 1850) == "bench.batch"
    assert T.span_at(tr, 1020) == "none"


def test_span_window_finds_the_window_span(tr):
    assert T.span_window(tr["host"]) == (1000, 2000)
    with pytest.raises(ValueError):
        T.span_window([["bench.batch", 0, 1]])


def test_metric_readers_on_the_recorded_trace(tr):
    from bench.lib import spec
    dense = spec.family("dense")
    facts = {"trace": tr, "config": spec.config("internlm2_1_8b-l16"),
             "family": dense, "workload": {"policy": "mixed_hi"},
             "traffic": {"batch": 8, "seq": 512},
             "window_groups": [[("head", None)]], "window_s": 1e-6,
             "device_kind": "TPU v5 lite"}
    assert spec.metric_reader("device_idle_share")(facts) == \
        pytest.approx(55.0)
    want = 100 * dense.step_flops(facts["config"], 8, 512,
                                  [("head", None)]) / (1e-6 * 197e12)
    assert spec.metric_reader("step_mfu")(facts) == pytest.approx(want)
    empty = {**facts, "trace": {**tr, "device": []}}
    assert spec.metric_reader("device_idle_share")(empty) is None
    read = spec.metric_reader("resident_hbm_gib")
    assert read({**facts, "resident_bytes": 3 * 2 ** 29}) == 1.5
    assert read(facts) is None
