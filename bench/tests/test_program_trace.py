"""The program's spans and name stacks, reduced to the per-layer metrics,
on a small hand-made trace whose numbers are worked out by hand below."""
import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.lib import program as P
from bench.lib import spec
from bench.lib import trace as T

DATA = Path(__file__).parent / "data"
METRICS = ("bundle_exposed_share", "step_host_exposed_share",
           "backward_share", "update_share")

# Window [1000, 3000]; device ops cover [1000,1020] [1400,1900]
# [1950,2100] [2200,2260] [2720,2800] [2950,3000]: 860 ns busy, and the
# gaps [1020,1400] [1900,1950] [2100,2200] [2260,2720] [2800,2950] are
# 1140 ns idle.
#   bundle spans [1150,1350] [1500,2100] [2700,2800] hold 200 + 50 + 20
#   ns of it; the two repro.train_step spans [1060,2390] [2660,2990]
#   hold 340 + 50 + 100 + (130 + 60) + 150 = 830, so 560 outside the
#   bundle spans; 310 lie outside the steps.
#   Busy time by innermost op: the while loop [1400,1900] keeps 300 ns
#   of its own (backward), its body 100 backward and 100 recomputed
#   forward; forward 20 + 100 + 80 = 200, backward 300 + 100 + 50 =
#   450, update 100 (the fused kernel) + 50 = 150, outside the step 60.
IDLE_BUNDLE, IDLE_STEP_HOST, IDLE_OUTSIDE = 270, 560, 310
SPLIT = {"busy": 860, "forward": 200, "backward": 450, "update": 150,
         "outside": 60}


@pytest.fixture
def tr():
    return json.loads((DATA / "trace_program_small.json").read_text())


@pytest.fixture
def small():
    return json.loads((DATA / "trace_small.json").read_text())


def read(name, tr):
    return spec.metric_reader(name)({"trace": tr})


def test_gaps_and_busy_time_of_the_hand_made_trace(tr):
    assert T.busy_ns(tr) == SPLIT["busy"]
    assert T.gaps(tr) == [(1020, 1400), (1900, 1950), (2100, 2200),
                          (2260, 2720), (2800, 2950)]
    assert IDLE_BUNDLE + IDLE_STEP_HOST + IDLE_OUTSIDE == \
        T.window_ns(tr) - T.busy_ns(tr)


def test_idle_time_inside_the_bundle_and_step_spans(tr):
    assert P.bundle_idle_ns(tr) == IDLE_BUNDLE
    assert P.step_host_idle_ns(tr) == IDLE_STEP_HOST
    assert read("bundle_exposed_share", tr) == pytest.approx(13.5)
    assert read("step_host_exposed_share", tr) == pytest.approx(28.0)


def test_each_busy_instant_counts_once_for_the_innermost_op(tr):
    assert P.device_split({"trace": tr}) == SPLIT
    assert read("backward_share", tr) == pytest.approx(100 * 450 / 860)
    assert read("update_share", tr) == pytest.approx(100 * 150 / 860)
    # the while loop alone would count its body twice; innermost() cuts
    # it into its own 300 ns and its body's two 100 ns
    pieces = [(a, b) for a, b, _ in P.innermost(tr["device_scope"], 1400,
                                                1900)]
    assert pieces == [(1400, 1450), (1450, 1550), (1550, 1600),
                      (1600, 1700), (1700, 1900)]


@pytest.mark.parametrize("stack,kind", [
    ("jit(step)/update/fused_adamw_kernel/pallas_call", "update"),
    ("jit(step)/fused_sgdm_kernel/pallas_call", "update"),
    ("jit(step)/transpose(jvp(model))/while/body/dot_general", "backward"),
    ("jit(step)/jvp(model)/checkpoint/dot_general", "forward"),
    ("jit(convert_element_type)/convert_element_type", "outside"),
    ("", "outside")])
def test_a_name_stack_falls_in_one_kind(stack, kind):
    assert P.kind(stack) == kind


def test_idle_gaps_name_the_programs_spans(tr):
    assert P.idle_gaps(tr) == [["bench.loss_readback", 460e-9],
                               ["repro.bundle_fetch", 380e-9],
                               ["repro.train_step", 150e-9],
                               ["repro.write_back", 100e-9],
                               ["repro.bundle_offload", 50e-9]]
    assert P.span_at(tr, 1055) == "bench.train_step g3"
    assert P.span_at(tr, 1005) == "bench.batch"


def test_no_program_spans_or_no_name_stacks_read_none_never_zero(tr):
    bare = {**tr, "program": []}
    assert all(read(m, bare) is None for m in METRICS)
    nameless = {**tr, "device_scope": [[s, d, ""]
                                       for s, d, _ in tr["device_scope"]]}
    assert read("backward_share", nameless) is None
    assert read("update_share", nameless) is None
    assert read("bundle_exposed_share", nameless) == pytest.approx(13.5)
    assert all(spec.metric_reader(m)({"trace": None}) is None
               for m in METRICS)


def test_the_recorded_small_trace_reads_as_before(small, tmp_path,
                                                  monkeypatch):
    """A trace with no program in it (the parent's program): every new
    metric reads None and the older reductions are unchanged."""
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path)
    before = copy.deepcopy(small)
    assert all(read(m, small) is None for m in METRICS)
    assert T.busy_ns(small) == 450 and T.idle_share(small) == \
        pytest.approx(0.55)
    assert T.idle_gaps(small) == [["bench.loss_readback", 300e-9],
                                  ["bench.train_step g0", 250e-9]]
    assert small == before


def test_spans_are_read_from_the_runs_own_xplane(tmp_path, monkeypatch):
    """On a CPU trace: the loader finds the file by its window span, reads
    the program's spans with their stats, and finds no name stacks."""
    monkeypatch.setattr(P, "TRACE_DIR", tmp_path)
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path / "run"),
                             profiler_options=_no_python_tracer())
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("repro.train_step", step=7):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    facts = {"trace": T.load(str(tmp_path / "run"))}
    tr = P.extended(facts)
    assert tr is facts["trace"]
    assert [(n, stats) for n, _, _, stats in tr["program"]] == \
        [("repro.train_step", {"step": 7})]
    assert P.device_split(facts) is None       # no chip, no name stacks
    assert spec.metric_reader("step_host_exposed_share")(facts) is not None
    other = {"trace": {**facts["trace"], "window": [0, 1]}}
    del other["trace"]["program"], other["trace"]["device_scope"]
    assert P.extended(other) is None           # no trace has that window


def _no_python_tracer():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def test_the_new_metrics_are_declared_for_the_cells_that_read_them():
    bench = spec.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["bundle_exposed_share"]["workloads"] == \
        ["internlm2-l16-hift-b8s512"]
    assert entries["backward_share"]["moves"] == "train_tokens_per_s"
    for name in METRICS:
        assert entries[name]["unit"] == "%"
        assert entries[name]["source"] == "device_trace"
