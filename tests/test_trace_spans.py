"""The program's own spans and scopes, read back from a profiler trace.

The grouped step and the FPFT step mark their host work with
``jax.profiler.TraceAnnotation`` spans named ``repro.*`` (on the profiler's
clock, beside the device's events) and their optimizer update with the
``update`` name scope; the benchmark's trace reduction reads both."""
import glob
import os

import jax
import pytest

from conftest import make_batch, tiny_dense_cfg
from repro.core import HiFTConfig, make_runner

# a revisit's spans inside repro.train_step, in the order the step runs them
REVISIT = ["repro.split", "repro.bundle_fetch", "repro.place_inputs",
           "repro.dispatch", "repro.bundle_offload", "repro.write_back",
           "repro.release"]


def _runner(strategy: str, **kw):
    return make_runner(tiny_dense_cfg(n_layers=2, ce_chunk=0), strategy,
                       optimizer="adamw", seed=0, **kw)


def _hift():
    return _runner("hift", hift=HiFTConfig(m=2))     # two groups


def _losses(runner, steps: int) -> list:
    return [float(runner.train_step(make_batch(runner.cfg, batch=2, seq=16,
                                               seed=s)))
            for s in range(steps)]


def _traced(runner, steps: int, trace_dir: str) -> tuple:
    """The losses of ``steps`` steps run under the profiler, and the
    ``[name, start_ns, duration_ns, stats]`` of every ``repro.`` span."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # the spans, not every Python call
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        losses = _losses(runner, steps)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    spans = sorted(([ev.name, int(ev.start_ns), int(ev.duration_ns),
                     dict(ev.stats)]
                    for plane in data.planes for line in plane.lines
                    for ev in line.events if ev.name.startswith("repro.")),
                   key=lambda e: (e[1], -e[2]))
    return losses, spans


def _inside(spans: list, parent: list) -> list:
    _, ps, pd, _ = parent
    return [e for e in spans if e is not parent
            and ps <= e[1] and e[1] + e[2] <= ps + pd]


@pytest.fixture(scope="module")
def hift_run(tmp_path_factory):
    """One sweep and one revisit with the profiler off (it compiles each
    group's step), then the same steps again from the same start with it
    on: steps are pure, so the start state stays valid."""
    runner = _hift()
    start, steps = runner.state, runner.k + 1
    untraced = _losses(runner, steps)
    runner.state = start
    losses, spans = _traced(runner, steps,
                            str(tmp_path_factory.mktemp("trace")))
    steps_spans = [e for e in spans if e[0] == "repro.train_step"]
    assert [e[3]["step"] for e in steps_spans] == list(range(steps))
    return runner, (untraced, losses), spans, steps_spans


def test_a_revisit_nests_its_spans_in_the_order_of_the_step(hift_run):
    runner, _, spans, steps = hift_run
    revisit = steps[-1]
    inner = _inside(spans, revisit)
    assert [e[0] for e in inner] == REVISIT
    gi = runner.strategy._order_at(runner.state)[0]
    assert {e[3]["group"] for e in inner[:-1]} == {gi}
    # one after the other: no span of the step overlaps the next
    assert all(a[1] + a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_a_first_visit_fetches_no_bundle(hift_run):
    runner, _, spans, steps = hift_run
    first = [n for n in REVISIT if n != "repro.bundle_fetch"]
    for parent in steps[:runner.k]:
        assert [e[0] for e in _inside(spans, parent)] == first


def test_fetch_and_offload_carry_the_group_and_its_bundle_bytes(hift_run):
    runner, _, spans, _ = hift_run
    moved = [e for e in spans
             if e[0] in ("repro.bundle_fetch", "repro.bundle_offload")]
    assert len(moved) == runner.k + 2     # k offloads, then fetch + offload
    for _, _, _, stats in moved:
        bundle = runner.state.opt_state[str(stats["group"])]
        assert stats["bytes"] == sum(x.nbytes
                                     for x in jax.tree.leaves(bundle))
        assert stats["bytes"] == runner.strategy._bundle_bytes[stats["group"]]


def test_offloads_say_whether_they_wrote_in_place(hift_run):
    """Every offload span carries ``in_place``: 0 here, where the CPU
    backend keeps a bundle where it is and no store runs."""
    runner, _, spans, _ = hift_run
    offloads = [e[3] for e in spans if e[0] == "repro.bundle_offload"]
    assert len(offloads) == runner.k + 1
    assert [stats["in_place"] for stats in offloads] == [0] * len(offloads)


def test_losses_are_bit_identical_with_the_profiler_on_and_off(hift_run):
    _, (untraced, traced), _, _ = hift_run
    assert traced == untraced


@pytest.fixture(scope="module")
def fpft_run(tmp_path_factory):
    runner = _runner("fpft")
    _, spans = _traced(runner, 2, str(tmp_path_factory.mktemp("trace")))
    return runner, spans


def test_fpft_marks_its_step_and_dispatch_and_moves_no_bundle(fpft_run):
    _, spans = fpft_run
    inner = ["repro.dispatch", "repro.release"]
    assert [e[0] for e in spans] == (["repro.train_step"] + inner) * 2
    for parent in (e for e in spans if e[0] == "repro.train_step"):
        assert [e[0] for e in _inside(spans, parent)] == inner


def test_the_lowered_steps_hold_the_update_scope(hift_run, fpft_run):
    from repro.core.grouping import split_params
    from repro.core.strategy import fpft_step_body

    strat, gi = hift_run[0].strategy, 1
    params = hift_run[0].state.params
    active, frozen = split_params(params, strat.groups[gi])
    fpft = fpft_run[0]
    batch = make_batch(fpft.cfg, batch=2, seq=16)
    fn, _ = strat.build_step(gi)
    text = fn.lower(active, frozen, strat._init_bundle(active), batch,
                    1e-3).as_text(debug_info=True)
    assert "jit(step)/update/" in text
    assert "transpose(" in text           # the backward's own name stack

    body = fpft_step_body(fpft.cfg, fpft.strategy.optimizer)
    text = jax.jit(body).lower(fpft.state.params, fpft.state.opt_state,
                               batch, 1e-3).as_text(debug_info=True)
    assert "jit(step)/update/" in text
