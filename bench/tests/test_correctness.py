"""The check that decides ``correct``, at a size a CPU test can hold.

The harness's look for a chip is skipped; the rest of a run is driven
with the timed path sound, and broken underneath in each way a one-chip
training cell can be broken: a step that returns its state unchanged,
half of each batch left out (the mean taken over the rest) and, where
HiFT revisits its groups, a revisit that starts from a fresh bundle (as
if the offloaded one were lost) or leaves its group unchanged.  The
control, the plain reference computed one precision lower than the cell's
matmuls and put in the program's place, has to fail the check too.
"""
import jax
import pytest

from bench.lib import cell as C
from bench.lib import reference as R
from bench.tests.tiny import tiny_cell

CELLS = ("internlm2-l16-hift-b8s512", "qwen2-fpft-b8s512")
SEED = 2 ** 31 + 977


def _unchanged(runner):
    real = runner.strategy.step

    def step(state, batch):
        _, metrics = real(state, batch)
        return state.replace(step=state.step + 1), metrics
    runner.strategy.step = step


def _half_batch(runner):
    real = runner.strategy.step

    def step(state, batch):
        return real(state, jax.tree.map(lambda x: x[: x.shape[0] // 2],
                                        batch))
    runner.strategy.step = step


def _visited(runner, state) -> str:
    order = state.extra["order"]
    return str(int(order[int(state.step) % len(order)]))


def _revisit_fresh(runner):
    real = runner.strategy.step

    def step(state, batch):
        kept = dict(state.opt_state)
        kept.pop(_visited(runner, state), None)
        return real(state.replace(opt_state=kept), batch)
    runner.strategy.step = step


def _revisit_unchanged(runner):
    real = runner.strategy.step

    def step(state, batch):
        revisit = _visited(runner, state) in state.opt_state
        new, metrics = real(state, batch)
        return (state.replace(step=state.step + 1) if revisit else new,
                metrics)
    runner.strategy.step = step


def _failing(checks: dict) -> list:
    return [k for k, ch in checks.items() if not ch["value"] <= ch["limit"]]


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(name):
    res = C.run(tiny_cell(name), SEED, 0.2, False, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_step_is_not_correct(name, fault):
    res = C.run(tiny_cell(name), SEED, 0.2, False, require_tpu=False,
                plant=fault)
    assert not res["correct"]
    assert _failing(res["checks"]), res["checks"]


@pytest.mark.parametrize("fault", [_revisit_fresh, _revisit_unchanged],
                         ids=["revisit_fresh", "revisit_unchanged"])
def test_a_broken_revisit_is_not_correct(fault):
    res = C.run(tiny_cell(CELLS[0]), SEED, 0.2, False, require_tpu=False,
                plant=fault)
    assert not res["correct"]
    assert _failing(res["checks"]), res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name):
    cell = tiny_cell(name)
    c, w, mix = cell.config, cell.workload, cell.traffic
    ref = R.replay(c, w, mix, SEED)
    low = R.replay(c, w, mix, SEED, precision=R.CONTROL[w["matmuls"]])
    checks = {k: {"value": v, "limit": w["limits"][k]}
              for k, (v, _) in C.compare(low, ref).items()}
    assert _failing(checks), checks
