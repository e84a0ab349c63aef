"""chip_smoke.py's phases on CPU at SMOKE size.

The script itself refuses to run without a TPU, so these tests import its
phase functions and steer the two chip-only checks (compiled fused kernels,
pinned_host bundles) from here: each check passes on what the CPU does and
refuses what the CPU does when held to the chip's expectation.  Also the
compilation-cache helper the entry points call at startup.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def trained(chip_smoke, tmp_path_factory):
    # a cache directory placed from outside: launch.train then sets none
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(compile_cache.ENV_VAR,
                  str(tmp_path_factory.mktemp("jax_cache")))
        return chip_smoke.train_phase(smoke=True, m=1, batch=2, seq=32,
                                      extra=("--fused-update",))


def test_train_phase_runs_a_sweep_and_a_revisit(chip_smoke, trained):
    assert trained["k"] == 4                  # embed, 2 layers, head
    assert len(trained["losses"]) == len(trained["step_times"]) == 5
    # what the CPU does: interpreted kernels, bundles left in device memory
    chip_smoke.check_train(trained, offload_kind="device",
                           fused_interpret=True)


def test_check_train_refuses_interpreted_fused_kernels(chip_smoke, trained):
    with pytest.raises(RuntimeError, match="interpret"):
        chip_smoke.check_train(trained, offload_kind="device")


def test_check_train_wants_every_bundle_in_pinned_host(chip_smoke, trained):
    with pytest.raises(RuntimeError, match="pinned_host"):
        chip_smoke.check_train(trained, fused_interpret=True)
    state = trained["state"]
    bundles = {key: jax.device_put(
        b, jax.tree.map(lambda x: x.sharding.with_memory_kind("pinned_host"),
                        b))
        for key, b in state.opt_state.items()}
    offloaded = dict(trained, state=state.replace(opt_state=bundles))
    chip_smoke.check_train(offloaded, fused_interpret=True)
    # one bundle left behind in device memory is caught
    bundles["0"] = state.opt_state["0"]
    with pytest.raises(RuntimeError, match="pinned_host"):
        chip_smoke.check_train(dict(trained, state=state.replace(
            opt_state=bundles)), fused_interpret=True)


def test_check_train_refuses_non_finite_loss(chip_smoke, trained):
    losses = list(trained["losses"])
    losses[-1] = float("nan")
    with pytest.raises(RuntimeError, match="losses"):
        chip_smoke.check_train(dict(trained, losses=losses),
                               offload_kind="device", fused_interpret=True)


def test_serve_phase_drains_and_matches_fixed_batch_engine(chip_smoke,
                                                           trained):
    res = chip_smoke.serve_phase(trained["cfg"], trained["state"])
    chip_smoke.check_serve(res)
    same, total = chip_smoke.agreement(res)
    assert total == 4 * chip_smoke.SERVE_MAX_NEW
    assert same == total                      # fp32 on CPU: bit-stable
    with pytest.raises(RuntimeError, match="occupancy"):
        chip_smoke.check_serve(dict(res, occupancy=0.25))


@pytest.mark.parametrize("depth", [None, 2])
def test_hift_revisit_reuses_the_compiled_step_under_real_offload(
        monkeypatch, depth):
    """On the chip a revisited group's bundle comes back from pinned_host
    committed to its device; the first visit's inputs must be committed too,
    or every revisit compiles the group's step again.  Steered here by
    making the CPU move bundles between memory kinds as the chip does."""
    from conftest import make_batch, tiny_dense_cfg
    from repro.core import HiFTConfig, make_runner, pipeline, strategy

    def moved(kind):
        return lambda tree, shardings=None, into=None: jax.device_put(
            tree, pipeline._leaf_placements(tree, kind))

    for module in (strategy, pipeline):
        monkeypatch.setattr(module, "host_put", moved("pinned_host"))
        monkeypatch.setattr(module, "device_put_async", moved("device"))
    runner = make_runner(tiny_dense_cfg(ce_chunk=0), "hift", optimizer="adamw",
                         hift=HiFTConfig(m=2), pipeline_depth=depth)
    for step in range(2 * runner.k + 1):
        runner.train_step(make_batch(runner.cfg, batch=2, seq=16, seed=step))
    assert {leaf.sharding.memory_kind for leaf in
            jax.tree.leaves(runner.state.opt_state["0"])} == {"pinned_host"}
    assert {gi: fn._cache_size()
            for gi, (fn, _) in runner.strategy._step_fns.items()} == \
        {gi: 1 for gi in range(runner.k)}


@pytest.mark.timeout(600)
def test_mesh_phase_on_four_virtual_devices(tmp_path):
    """The four-chip phase at SMOKE size on four CPU devices, in a child
    process (the device count is fixed when JAX starts): fpft and hift on
    the 1x4 mesh against hift on one device, held to the same checks."""
    script = ("import sys; sys.path.insert(0, sys.argv[1]); "
              "import chip_smoke as c; "
              "c.check_mesh(c.mesh_phase(smoke=True, batch=4, seq=32))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", script, str(ROOT)],
                          capture_output=True, text=True, timeout=500,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]


def test_chip_smoke_refuses_a_cpu(chip_smoke):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch,
                                                         tmp_path):
    """With the directory placed from outside, no directory is set: only
    the key's policy (metadata in the key) is."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *args: updates.append(args))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert updates == [("jax_compilation_cache_include_metadata_in_key",
                        True)]


def test_compile_cache_defaults_to_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        path = compile_cache.setup_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert compile_cache.setup_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
