"""The bundle pipeline (core.pipeline) vs the serial schedule: BIT-identical
states — the pipeline may only move WHEN transfers happen, never what they
carry — plus the in-flight budget and cache-coherence rules.

The registry entry ``hift_pipelined`` additionally rides the full strategy
conformance battery (tests/test_strategy_conformance.py): purity, mid-sweep
checkpoint lockstep resume, metrics and memory-model agreement come from
there for free.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, tiny_dense_cfg
from repro.common.pytree import flatten_with_paths
from repro.core import (HiFTConfig, LiSAConfig, LRSchedule, make_runner)
from repro.core.pipeline import (BundlePipeline, _host_placements, host_put,
                                 reusable, writes_in_place)
from repro.train import checkpoint as ckpt


def _snap(state):
    return {path: np.array(leaf)
            for path, leaf in flatten_with_paths(state.to_tree()).items()}


def _assert_same(a, b, err=""):
    assert set(a) == set(b), (err, set(a) ^ set(b))
    for path in a:
        np.testing.assert_array_equal(a[path], b[path], err_msg=f"{err}{path}")


def _runner(strategy, cfg, seed=0, **kw):
    kw.setdefault("schedule", LRSchedule(base_lr=3e-3))
    return make_runner(cfg, strategy, seed=seed, **kw)


# ------------------------------------------------------- bitwise equality

def test_pipelined_hift_bitwise_equal_over_two_sweeps():
    """Acceptance: pipelined HiFT == serial HiFT, bit for bit, every step of
    >= 2 full sweeps — and the prefetcher actually worked (cache hits from
    sweep 2 on) within its <= 2-bundle budget."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    serial = _runner("hift", cfg)
    piped = _runner("hift_pipelined", cfg)
    assert piped.k == serial.k
    for step in range(2 * serial.k + 1):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        ls = serial.train_step(batch)
        lp = piped.train_step(batch)
        assert float(ls) == float(lp), step
        _assert_same(_snap(serial.state), _snap(piped.state),
                     err=f"step {step}: ")
    stats = piped.strategy._pipeline.stats
    # every step of sweep >= 2 prefetch-hits (sweep 1 bundles are fresh)
    assert stats.prefetch_hits >= serial.k
    assert stats.prefetch_misses == 0
    assert stats.max_resident <= 2


def test_pipelined_lisa_bitwise_equal():
    """LiSA's sampled schedule is a pure fn of (seed, step), so it pipelines
    too; re-samples landing on the same group skip the prefetch."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    lisa = LiSAConfig(m=1, switch_every=2, seed=3)
    serial = _runner("lisa", cfg, lisa=lisa)
    piped = _runner("lisa", cfg, lisa=lisa, pipeline_depth=2)
    assert piped.strategy._pipeline is not None
    for step in range(12):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        assert float(serial.train_step(batch)) == \
            float(piped.train_step(batch)), step
    _assert_same(_snap(serial.state), _snap(piped.state), err="lisa: ")
    assert piped.strategy._pipeline.stats.max_resident <= 2


def test_pipelined_fused_equals_serial_unfused_bitwise():
    """Both hot-loop knobs together (pipeline + fused sgdm kernel) leave the
    training trajectory bit-identical to the seed serial+unfused loop."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    serial = _runner("hift", cfg, optimizer="sgdm", fused_update=False)
    piped = _runner("hift", cfg, optimizer="sgdm", fused_update=True,
                    pipeline_depth=2)
    for step in range(2 * serial.k):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        assert float(serial.train_step(batch)) == \
            float(piped.train_step(batch)), step
    _assert_same(_snap(serial.state), _snap(piped.state), err="fused: ")


# ------------------------------------------------- checkpoint / coherence

def test_pipelined_mid_sweep_checkpoint_resume(tmp_path):
    """Save a pipelined run MID-SWEEP (prefetch cache warm), restore into a
    FRESH pipelined runner (cold cache, different seed) and into nothing at
    all (the uninterrupted serial reference): all three continue in bitwise
    lockstep.  The pipeline is a transfer cache, not state."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    serial = _runner("hift", cfg)
    piped = _runner("hift_pipelined", cfg)
    mid = serial.k + 2          # inside sweep 2: bundles exist, cache warm
    for step in range(mid):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        serial.train_step(batch)
        piped.train_step(batch)
    ckpt.save_state(tmp_path, mid, piped.state)
    restored = ckpt.restore_state(tmp_path, mid)
    fresh = _runner("hift_pipelined", cfg, seed=7)
    fresh.load_state_dict(restored.to_tree())
    assert fresh.step_count == mid
    for step in range(mid, mid + serial.k):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        l0 = float(serial.train_step(batch))
        l1 = float(piped.train_step(batch))
        l2 = float(fresh.train_step(batch))
        assert l0 == l1 == l2, step
    _assert_same(_snap(serial.state), _snap(piped.state), err="warm: ")
    _assert_same(_snap(serial.state), _snap(fresh.state), err="resumed: ")


def test_prefetch_cache_ignores_forked_state():
    """Re-stepping an OLD state must not consume a prefetch uploaded for a
    different host tree: entries are keyed by source identity, so a fork
    falls back to a plain upload and stays bit-identical."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    piped = _runner("hift_pipelined", cfg)
    serial = _runner("hift", cfg)
    batch = make_batch(cfg, batch=2, seq=16)
    for _ in range(serial.k + 1):   # into sweep 2: cache warm
        serial.train_step(batch)
        piped.train_step(batch)
    fork_p, fork_s = piped.state, serial.state
    # advance past the fork, then replay the forked state on both
    piped.train_step(batch)
    s1, m1 = piped.strategy.step(fork_p, batch)
    s2, m2 = serial.strategy.step(fork_s, batch)
    assert float(m1["loss"]) == float(m2["loss"])
    _assert_same(_snap(s1), _snap(s2), err="fork: ")


# --------------------------------------------------------- budget / wiring

def test_bundle_pipeline_budget_blocks_at_depth():
    """Unit-level: with depth 2, a third device bundle cannot be admitted
    until an older offload drains; depth < 2 is rejected outright."""
    with pytest.raises(ValueError, match="depth"):
        BundlePipeline(1)
    pipe = BundlePipeline(2)
    mk = lambda i: {"opt": jnp.full((4,), float(i))}
    for i in range(5):
        key = str(i % 2)
        got = pipe.fetch(key, mk(i))
        pipe.prefetch(str((i + 1) % 2), mk(i + 10))
        pipe.offload(key, got)
        # post-offload the active slot is empty (its buffer is draining)
        assert pipe.device_resident(active=0) <= pipe.depth
    assert pipe.stats.max_resident <= 2
    assert pipe.stats.offloads == 5
    pipe.flush()
    assert pipe.device_resident(active=0) == 0


def test_registry_entry_and_knob_threading():
    """hift_pipelined registers with depth >= 2 + memory mode, and
    make_runner's pipeline_depth/fused_update knobs reach the strategy."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    r = _runner("hift_pipelined", cfg)
    assert r.strategy._pipeline is not None
    assert r.strategy.hift.pipeline_depth == 2
    assert r.strategy.memory_mode == "hift_pipelined"
    r2 = _runner("hift", cfg, pipeline_depth=2)
    assert r2.strategy._pipeline is not None
    assert r2.strategy.memory_mode == "hift_pipelined"
    r3 = _runner("hift", cfg)
    assert r3.strategy._pipeline is None
    assert r3.strategy.memory_mode == "hift"
    with pytest.raises(ValueError, match="pipeline_depth"):
        _runner("mezo", cfg, pipeline_depth=2)
    with pytest.raises(ValueError, match="fused"):
        _runner("hift", cfg, optimizer="adafactor", fused_update=True)


def test_depth_three_lookahead_bitwise_equal():
    """depth > 2 chunk-granular lookahead: the prefetch window walks depth-1
    groups ahead of the active step, stays within its in-flight budget, and
    the trajectory is still bit-identical to the serial schedule."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    serial = _runner("hift", cfg)
    deep = _runner("hift", cfg, pipeline_depth=3)
    assert deep.strategy._pipeline.depth == 3
    for step in range(2 * serial.k + 1):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        assert float(serial.train_step(batch)) == \
            float(deep.train_step(batch)), step
    _assert_same(_snap(serial.state), _snap(deep.state), err="depth3: ")
    stats = deep.strategy._pipeline.stats
    assert stats.prefetch_hits >= serial.k   # lookahead actually served
    assert stats.max_resident <= 3           # never beyond the window


# ------------------------------------------------------- in-place offload

def _pinned(tree, **change):
    """ShapeDtypeStructs of ``tree`` placed in pinned host memory, as a
    previous offload of the same bundle leaves them on a TPU (the CPU
    backend cannot compile a pinned-host store, so the choice is tested on
    placements alone)."""
    host = _host_placements(tree)
    return jax.tree.map(lambda x, h: jax.ShapeDtypeStruct(
        change.get("shape", x.shape), change.get("dtype", x.dtype),
        sharding=h), tree, host)


def _bundle():
    return {"opt": {"m": jnp.ones((2, 8)), "v": jnp.ones((2, 8)),
                    "count": jnp.zeros((), jnp.int32)},
            "master": jnp.full((2, 8), 3.0)}


@pytest.mark.parametrize("case,expect", [
    ("pinned", True), ("first_visit", False), ("device_memory", False),
    ("numpy", False), ("shape", False), ("dtype", False),
    ("structure", False), ("deleted", False)])
def test_offload_reuses_only_a_matching_pinned_host_tree(case, expect):
    """An offload writes in place only over a tree of the same structure,
    shapes and dtypes already in pinned host memory where the new bundle
    goes; anything else (no bundle yet, a restored or device-resident one,
    another layout, a consumed one) gets fresh buffers."""
    new = _bundle()
    old = {"pinned": lambda: _pinned(new),
           "first_visit": lambda: None,
           "device_memory": lambda: jax.tree.map(jnp.copy, new),
           "numpy": lambda: jax.tree.map(np.asarray, new),
           "shape": lambda: _pinned(new, shape=(3, 8)),
           "dtype": lambda: _pinned(new, dtype=jnp.bfloat16),
           "structure": lambda: {"opt": _pinned(new)["opt"]},
           "deleted": lambda: _deleted(new)}[case]()
    assert reusable(new, old, _host_placements(new)) is expect


def _deleted(tree):
    out = jax.device_put(tree, _host_placements(tree))
    for x in jax.tree.leaves(out):
        x.delete()
    return out


def test_offload_choice_follows_the_runners_bundles(tmp_path):
    """Fresh at a first visit and after a checkpoint restore (its bundles
    come back as device arrays), in place over the pinned-host image of
    the bundle the step replaces.  The CPU backend keeps bundles where
    they are: ``host_put`` is the identity and consumes nothing."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    r = _runner("hift", cfg)
    for step in range(r.k):
        r.train_step(make_batch(cfg, batch=2, seq=16, seed=step))
    key = str(r.strategy._order_at(r.state)[0])
    bundle = r.state.opt_state[key]
    new = jax.tree.map(lambda x: x + 0, bundle)      # a step's output
    host = _host_placements(new)
    assert not reusable(new, None, host)
    assert reusable(new, _pinned(bundle), host)
    ckpt.save_state(tmp_path, r.k, r.state)
    restored = ckpt.restore_state(tmp_path, r.k).opt_state[key]
    assert not reusable(new, restored, host)
    assert not writes_in_place(new, bundle)          # CPU: no store
    assert host_put(new, into=bundle) is new
    assert not any(x.is_deleted() for x in jax.tree.leaves(bundle))


def test_offload_over_a_draining_bundle_waits_for_it_first():
    """At depth 3 a group revisited at once offloads over a bundle whose
    own offload is still draining: the pipeline waits on it and drops it
    from the drain queue before the store consumes it."""
    pipe = BundlePipeline(3)
    first = pipe.offload("0", _bundle())
    assert list(pipe._draining) == [first]
    second = pipe.offload("0", _bundle(), into=first)
    assert [h is second for h in pipe._draining] == [True]
    assert pipe.stats.budget_waits == 1
    pipe.flush()
    assert pipe.device_resident(active=0) == 0


def test_pipelined_lisa_revisits_at_depth_three_bitwise_equal():
    """LiSA keeps one group for ``switch_every`` steps, so at depth 3 each
    offload lands over a bundle still draining: the trajectory stays
    bit-identical to the serial schedule."""
    cfg = tiny_dense_cfg(ce_chunk=0)
    lisa = LiSAConfig(m=1, switch_every=3, seed=5)
    serial = _runner("lisa", cfg, lisa=lisa)
    deep = _runner("lisa", cfg, lisa=lisa, pipeline_depth=3)
    for step in range(9):
        batch = make_batch(cfg, batch=2, seq=16, seed=step)
        assert float(serial.train_step(batch)) == \
            float(deep.train_step(batch)), step
    _assert_same(_snap(serial.state), _snap(deep.state), err="lisa3: ")
    assert deep.strategy._pipeline.stats.max_resident <= 3
