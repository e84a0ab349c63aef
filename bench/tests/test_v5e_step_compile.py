"""The largest step program of each cell compiles for a described TPU v5e
and fits one chip.

Nothing runs: each program is lowered on shapes placed on one chip of a
``v5e:2x2`` topology that the installed TPU compiler describes.  The
topology is described inside a module fixture, never at import, so only
the worker that runs this file loads the TPU compiler.  JAX's persistent
compilation cache is off around the compiles: an entry compiled for a
chip cannot be read back here.  Donation is as on the chip (the program
turns it off on the CPU backend, so the step is jitted again here).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.lib import model as M
from bench.lib import spec

# HBM that one v5e chip gives a process: memory_stats()["bytes_limit"] as
# read on the chip
BYTES_LIMIT = 16909336064


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _place(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _nbytes(tree) -> int:
    return sum(x.size * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _in_use(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _batch(cell, sharding):
    shape = (cell.traffic["batch"], cell.traffic["seq"])
    tok = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)
    return {"tokens": tok, "labels": tok}


def test_hift_layer0_group_fits_one_chip(one_chip, monkeypatch):
    from repro.core import HiFTConfig
    from repro.core.grouping import split_params
    from repro.core.registry import make_strategy
    from repro.kernels import ops
    from repro.optim import make_optimizer
    from repro.optim.mixed_precision import get_policy

    monkeypatch.setattr(ops, "default_interpret", lambda interpret=None:
                        False)
    cell = spec.cell("internlm2-l16-hift-b8s512")
    c, w = cell.config, cell.workload
    st = make_strategy("hift", M.arch_config(c),
                       make_optimizer("adamw", use_pallas_fused=True),
                       hift=HiFTConfig(m=w["m"], strategy=w["order"]),
                       policy=get_policy(w["policy"]))
    params = jax.eval_shape(lambda: M.init_params(c, 0, jnp.bfloat16))
    gi = 1                                   # layer 0: the deepest backward
    active, frozen = jax.eval_shape(
        lambda p: split_params(p, st.groups[gi]), params)
    bundle = jax.eval_shape(st._init_bundle, active)
    fn, _ = st.build_step(gi)
    step = jax.jit(fn.__wrapped__, donate_argnums=(0, 2))
    compiled = step.lower(
        *_place((active, frozen, bundle), one_chip), _batch(cell, one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the fused AdamW kernel
    # the resident layer stack lives beside the step's own copies of it
    total = _in_use(compiled) + _nbytes(params["layers"])
    assert total < BYTES_LIMIT, f"{total / 2**30:.2f} GiB"


def test_fpft_step_fits_one_chip(one_chip):
    from repro.core.strategy import fpft_step_body
    from repro.optim import make_optimizer
    from repro.optim.mixed_precision import get_policy

    cell = spec.cell("qwen2-fpft-b8s512")
    c, w = cell.config, cell.workload
    opt = make_optimizer(w["optimizer"])
    params = jax.eval_shape(lambda: M.init_params(c, 0, jnp.float32))
    opt_state = jax.eval_shape(opt.init, params)
    step = jax.jit(fpft_step_body(M.arch_config(c), opt,
                                  get_policy(w["policy"])),
                   donate_argnums=(0, 1))
    compiled = step.lower(
        *_place((params, opt_state), one_chip), _batch(cell, one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)).compile()
    total = _in_use(compiled)
    assert total < BYTES_LIMIT, f"{total / 2**30:.2f} GiB"
