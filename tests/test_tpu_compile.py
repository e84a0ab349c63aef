"""Main-path Pallas kernels compile for a described TPU v5e.

Nothing runs: each kernel is lowered at a real width on shapes placed on one
chip of a ``v5e:2x2`` topology that the installed TPU compiler describes, and
compiled with ``interpret=False``.  This catches what interpret mode cannot:
tiles not aligned to the chip's layout, blocks over the VMEM budget.

The topology is described inside a module fixture (never at import), so only
the worker that runs this file loads the TPU compiler; every kernel test of
that kind lives in this one file.  JAX's persistent compilation cache is off
around the compiles: an entry compiled for a chip cannot be read back here.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.models import get_family


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


def _block_size(arch: str) -> int:
    """Parameters in one transformer block of ``arch`` at full width — the
    leaf set a HiFT group of one layer packs into one update stream."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: get_family(cfg).init(
        cfg, jax.random.PRNGKey(0)))
    return sum(x.size for x in jax.tree.leaves(shapes["layers"])) \
        // cfg.n_layers


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _fused_update(name):
    from repro.kernels.fused_adagrad import fused_adagrad_pallas
    from repro.kernels.fused_adamw import fused_adamw_pallas
    from repro.kernels.fused_sgdm import fused_sgdm_pallas
    if name == "adamw":
        return 2, lambda p, g, m, v: fused_adamw_pallas(
            p, g, m, v, lr=1e-3, c1=0.1, c2=0.001, interpret=False)
    if name == "sgdm":
        return 1, lambda p, g, mu: fused_sgdm_pallas(
            p, g, mu, lr=1e-3, interpret=False)
    return 1, lambda p, g, a: fused_adagrad_pallas(
        p, g, a, lr=1e-3, interpret=False)


@pytest.mark.parametrize("name", ["adamw", "sgdm", "adagrad"])
def test_fused_update_compiles_for_a_qwen2_block(one_chip, name):
    n = _block_size("qwen2_0_5b")
    assert n > 14_000_000                      # one full-width qwen2 layer
    n_state, fn = _fused_update(name)
    flat = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = _compile(fn, *[flat] * (2 + n_state))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen2_head_width(one_chip):
    from repro.kernels.flash_attention import flash_attention_pallas
    cfg = get_config("qwen2_0_5b")
    qkv = jax.ShapeDtypeStruct((1, 2048, cfg.n_heads, cfg.head_dim),
                               jnp.bfloat16, sharding=one_chip)
    compiled = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        qkv, qkv, qkv)
    assert cfg.head_dim == 64
    assert "tpu_custom_call" in compiled.as_text()
