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
    # the call carries the kernel's own name, which a trace shows
    assert f"fused_{name}_kernel" in compiled.as_text()


def test_hift_group_step_runs_the_named_fused_adamw_under_update(
        one_chip, monkeypatch):
    """A HiFT group step as ``make_runner`` builds it on a TPU: its update
    is the fused AdamW kernel, named, inside the ``update`` scope."""
    from repro.core import HiFTConfig, make_runner
    from repro.core.grouping import split_params
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_interpret", lambda interpret=None:
                        False)
    st = make_runner(get_config("qwen2_0_5b", smoke=True), "hift",
                     optimizer="adamw", fused_update=True,
                     hift=HiFTConfig(m=1)).strategy
    gi = 1                                    # layer 0
    place = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), t)
    params = jax.eval_shape(lambda: get_family(st.cfg).init(
        st.cfg, jax.random.PRNGKey(0)))
    active, frozen = jax.eval_shape(
        lambda p: split_params(p, st.groups[gi]), params)
    bundle = jax.eval_shape(st._init_bundle, active)
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32, sharding=one_chip)
    fn, _ = st.build_step(gi)
    text = jax.jit(fn.__wrapped__).lower(
        *place((active, frozen, bundle)), {"tokens": tok, "labels": tok},
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    ).compile().as_text()
    assert "fused_adamw_kernel" in text
    assert 'op_name="jit(step)/update/fused_adamw_kernel' in text


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


def test_bundle_offload_store_aliases_an_internlm2_block_bundle(one_chip):
    """The in-place offload of a full-width internlm2 block's Mixed^Hi
    bundle (fp32 master, AdamW m and v): every leaf of the store's host
    output is aliased onto the donated host bundle, so the offload
    allocates no pinned host buffer."""
    from repro.core.grouping import split_params
    from repro.core.pipeline import (_host_placements, aliases_every_output,
                                     compile_store)
    from repro.core.registry import make_strategy
    from repro.core.strategy import HiFTConfig
    from repro.optim import make_optimizer
    from repro.optim.mixed_precision import get_policy

    cfg = get_config("internlm2_1_8b")
    st = make_strategy("hift", cfg, make_optimizer("adamw"),
                       hift=HiFTConfig(m=1), policy=get_policy("mixed_hi"))
    params = jax.eval_shape(lambda: get_family(cfg).init(
        cfg, jax.random.PRNGKey(0)))
    active, _ = jax.eval_shape(lambda p: split_params(p, st.groups[1]),
                               params)               # layer 0
    bundle = jax.eval_shape(st._init_bundle, active)
    new = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), bundle)
    host = _host_placements(new)
    old = jax.tree.map(lambda x, h: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=h), new, host)
    compiled = compile_store(new, old, host)

    leaves = jax.tree.leaves(new)
    big = [x for x in leaves if x.ndim]
    assert {x.dtype for x in big} == {jnp.dtype(jnp.float32)}
    assert len(big) == 3 * len(jax.tree.leaves(active))   # master, m, v
    nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert nbytes > 700_000_000                  # one full-width block
    stats = compiled.memory_analysis()
    assert stats.host_alias_size_in_bytes == stats.host_output_size_in_bytes
    assert stats.host_alias_size_in_bytes >= nbytes
    # padding of the one scalar (AdamW's count) is all that is over
    assert stats.host_alias_size_in_bytes - nbytes < 4096
    assert aliases_every_output(compiled)
    # every output leaf aliases its own donated parameter, which lives in
    # host memory (S(5)): the n device inputs come first, then the n old
    head = compiled.as_text().split("\n", 1)[0]
    n = len(leaves)
    for i in range(n):
        assert f"{{{i}}}: ({n + i}, {{}}, may-alias)" in head
    params_layout = head.split("entry_computation_layout={(", 1)[1] \
        .split(")->", 1)[0]
    assert params_layout.count("S(5)") == n
