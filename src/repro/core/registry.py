"""Fine-tuning strategy registry (mirrors ``repro.configs.registry``).

Strategies register themselves by name; :func:`make_runner` is the canonical
entry point for building a training driver:

    runner = make_runner(cfg, strategy="hift", optimizer="adamw",
                         hift=HiFTConfig(m=2), schedule=LRSchedule(2e-3))
    loss = runner.train_step(batch)

Everything downstream (train/loop.py, launch/train.py, dry-run, benchmarks,
examples) programs against this surface;
``hift|hift_pipelined|fpft|fpft_streamed|mezo|lisa|lomo|adalomo`` are the
built-ins — all
mesh-aware via ``make_runner(..., mesh=...)`` — and new strategies plug in
with one ``@register_strategy`` line.  Every entry in
the registry is held to one shared contract (purity, checkpoint
round-trips, metrics, memory accounting) by
``tests/test_strategy_conformance.py``; registering a strategy buys that
coverage for free.
"""
from __future__ import annotations

from typing import Any, Optional

_REGISTRY: dict[str, type] = {}


def register_strategy(name: str):
    """Class decorator: add a Strategy class to the registry under ``name``."""
    def deco(cls):
        _REGISTRY[name] = cls
        return cls
    return deco


def _ensure_loaded() -> None:
    # the built-ins register as an import side effect
    from repro.core import strategy  # noqa: F401


def strategy_ids() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def get_strategy_cls(name: str) -> type:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise ValueError(f"unknown strategy {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def make_strategy(name: str, cfg, optimizer, **kwargs):
    """Build a Strategy instance (static config only — no training state)."""
    return get_strategy_cls(name)(cfg, optimizer, **kwargs)


# optimizers with a fused Pallas update kernel (the paper's three headline
# optimizers — see docs/performance.md for the coverage matrix)
FUSED_OPTIMIZERS = ("adamw", "sgdm", "adagrad")


def make_runner(cfg, strategy: str = "hift", *, params: Any = None,
                optimizer: Any = "adamw", rng: Any = None, seed: int = 0,
                mesh: Any = None, fused_update: Any = None,
                pipeline_depth: Any = None, **kwargs):
    """One factory for every fine-tuning strategy.

    ``optimizer`` may be a name (resolved via ``repro.optim.make_optimizer``)
    or an ``Optimizer``; ``params`` default to a fresh ``family.init`` from
    ``seed``.  ``mesh`` (a ``jax.sharding.Mesh``, e.g. from
    ``repro.launch.mesh.mesh_from_spec("2x4")``) makes the strategy's jitted
    steps mesh-aware: params/optimizer state shard over the ``model`` axis
    and batches over ``data`` per ``repro.dist.shardings`` (see
    ``docs/sharding.md``).

    Hot-loop knobs (see ``docs/performance.md``):

    - ``fused_update``: route the optimizer's elementwise update through the
      fused Pallas kernels (one VMEM pass over param+moments).  ``None``
      (default) auto-selects: fused on TPU for the GROUPED strategies
      (whose group-sized trees the packed layout was sized for) without a
      multi-device mesh, unfused elsewhere — the packing concatenates each
      dtype bucket into one contiguous stream, so full-tree strategies like
      fpft pay transient full-tree copies and must opt in explicitly, and
      GSPMD cannot partition a compiled Pallas kernel over a mesh (the
      sharded step would not compile).  Requires ``optimizer``
      given by NAME (one of ``FUSED_OPTIMIZERS``) so the factory can
      rebuild it.
    - ``pipeline_depth``: >= 2 pipelines the host<->device transfers
      (``repro.core.pipeline``) with a depth-bundle device window.  For the
      grouped strategies (``hift``/``hift_pipelined``/``lisa``) it overrides
      the matching field of an explicit ``hift=``/``lisa=`` config (depth-1
      upcoming bundles prefetch while the active step computes); for
      ``fpft_streamed`` it sets the ChunkStream window depth (overriding an
      explicit ``stream=`` config's depth).
    - ``stream_window``: chunk byte size for ``fpft_streamed``'s bounded
      device window (``StreamConfig.chunk_bytes``; the ``launch.train``/
      ``launch.dryrun`` ``--stream-window`` flag lands here).  Only valid
      with ``strategy="fpft_streamed"``.
    - ``quant``: a ``QuantConfig`` for quantized resident state (see
      ``docs/quantization.md``).  ``frozen="int8"|"nf4"`` codec-encodes the
      grouped strategies' resident tree; ``moments="bf16"`` rebuilds a
      by-NAME optimizer with ``moment_dtype=bf16`` (half the optimizer
      state bytes) — it therefore needs the optimizer given by name, and
      one of the moment-carrying ``FUSED_OPTIMIZERS``.

    Remaining kwargs go to the strategy constructor (``schedule``,
    ``policy``, ``loss_fn``, ``param_sharding_fn``, and per-strategy configs
    such as ``hift=``, ``lisa=``, ``mezo=``).
    """
    import dataclasses

    import jax

    from repro.core.strategy import (HiFTConfig, LiSAConfig, Runner,
                                     StreamConfig)
    from repro.models import get_family
    from repro.optim import make_optimizer

    stream_window = kwargs.pop("stream_window", None)
    if stream_window is not None:
        if strategy != "fpft_streamed":
            raise ValueError("stream_window sizes fpft_streamed's chunk "
                             f"window; it does not apply to {strategy!r}")
        kwargs["stream"] = dataclasses.replace(
            kwargs.get("stream") or StreamConfig(),
            chunk_bytes=int(stream_window))

    quant = kwargs.pop("quant", None)
    grouped = strategy in ("hift", "hift_pipelined", "lisa")
    if isinstance(optimizer, str):
        fused = (jax.default_backend() == "tpu" and grouped
                 and (mesh is None or mesh.size == 1)) \
            if fused_update is None else bool(fused_update)
        okw = {"use_pallas_fused": True} if (fused and
                                             optimizer in FUSED_OPTIMIZERS) \
            else {}
        if fused_update and not okw:
            raise ValueError(f"no fused update kernel for {optimizer!r}; "
                             f"have {FUSED_OPTIMIZERS}")
        if quant is not None and quant.moments:
            if optimizer not in FUSED_OPTIMIZERS:
                raise ValueError(
                    "quant.moments applies to the moment-carrying "
                    f"optimizers {FUSED_OPTIMIZERS}, not {optimizer!r} "
                    "(sgd keeps no moments; adafactor's factored stats "
                    "are already sub-fp32-sized)")
            okw["moment_dtype"] = "bfloat16"
        optimizer = make_optimizer(optimizer, **okw)
    elif fused_update:
        raise ValueError("fused_update=True needs the optimizer given by "
                         "name so make_runner can rebuild it fused")
    elif quant is not None and quant.moments:
        raise ValueError("quant.moments needs the optimizer given by name "
                         "so make_runner can rebuild it with "
                         "moment_dtype=bf16")
    if quant is not None:
        kwargs["quant"] = quant
    if pipeline_depth is not None:
        if strategy == "hift_pipelined" and pipeline_depth < 2:
            raise ValueError(
                "hift_pipelined IS the pipelined schedule; an explicit "
                f"pipeline_depth={pipeline_depth} would silently re-enable "
                "it — use strategy 'hift' for the serial path")
        if strategy in ("hift", "hift_pipelined"):
            kwargs["hift"] = dataclasses.replace(
                kwargs.get("hift") or HiFTConfig(),
                pipeline_depth=pipeline_depth)
        elif strategy == "lisa":
            kwargs["lisa"] = dataclasses.replace(
                kwargs.get("lisa") or LiSAConfig(),
                pipeline_depth=pipeline_depth)
        elif strategy == "fpft_streamed":
            kwargs["stream"] = dataclasses.replace(
                kwargs.get("stream") or StreamConfig(),
                depth=pipeline_depth)
        else:
            raise ValueError("pipeline_depth applies to the pipelined "
                             "strategies (hift/lisa/fpft_streamed), not "
                             f"{strategy!r}")
    if params is None:
        params = get_family(cfg).init(cfg, jax.random.PRNGKey(seed))
    if rng is None:
        rng = jax.random.PRNGKey(seed)
    if mesh is not None:
        kwargs["mesh"] = mesh
    return Runner(make_strategy(strategy, cfg, optimizer, **kwargs), params,
                  rng=rng)
