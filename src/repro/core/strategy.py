"""Unified Strategy API: one functional surface for HiFT / FPFT / MeZO /
LiSA / LOMO.

The paper's claim is that HiFT is an optimizer-independent *strategy*, not a
bespoke trainer — this module makes strategies first-class:

    strategy = make_strategy("hift", cfg, optimizer, hift=HiFTConfig(m=1))
    state = strategy.init(params)                   # -> TrainState
    state, metrics = strategy.step(state, batch)    # state-in / state-out

Construction captures everything STATIC (config, model family, optimizer,
jitted step cache); ALL training state — params, optimizer bundles, the step
counter, HiFT's queue order, MeZO's rng — lives in the immutable
:class:`TrainState` pytree, the one checkpointable object:
``state.to_tree()`` round-trips through ``repro.train.checkpoint`` including
HiFT's mid-sweep queue position.

Built-in strategies (registered in ``repro.core.registry``):
  - ``hift`` : the paper's Algorithm 1 — one group of m units per step in a
               fixed visit order, per-group optimizer bundles, host offload,
               Mixed^Hi fp32 masters for the active group only.
  - ``fpft`` : the standard full-parameter baseline (all params every step).
  - ``lisa`` : LiSA-style random layer sampling ("LISA: Layerwise Importance
               Sampling", Pan et al. 2024) — the same grouped machinery as
               HiFT, but the active group is re-SAMPLED every
               ``switch_every`` steps instead of swept in a fixed order.
  - ``mezo`` : zeroth-order SPSA (``repro.optim.mezo``) — no gradients, no
               optimizer state; ``opt_state`` stays empty and the rng rides
               in ``extra`` (the paper's memory floor baseline).
  - ``lomo`` : LOMO-style fused backward ("Full Parameter Fine-tuning for
               Large Language Models with Limited Resources", Lv et al.
               2023) — the SGD(+clip) update is fused into the backward
               pass, consuming each layer's gradient in cotangent order, so
               a full gradient tree never materializes; like MeZO the
               optimizer bundle is empty.
  - ``adalomo`` : AdaLomo ("AdaLomo: Low-memory Optimization with Adaptive
               Learning Rate", Lv et al. 2023) — the same fused backward,
               but each layer's in-scan update is Adafactor-grade (factored
               row/col second moments + per-matrix update-RMS clipping,
               reusing ``repro.optim.adafactor``'s leaf math).  The factored
               statistics — O(r+c) floats per matrix — are the ONLY resident
               optimizer state; gradients still die layer-by-layer.
  - ``hift_pipelined`` : HiFT with the double-buffered bundle pipeline
               (``repro.core.pipeline``) on by default — next group's
               optimizer bundle uploads while the current step computes;
               bit-identical to ``hift``, at most ``pipeline_depth``
               bundles device-resident (see ``docs/performance.md``).
  - ``fpft_streamed`` : ChunkFT-style full-parameter fine-tuning — FPFT's
               update with the optimizer moments host-resident, streamed
               chunk-by-chunk through a bounded device window
               (``core.pipeline.ChunkStream``) during the update.
               Bit-identical to ``fpft`` with the same (stream-safe)
               optimizer; optimizer-state device residency drops from
               2*zeta_1 to ``depth * chunk_bytes``.

Every strategy is also **mesh-aware**: pass ``mesh=`` (a
``jax.sharding.Mesh`` with ``data``/``model`` axes, e.g. from
``repro.launch.mesh.mesh_from_spec``) and the jitted steps compile under
explicit ``in_shardings``/``out_shardings`` from ``repro.dist.shardings`` —
active-group params and optimizer bundles shard over ``model``, frozen
params replicate, batches split over ``data``, and MoE layers route through
their ``shard_map`` expert-parallel path.  ``docs/sharding.md`` documents
the placement rules and the CPU-device-count trick for testing them.

:class:`Runner` is the thin mutable facade over ``(strategy, state)`` that
driver loops use; ``repro.core.registry.make_runner`` is the factory.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.common.pytree import tree_cast, tree_size
from repro.dist import ctx as dist_ctx
from repro.dist import shardings as dist_shardings
from repro.dist.compress import compress_tree_with_feedback, init_residuals
from repro.core.grouping import (Group, group_cut, make_groups, merge_params,
                                 order_groups, split_params)
from repro.core.pipeline import (BundlePipeline, ChunkLayout, ChunkStream,
                                 device_put_async, host_put, writes_in_place)
from repro.core.registry import register_strategy
from repro.core.scheduler import LRSchedule
from repro.models import get_family, unit_first_depth
from repro.models.base import LomoPieces
from repro.optim import base as opt_base
from repro.optim.adafactor import beta2_at, leaf_update, moment_init
from repro.optim.base import Optimizer
from repro.optim.mezo import mezo_step
from repro.optim.mixed_precision import FP32, Policy

PyTree = Any
Metrics = dict


# --------------------------------------------------------------- placement
#
# host_put / device_put_async live in repro.core.pipeline (with the
# double-buffered BundlePipeline that schedules them off the critical
# path); re-exported here because this module is their historical home.


def write_back(params: PyTree, new_active: PyTree, group: Group) -> PyTree:
    """Fold the updated active sub-tree back into the full param tree."""
    taken_stacked = {k: (lo, hi) for k, lo, hi in group.stacked_ranges}
    out = dict(params)
    for key, sub in new_active.items():
        if key in taken_stacked:
            lo, _ = taken_stacked[key]
            out[key] = jax.tree.map(
                lambda full, s: jax.lax.dynamic_update_slice_in_dim(full, s, lo, axis=0),
                params[key], sub)
        else:
            out[key] = sub
    return out


# ----------------------------------------------------------------- configs

@dataclasses.dataclass
class HiFTConfig:
    m: int = 1                        # layers (units) per group
    strategy: str = "bottom2up"       # visit ORDER: bottom2up | top2down | random
    seed: int = 0
    use_cut: bool = True              # stop_gradient below the active group
    offload_optimizer: bool = True    # keep inactive opt state on host
    pipeline_depth: int = 1           # max device-resident bundles; >= 2
                                      # double-buffers host<->device bundle
                                      # transfers (core.pipeline) — bit-
                                      # identical to the serial schedule


@dataclasses.dataclass
class LiSAConfig:
    m: int = 1                        # units per sampled group
    switch_every: int = 5             # steps between re-sampling the group
    seed: int = 0
    use_cut: bool = True
    offload_optimizer: bool = True
    pipeline_depth: int = 1           # as HiFTConfig: LiSA's sample is a
                                      # pure fn of (seed, step), so step+1's
                                      # group is prefetchable too


@dataclasses.dataclass
class MeZOConfig:
    eps: float = 1e-3                 # SPSA perturbation scale
    seed: int = 0                     # default rng when init() gets none


@dataclasses.dataclass
class LOMOConfig:
    grad_clip: float = 1.0            # global-norm clip threshold (0 = off);
                                      # >0 adds the paper's second backward
                                      # sweep to compute the norm first
    weight_decay: float = 0.0         # decoupled, as in repro.optim.sgd


@dataclasses.dataclass
class AdaLomoConfig:
    grad_clip: float = 0.0            # global-norm clip (0 = off, the
                                      # default: the per-matrix update-RMS
                                      # clip below already bounds steps);
                                      # >0 adds LOMO's norm-only sweep
    weight_decay: float = 0.0         # decoupled, inside the leaf update
    eps1: float = 1e-30               # Adafactor's gradient-square epsilon
    clip_threshold: float = 1.0       # per-matrix update-RMS clip d
    decay_rate: float = 0.8           # beta2 schedule 1 - t^-decay_rate
    relative_step: bool = False       # alpha = lr * max(eps2, RMS(p)) — the
                                      # paper's grouped update size; RMS is
                                      # per trailing matrix (matrix_rms), so
                                      # fused and fallback paths agree
    eps2: float = 1e-3                # relative-step LR floor


@dataclasses.dataclass
class CrossPodConfig:
    """Cross-pod data parallelism: the global batch splits into ``pods``
    equal chunks whose partial gradients are reduced into one update.  With
    ``compress`` on, each pod's partial passes through the int8
    error-feedback quantizer (``repro.dist.compress``) before the reduce —
    4x fewer bytes on the slow DCI wire — and the per-pod fp32 residuals
    become training state (FPFT: ``extra["ef_residual"]``; grouped
    strategies: the active group's bundle under ``"ef"``), so they
    checkpoint, offload and conformance-test like everything else."""
    pods: int = 2
    compress: bool = True


@dataclasses.dataclass
class StreamConfig:
    """Chunk-granular state streaming (``core.pipeline.ChunkStream``).

    ``chunk_bytes`` is the packed byte budget of one stream chunk — the unit
    the host<->device window moves, measured against the layout's BASE tree
    (congruent trees of wider dtypes move proportionally more bytes per
    chunk).  ``depth`` is the maximum device-resident chunks per streamed
    tree, the ChunkFT analogue of ``HiFTConfig.pipeline_depth``: depth-1
    chunks of lookahead upload while the active chunk's update runs.
    Consumed by ``fpft_streamed`` (host-resident AdamW moments stream
    through the window during the update) and by the LOMO/AdaLomo
    segment-streaming opt-in."""
    chunk_bytes: int = 1 << 20
    depth: int = 2

    def __post_init__(self):
        if self.chunk_bytes <= 0:
            raise ValueError(
                f"stream chunk_bytes must be > 0, got {self.chunk_bytes}")
        if self.depth < 2:
            raise ValueError(
                f"stream depth must be >= 2, got {self.depth}; the serial "
                "(resident) path is plain 'fpft'")


@dataclasses.dataclass
class QuantConfig:
    """Quantized resident state (see ``docs/quantization.md``).

    ``frozen``: blockwise codec for the grouped strategies' resident param
    tree — ``"int8"`` (~4x smaller than fp32) or ``"nf4"`` (~8x), both from
    ``repro.dist.quant`` (per-(8,128)-tile scales).  The resident tree stays
    ENCODED between steps; the jitted step dequantizes the frozen majority
    on the fly (2-d leaves can route through the fused dequant-matmul
    kernel) and re-quantizes the active group after its update.  The active
    group's fp32 master rides its optimizer bundle across revisits, so
    quantization error never accumulates into the update path — it is a
    one-way rounding of the FROZEN view only.

    ``moments``: resident dtype of the optimizer moments — ``"bf16"``
    halves AdamW's state bytes (and the streamed/offloaded strategies' wire
    bytes); every update still computes in fp32 and re-rounds on store
    (``repro.optim``'s ``moment_dtype``).  Wired by ``make_runner`` when
    the optimizer is given by NAME (the factory rebuilds it)."""
    frozen: Optional[str] = None
    moments: Optional[str] = None

    def __post_init__(self):
        from repro.dist.quant import QUANT_FORMATS
        if self.frozen is not None and self.frozen not in QUANT_FORMATS:
            raise ValueError(
                f"QuantConfig.frozen must be one of {QUANT_FORMATS} or "
                f"None, got {self.frozen!r}")
        if self.moments is not None and self.moments not in ("bf16",
                                                             "bfloat16"):
            raise ValueError(
                "QuantConfig.moments supports 'bf16' (fp32 is the default "
                f"resident moment dtype), got {self.moments!r}")
        if self.frozen is None and self.moments is None:
            raise ValueError(
                "empty QuantConfig: set frozen='int8'|'nf4' and/or "
                "moments='bf16'")

    @property
    def moment_dtype(self):
        """The jnp dtype ``moments`` resolves to (None = fp32 default)."""
        return jnp.bfloat16 if self.moments else None


def crosspod_reduce(loss_and_grad: Callable, params: PyTree, batch,
                    residuals: PyTree, cross_pod: CrossPodConfig):
    """Cross-pod data-parallel gradient reduce with optional int8
    error-feedback compression on the wire.

    The batch splits into ``pods`` equal leading-dim chunks — one per pod —
    and a ``lax.scan`` computes each pod's partial gradient in turn, so only
    ONE pod's gradient tree is ever live (the per-process liveness a real
    multi-pod launch has).  With ``compress`` on each partial round-trips
    through ``dist.compress`` before entering the sum: what crosses the scan
    carry is exactly what would cross the DCI wire (int8 payload + per-leaf
    scale), and pod i's fp32 residual — slice i of the stacked ``residuals``
    tree — feeds back into its next quantization (EF-SGD).  Returns
    ``(grads, new_residuals, mean_loss)``; with ``compress=False`` this is
    plain chunked gradient accumulation, matching the single-reduce step up
    to fp reassociation."""
    pods = cross_pod.pods

    def chunk(x):
        if x.shape[0] % pods:
            raise ValueError(
                f"cross-pod reduce needs a batch divisible by pods={pods}; "
                f"got leading dim {x.shape[0]}")
        return x.reshape((pods, x.shape[0] // pods) + x.shape[1:])

    pod_batch = jax.tree.map(chunk, batch)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, xs):
        g_acc, l_acc = carry
        b, r = xs
        loss, g = loss_and_grad(b)
        if cross_pod.compress:
            g, r = compress_tree_with_feedback(g, r)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), g_acc, g)
        return (g_acc, l_acc + loss.astype(jnp.float32)), r

    (g_sum, l_sum), new_res = jax.lax.scan(
        body, (zeros, jnp.zeros((), jnp.float32)), (pod_batch, residuals))
    grads = jax.tree.map(lambda g, p: (g / pods).astype(p.dtype),
                         g_sum, params)
    return grads, new_res, l_sum / pods


# -------------------------------------------------------------- TrainState

@dataclasses.dataclass(frozen=True)
class TrainState:
    """The one checkpointable object: immutable, pytree-registered.

    ``opt_state`` layout is strategy-owned: FPFT holds one optimizer state
    tree, grouped strategies hold ``{str(group_index): bundle}`` (string keys
    so the path-keyed checkpoint codec round-trips it), MeZO holds ``{}``.
    ``extra`` carries small strategy extras (HiFT visit order, MeZO rng)."""
    params: PyTree
    opt_state: PyTree
    step: Any = 0
    extra: PyTree = dataclasses.field(default_factory=dict)

    def replace(self, **kw) -> "TrainState":
        """Functional update (``dataclasses.replace``) — states are frozen."""
        return dataclasses.replace(self, **kw)

    def to_tree(self) -> dict:
        """Plain dict-of-dicts view for the path-keyed checkpoint codec.

        Layout: ``{"params", "opt_state", "step", "extra"}`` with ``step``
        normalized to a host ``np.int64`` scalar.  Leaves may be sharded
        jax.Arrays — ``repro.train.checkpoint.save`` snapshots them to host
        numpy (an implicit all-gather per leaf) before serializing, so a
        state trained on a mesh checkpoints like any other."""
        return {"params": self.params, "opt_state": self.opt_state,
                "step": np.int64(int(self.step)), "extra": self.extra}

    @classmethod
    def from_tree(cls, tree: dict) -> "TrainState":
        """Inverse of :meth:`to_tree`.

        Accepts two layouts: the current one (``step`` key, see
        :meth:`to_tree`) and the pre-Strategy-API runner ``state_dict``
        (``step_count`` key), which is routed to
        :meth:`_from_legacy_tree` so checkpoints written before PR 1 keep
        restoring.  Restored leaves are host-resident; re-placing them on a
        mesh is the caller's job (``strategy.place_params`` /
        ``jax.device_put``)."""
        if "step" not in tree and "step_count" in tree:
            return cls._from_legacy_tree(tree)
        return cls(params=tree["params"],
                   opt_state=tree.get("opt_state") or {},
                   step=int(np.asarray(tree["step"])),
                   extra=tree.get("extra") or {})

    @classmethod
    def _from_legacy_tree(cls, tree: dict) -> "TrainState":
        """Read pre-Strategy-API runner state_dicts ({params, opt_states |
        opt_state, step_count[, order]}) so old checkpoints keep resuming."""
        extra = {}
        if "order" in tree:
            extra["order"] = tree["order"]
        opt_state = tree.get("opt_states")
        if opt_state is None:
            opt_state = tree.get("opt_state") or {}
        return cls(params=tree["params"], opt_state=opt_state,
                   step=int(np.asarray(tree["step_count"])), extra=extra)


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.step, s.extra), None),
    lambda _, c: TrainState(*c))


# ------------------------------------------------------------ Strategy base

class Strategy:
    """Protocol base.  Subclasses implement ``init`` and ``step``.

    **Purity contract.**  Construction captures everything static (config,
    model family, optimizer, mesh, jitted-step caches); after ``__init__`` a
    strategy instance never mutates observable state.  ``init`` is a pure
    function of ``(params, rng)`` and ``step`` of ``(state, batch)`` — all
    training state, including HiFT's queue position and MeZO's rng, lives in
    the returned :class:`TrainState`, so drivers may checkpoint/fork/replay
    states freely and two strategies built from the same arguments are
    interchangeable mid-run.

    One caveat: on accelerator backends the jitted steps DONATE the active
    param / optimizer buffers (the k-fold memory reduction depends on it),
    and a grouped step writes the active group's new host bundle into the
    pinned host buffers of the input state's bundle, so the input state is
    consumed, its host bundles included — sequential drivers like
    ``Runner`` are unaffected (a checkpoint copies the state to host numpy
    before it returns), but re-stepping an old state is CPU-only.

    **Sharding.**  With a multi-device ``mesh`` the steps compile with
    explicit shardings (see module docstring); ``param_sharding_fn(tree,
    mesh) -> sharding tree`` overrides the structural placement rule from
    ``repro.dist.shardings.param_shardings``."""

    name = "base"
    k = 1   # steps per LR cycle (HiFT: number of groups; others: 1)
    # how core.memory_model accounts this strategy (tests/test_strategy_
    # conformance.py cross-checks analyze(mode=memory_mode, m=memory_m)
    # against peak_trainable_params / peak_grad_params)
    memory_mode = "fpft"
    memory_m = 1
    # declaration the conformance battery keys its cross-pod case on: True
    # for strategies whose step accepts a CrossPodConfig (gradient-based
    # strategies with a whole-tree reduce point); the fused-backward and
    # zeroth-order families have no gradient tree to compress
    supports_cross_pod = False
    # why cross_pod is unsupported — appended to the rejection error when
    # non-empty, so strategies with a structural reason (the fused-backward
    # family) point the user somewhere actionable
    cross_pod_unsupported_reason = ""
    # declarations the quantized-residency machinery keys on (see
    # QuantConfig): frozen-tree codecs need a frozen resident tree (grouped
    # strategies only); moment quantization needs a first-class optimizer
    # moment tree the ``moment_dtype`` factories own
    supports_quant_frozen = False
    supports_quant_moments = False

    def __init__(self, cfg, optimizer: Optional[Optimizer], *,
                 schedule: Optional[LRSchedule] = None, policy: Policy = FP32,
                 loss_fn: Optional[Callable] = None, mesh=None,
                 param_sharding_fn: Optional[Callable] = None,
                 cross_pod: Optional[CrossPodConfig] = None,
                 quant: Optional[QuantConfig] = None):
        self.cfg = cfg
        self.model = get_family(cfg)
        self.optimizer = optimizer
        self.schedule = schedule if schedule is not None else LRSchedule()
        self.policy = policy
        self.loss_fn = loss_fn or self.model.loss_fn
        self.mesh = mesh
        self.param_sharding_fn = param_sharding_fn
        if cross_pod is not None and not self.supports_cross_pod:
            msg = f"strategy {self.name!r} does not support cross_pod"
            if self.cross_pod_unsupported_reason:
                msg = f"{msg}: {self.cross_pod_unsupported_reason}"
            raise ValueError(msg)
        self.cross_pod = cross_pod
        if quant is not None:
            if quant.frozen and not self.supports_quant_frozen:
                raise ValueError(
                    f"strategy {self.name!r} does not support "
                    f"quant.frozen={quant.frozen!r}: only the grouped "
                    "strategies (hift/hift_pipelined/lisa) keep a frozen "
                    "resident tree to encode")
            if quant.moments and not self.supports_quant_moments:
                raise ValueError(
                    f"strategy {self.name!r} does not support "
                    "quant.moments: it keeps no first-class optimizer "
                    "moment tree (see QuantConfig)")
        self.quant = quant

    # ------------------------------------------------------------ sharding

    @property
    def sharded(self) -> bool:
        """True when a multi-device mesh drives the jitted steps."""
        return self.mesh is not None and self.mesh.size > 1

    def param_shardings(self, tree: PyTree) -> PyTree:
        """NamedSharding tree for a params-shaped tree (structural rule from
        ``dist.shardings`` unless ``param_sharding_fn`` overrides it).

        Known limit of the override: optimizer state / bundles keep the
        structural rule (which mirrors the default placement), so a custom
        ``param_sharding_fn`` that diverges from it makes GSPMD reshard
        moments inside the update until bundle shardings learn to derive
        from the resolved param tree."""
        if self.param_sharding_fn is not None:
            return self.param_sharding_fn(tree, self.mesh)
        return dist_shardings.param_shardings(tree, self.mesh)

    def resident_param_shardings(self, tree: PyTree) -> PyTree:
        """Placement of the FULL param tree between steps.  Default: the
        in-step placement.  Grouped strategies override to replicated —
        between their steps the tree is mostly frozen weights, and keeping
        them resident-replicated makes the per-step frozen transfer a no-op
        instead of an every-step all-gather."""
        return self.param_shardings(tree)

    @property
    def _cross_pod_on(self) -> bool:
        return self.cross_pod is not None and self.cross_pod.pods > 1

    def place_params(self, params: PyTree) -> PyTree:
        """Commit a param tree onto its resident placement (no-op
        unsharded)."""
        if not self.sharded:
            return params
        return jax.device_put(params, self.resident_param_shardings(params))

    def _opt_state_placement(self, opt_state: PyTree,
                             params: PyTree) -> PyTree:
        """Resident placement of ``opt_state`` (what ``init`` gives it)."""
        return dist_shardings.opt_state_shardings(opt_state, params,
                                                  self.mesh)

    def place_state(self, state: TrainState) -> TrainState:
        """Commit a host-resident TrainState onto this strategy's resident
        placement — the landing pad of elastic resize (``dist.elastic``):
        params go to their resident shardings, optimizer state to the same
        placement ``init`` would give it.  ``extra`` stays host-resident
        (visit orders and rng are host state; FPFT's EF residual tree is
        re-placed by the first step's ``device_put``).  Grouped strategies
        override to place params only — their bundles live on host between
        steps anyway."""
        if not self.sharded:
            return state
        params = self.place_params(state.params)
        opt_state = state.opt_state
        if opt_state and jax.tree.leaves(opt_state):
            opt_state = jax.device_put(
                opt_state, self._opt_state_placement(opt_state, params))
        return state.replace(params=params, opt_state=opt_state)

    def _trace_ctx(self):
        """Context the jitted steps are traced/called under: activates the
        ambient activation-sharding constraints (``repro.dist.ctx``) so
        layer-boundary annotations anchor GSPMD and MoE layers take their
        shard_map expert-parallel path."""
        if not self.sharded:
            return contextlib.nullcontext()
        return dist_ctx.activation_sharding(
            self.mesh, dist_shardings.data_axes(self.mesh))

    def init(self, params: PyTree, rng=None) -> TrainState:
        """Pure: build the strategy's :class:`TrainState` from a param tree
        (placing params on the mesh when sharded).  ``rng`` seeds stochastic
        strategies (MeZO); deterministic ones ignore it."""
        raise NotImplementedError

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        """Pure (modulo donation, see class docstring): advance one training
        step, returning the next state and a metrics dict with at least
        ``{"loss", "lr", "strategy"}``."""
        raise NotImplementedError

    def lr_at(self, step: int) -> float:
        return self.schedule.delayed(step, self.k)

    def peak_trainable_params(self, params: PyTree) -> int:
        """Max #params trainable in any single step (paper Fig. 6e)."""
        return tree_size(params)

    def peak_grad_params(self, params: PyTree) -> int:
        """Max #params whose gradient is LIVE at any instant of a step
        (the paper's zeta_3 granularity).  Default: everything trainable is
        resident at once; MeZO overrides to 0 (no backward) and LOMO to one
        fused segment (gradients are consumed layer-by-layer)."""
        return self.peak_trainable_params(params)


# --------------------------------------------------- grouped-step machinery

class _GroupedStrategy(Strategy):
    """Shared machinery for strategies that train ONE Group per step
    (HiFT's fixed sweep, LiSA's random sampling): per-group jitted steps,
    lazy optimizer-state bundles, host offload, Mixed^Hi masters.

    Sharded placement model: the resident full tree is REPLICATED (it is
    frozen weights but for one group), while inside a step the active
    group's params + bundle shard over ``model`` and the batch over
    ``data``.  So the per-step transfers are small (one group in, one group
    out) and the frozen majority never moves."""

    use_cut = True
    offload_optimizer = True
    memory_mode = "hift"
    supports_cross_pod = True
    # the grouped strategies are the quantized-residency home: the resident
    # tree is mostly frozen weights (codec-encoded between steps) and the
    # bundles carry moment trees (bf16-able via moment_dtype)
    supports_quant_frozen = True
    supports_quant_moments = True

    @property
    def _quant_frozen(self) -> Optional[str]:
        return self.quant.frozen if self.quant is not None else None

    def resident_param_shardings(self, tree: PyTree) -> PyTree:
        return dist_shardings.replicated(tree, self.mesh)

    def place_state(self, state: TrainState) -> TrainState:
        # bundles live host-side between steps (offload) and the per-step
        # device_put moves them in regardless — only the params need the
        # resident (replicated) placement restored after a resize
        if not self.sharded:
            return state
        return state.replace(params=self.place_params(state.params))

    def _setup_groups(self, m: int) -> None:
        self.units = self.model.unit_spec(self.cfg)
        self.groups = make_groups(self.units, m)
        self.k = len(self.groups)
        self.memory_m = m
        # per-group caches: gi -> (jitted step, in_shardings|None) and
        # ("wb", gi) -> jitted sharded write_back; gi -> the bundle's bytes
        self._step_fns: dict[Any, tuple[Callable, Any]] = {}
        self._bundle_bytes: dict[int, int] = {}
        self._pipeline: Optional[BundlePipeline] = None

    def _setup_pipeline(self, depth: int) -> None:
        """Enable the bundle pipeline (``core.pipeline``) when ``depth`` >= 2
        and there is actually something to overlap (offloading on, more than
        one group).  Switches the strategy's memory accounting to mode
        ``hift_pipelined`` with a ``depth``-bundle device window: the active
        bundle plus up to depth-1 chunks of lookahead (``memory_model``'s
        ``stream_depth`` and dryrun's per-device adjustment both scale with
        it, so deeper windows stay honestly priced)."""
        if depth <= 1 or not self.offload_optimizer or self.k <= 1:
            return
        self._pipeline = BundlePipeline(depth)
        self.memory_mode = "hift_pipelined"
        self.memory_stream_depth = depth

    def _cast_params(self, params: PyTree) -> PyTree:
        policy = self.policy
        if policy.master_active_group_only:       # Mixed^Hi
            return tree_cast(params, jnp.bfloat16)
        if policy.master_fp32 or policy.name == "fp32":
            return params                         # fp32 master resident
        return tree_cast(params, policy.param_dtype)

    def _resident_params(self, params: PyTree) -> PyTree:
        """Policy-cast, (optionally) codec-encode, and place the resident
        tree — what grouped ``init`` stores in ``TrainState.params``.  Under
        ``QuantConfig(frozen=...)`` every quantizable leaf becomes a
        ``{"q", "s", "t"}`` record (``repro.dist.quant``); the grouping /
        write-back machinery slices those records on dim 0 exactly like the
        plain leaves they encode."""
        params = self._cast_params(params)
        if self._quant_frozen is not None:
            from repro.dist.quant import quantize_tree
            params = quantize_tree(params, self._quant_frozen)
        return self.place_params(params)

    def _cut(self, group: Group) -> Optional[int]:
        if not self.use_cut:
            return None
        return group_cut(self.cfg, group, unit_first_depth)

    def _init_bundle(self, active: PyTree) -> PyTree:
        """Optimizer-state bundle for a group (created on first visit).
        Under a compressed cross-pod reduce the group's per-pod EF residuals
        ride in the bundle (key ``"ef"``, stacked pods-leading fp32) so host
        offload, pipelining and checkpointing cover them for free.

        Under quantized residency (``QuantConfig(frozen=...)``) the bundle
        ALWAYS carries an fp32 master decoded from the group's first-visit
        codec records: the master — not the re-quantized resident copy —
        feeds every later update of this group, so codec rounding never
        compounds across revisits."""
        if self._quant_frozen is not None:
            from repro.dist.quant import dequantize_tree
            master = tree_cast(dequantize_tree(active), jnp.float32)
            bundle = {"opt": self.optimizer.init(master), "master": master}
        elif self.policy.master_active_group_only:
            master = tree_cast(active, jnp.float32)
            bundle = {"opt": self.optimizer.init(master), "master": master}
        else:
            bundle = {"opt": self.optimizer.init(active)}
        if self._cross_pod_on and self.cross_pod.compress:
            bundle["ef"] = init_residuals(bundle.get("master", active),
                                          self.cross_pod.pods)
        return bundle

    def build_step(self, gi: int, example=None) -> tuple[Callable, Any]:
        """The jitted per-group train step (k of these exist).

        Returns ``(fn, in_shardings)``.  Unsharded, ``in_shardings`` is None
        and ``fn`` is a plain jit.  With a multi-device mesh (and ``example =
        (active, frozen, bundle, batch)`` supplying the argument structures)
        the step compiles with explicit shardings from
        ``dist.shardings.group_step_shardings``: active params + optimizer
        bundle partitioned over ``model``, frozen params replicated, the
        batch split over the data axes."""
        group = self.groups[gi]
        cut = self._cut(group)
        cfg, opt, policy = self.cfg, self.optimizer, self.policy
        loss_fn = self.loss_fn
        cp = self.cross_pod if self._cross_pod_on else None
        qf = self._quant_frozen

        def step(active, frozen, bundle, batch, lr):
            if qf is not None:
                # decode the frozen majority in-jit (no host-resident fp32
                # copy ever exists); the active group computes from its fp32
                # bundle master, and only the RESIDENT view re-encodes below
                from repro.dist.quant import dequantize_tree, quantize_tree
                frozen = dequantize_tree(frozen)
                work = tree_cast(bundle["master"], policy.param_dtype)
            else:
                work = active

            def loss_of(a, mb):
                full = merge_params(a, frozen, group)
                return loss_fn(cfg, full, mb, cut=cut,
                               compute_dtype=policy.compute_dtype)

            if cp is not None:
                grads, new_res, loss = crosspod_reduce(
                    lambda mb: jax.value_and_grad(loss_of)(work, mb),
                    work, batch, bundle.get("ef", {}), cp)
                ef = {"ef": new_res} if "ef" in bundle else {}
            else:
                loss, grads = jax.value_and_grad(loss_of)(work, batch)
                ef = {}
            with jax.named_scope("update"):
                if qf is not None:
                    new_master, new_st = opt.update(grads, bundle["opt"],
                                                    bundle["master"], lr)
                    new_active = quantize_tree(
                        tree_cast(new_master, policy.param_dtype), qf)
                    return new_active, {"opt": new_st, "master": new_master,
                                        **ef}, loss
                if policy.master_active_group_only:
                    master, st = bundle["master"], bundle["opt"]
                    new_master, new_st = opt.update(grads, st, master, lr)
                    new_active = tree_cast(new_master, policy.param_dtype)
                    return new_active, {"opt": new_st, "master": new_master,
                                        **ef}, loss
                new_active, new_st = opt.update(grads, bundle["opt"], active,
                                                lr)
                return new_active, {"opt": new_st, **ef}, loss

        if self.sharded and example is not None:
            ins, outs = dist_shardings.group_step_shardings(
                self.mesh, *example,
                active_shardings=self.param_shardings(example[0]))
            # donate the bundle only: `active` leaves whose in-step spec
            # matches the resident placement alias state.params (device_put
            # is a no-op then), and the jitted _write_back still needs that
            # tree alive after this step donates its buffers
            donate = () if jax.devices()[0].platform == "cpu" else (2,)
            return jax.jit(step, donate_argnums=donate, in_shardings=ins,
                           out_shardings=outs), ins
        donate = () if jax.devices()[0].platform == "cpu" else (0, 2)
        return jax.jit(step, donate_argnums=donate), None

    def _fn(self, gi: int, example=None) -> tuple[Callable, Any]:
        if gi not in self._step_fns:
            self._step_fns[gi] = self.build_step(gi, example)
        return self._step_fns[gi]

    def _write_back(self, gi: int, params: PyTree,
                    new_active: PyTree) -> PyTree:
        """Fold the active sub-tree back into the full tree.  Sharded, this
        is itself a jitted computation with ``out_shardings`` pinned to the
        canonical param placement, so the full tree's partitioning cannot
        drift as successive groups write their slices."""
        if not self.sharded:
            return write_back(params, new_active, self.groups[gi])
        key = ("wb", gi)
        if key not in self._step_fns:
            group = self.groups[gi]
            outs = self.resident_param_shardings(params)
            donate = () if jax.devices()[0].platform == "cpu" else (0,)
            fn = jax.jit(lambda p, a: write_back(p, a, group),
                         out_shardings=outs, donate_argnums=donate)
            self._step_fns[key] = (fn, None)
        return self._step_fns[key][0](params, new_active)

    def _bundle_placement(self, bundle: PyTree) -> Optional[PyTree]:
        """The sharding spec a group's bundle enters the jitted step under —
        the SAME ``bundle_shardings`` composition ``group_step_shardings``
        compiles arg 2 with, so a prefetched copy lands exactly where the
        step will donate it (no re-layout at fetch time)."""
        if not self.sharded:
            return None
        return dist_shardings.bundle_shardings(bundle, self.mesh)

    def _nbytes(self, gi: int, bundle: PyTree) -> int:
        """The bytes of group ``gi``'s bundle, summed once per group: a
        bundle keeps its leaves' shapes and dtypes from visit to visit."""
        if gi not in self._bundle_bytes:
            self._bundle_bytes[gi] = sum(x.nbytes
                                         for x in jax.tree.leaves(bundle))
        return self._bundle_bytes[gi]

    def _group_step(self, state: TrainState, batch, gi: int, lr: float,
                    next_gis: Optional[list] = None
                    ) -> tuple[PyTree, PyTree, jnp.ndarray]:
        group = self.groups[gi]
        key = str(gi)
        bundle = state.opt_state.get(key)
        fresh = bundle is None
        with TraceAnnotation("repro.split", group=gi):
            active, frozen = split_params(state.params, group)
            if fresh:
                bundle = self._init_bundle(active)
        lr = jnp.asarray(lr, jnp.float32)
        pipe = self._pipeline
        with self._trace_ctx():
            fn, ins = self._fn(gi, (active, frozen, bundle, batch))
            bspec = ins[2] if ins is not None else None
            if not fresh and self.offload_optimizer:
                # host -> device; sharded bundles keep their partitioning and
                # only change memory kind.  Pipelined, this is usually a
                # cache hit on the copy prefetched during the PREVIOUS step.
                with TraceAnnotation("repro.bundle_fetch", group=gi,
                                     bytes=self._nbytes(gi, bundle)):
                    bundle = (pipe.fetch(key, bundle, bspec)
                              if pipe is not None
                              else device_put_async(bundle, bspec))
            with TraceAnnotation("repro.place_inputs", group=gi):
                if ins is not None:
                    active, frozen, bundle, batch = jax.device_put(
                        (active, frozen, bundle, batch), ins[:4])
                else:
                    # commit every input where it lives: jit keys its cache
                    # on committedness, so an uncommitted first visit and a
                    # revisit whose bundle came back from host would compile
                    # twice
                    active, frozen, bundle, batch = device_put_async(
                        (active, frozen, bundle, batch))
            with TraceAnnotation("repro.dispatch", group=gi):
                new_active, new_bundle, loss = fn(active, frozen, bundle,
                                                  batch, lr)
        if pipe is not None and next_gis:
            # the step above is DISPATCHED, not done: start the upcoming
            # groups' uploads now so they overlap this step's compute.  With
            # depth > 2 the lookahead window covers depth-1 future visits
            # (the pipeline's in-flight budget evicts/blocks past that, so
            # residency never exceeds depth bundles).  First-visit groups
            # have no bundle yet (the step inits one) — nothing to prefetch;
            # revisits of gi inside the window are skipped (its bundle is
            # the one this step is updating).
            with TraceAnnotation("repro.bundle_prefetch", group=gi):
                seen = {gi}
                for ngi in next_gis:
                    if ngi in seen:
                        continue
                    seen.add(ngi)
                    nbundle = state.opt_state.get(str(ngi))
                    if nbundle is not None and not pipe.holds(str(ngi),
                                                              nbundle):
                        pipe.prefetch(str(ngi), nbundle,
                                      self._bundle_placement(nbundle))
        if self.offload_optimizer:
            # a revisit writes the new bundle over the previous one in its
            # pinned host buffers, consuming the input state's bundle
            old = state.opt_state.get(key)
            with TraceAnnotation("repro.bundle_offload", group=gi,
                                 bytes=self._nbytes(gi, new_bundle),
                                 in_place=int(writes_in_place(
                                     new_bundle, old, bspec))):
                new_bundle = (pipe.offload(key, new_bundle, bspec, into=old)
                              if pipe is not None
                              else host_put(new_bundle, bspec, into=old))
        opt_state = dict(state.opt_state)
        opt_state[key] = new_bundle
        with TraceAnnotation("repro.write_back", group=gi):
            params = self._write_back(gi, state.params, new_active)
        return params, opt_state, loss

    def peak_trainable_params(self, params: PyTree) -> int:
        if self._quant_frozen is not None:
            from repro.dist.quant import tree_logical_size
            return max(tree_logical_size(split_params(params, g)[0])
                       for g in self.groups)
        return max(tree_size(split_params(params, g)[0]) for g in self.groups)

    def group_at(self, state: TrainState, step: Optional[int] = None) -> Group:
        raise NotImplementedError


# ------------------------------------------------------------------- HiFT

@register_strategy("hift")
class HiFTStrategy(_GroupedStrategy):
    """Paper Algorithm 1 as k specialized jitted steps.

    Per training step exactly ONE group is active: gradients and optimizer
    state exist only for its sub-tree, the backward graph is cut below it,
    inactive bundles stay on host, and the LR advances once per sweep."""

    name = "hift"

    def __init__(self, cfg, optimizer, *, hift: Optional[HiFTConfig] = None,
                 schedule: Optional[LRSchedule] = None, policy: Policy = FP32,
                 loss_fn: Optional[Callable] = None, mesh=None,
                 param_sharding_fn: Optional[Callable] = None,
                 cross_pod: Optional[CrossPodConfig] = None,
                 quant: Optional[QuantConfig] = None):
        super().__init__(cfg, optimizer, schedule=schedule, policy=policy,
                         loss_fn=loss_fn, mesh=mesh,
                         param_sharding_fn=param_sharding_fn,
                         cross_pod=cross_pod, quant=quant)
        self.hift = hift if hift is not None else HiFTConfig()
        self.use_cut = self.hift.use_cut
        self.offload_optimizer = self.hift.offload_optimizer
        self._setup_groups(self.hift.m)
        self._setup_pipeline(self.hift.pipeline_depth)
        self.order = order_groups(self.groups, self.hift.strategy,
                                  self.hift.seed)

    def init(self, params: PyTree, rng=None) -> TrainState:
        return TrainState(self._resident_params(params), {}, 0,
                          {"order": np.asarray(self.order, np.int64)})

    def _order_at(self, state: TrainState) -> list[int]:
        # the visit order is state (it survives checkpoint/restore even when
        # the restoring process was built with a different seed)
        order = state.extra.get("order") if state.extra else None
        if order is None:
            return list(self.order)
        return [int(x) for x in np.asarray(order).reshape(-1)]

    def group_at(self, state: TrainState, step: Optional[int] = None) -> Group:
        step = int(state.step) if step is None else step
        return self.groups[self._order_at(state)[step % self.k]]

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        order = self._order_at(state)
        gi = order[step % self.k]
        # the sweep order makes the next depth-1 groups knowable NOW — that
        # is what the bundle pipeline exploits (prefetch while this step
        # computes; depth > 2 widens the lookahead window)
        next_gis = ([order[(step + d) % self.k]
                     for d in range(1, self._pipeline.depth)]
                    if self._pipeline else None)
        lr = self.schedule.delayed(step, self.k)
        params, opt_state, loss = self._group_step(state, batch, gi, lr,
                                                   next_gis=next_gis)
        new_state = TrainState(params, opt_state, step + 1, state.extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name,
                           "group": self.groups[gi].label()}


@register_strategy("hift_pipelined")
class PipelinedHiFTStrategy(HiFTStrategy):
    """HiFT with the double-buffered bundle pipeline on by default
    (``core.pipeline``): group g+1's optimizer bundle uploads while group
    g's step computes, and g's offload drains during g+1 — bit-identical
    states, the transfers just leave the critical path.  At most 2 bundles
    are device-resident (``memory_model`` mode ``hift_pipelined``).

    Registered separately so the registry-wide conformance battery holds the
    pipelined schedule to the same contract as serial HiFT (purity,
    mid-sweep checkpoint lockstep resume, memory-model agreement).
    Checkpoints are interchangeable with plain ``hift`` — the pipeline is a
    transfer cache, not state."""

    name = "hift_pipelined"

    def __init__(self, cfg, optimizer, *, hift: Optional[HiFTConfig] = None,
                 **kwargs):
        hift = hift if hift is not None else HiFTConfig()
        if hift.pipeline_depth < 2:
            hift = dataclasses.replace(hift, pipeline_depth=2)
        super().__init__(cfg, optimizer, hift=hift, **kwargs)


# ------------------------------------------------------------------- LiSA

@register_strategy("lisa")
class LiSAStrategy(_GroupedStrategy):
    """Random layer-subset fine-tuning, LiSA-style: every ``switch_every``
    steps the active group is re-sampled uniformly (with replacement) instead
    of swept in HiFT's fixed order.  The sample is a pure function of
    ``(seed, step)``, so checkpoint resume replays the schedule exactly; the
    per-group optimizer bundles persist across activations."""

    name = "lisa"

    def __init__(self, cfg, optimizer, *, lisa: Optional[LiSAConfig] = None,
                 schedule: Optional[LRSchedule] = None, policy: Policy = FP32,
                 loss_fn: Optional[Callable] = None, mesh=None,
                 param_sharding_fn: Optional[Callable] = None,
                 cross_pod: Optional[CrossPodConfig] = None,
                 quant: Optional[QuantConfig] = None):
        super().__init__(cfg, optimizer, schedule=schedule, policy=policy,
                         loss_fn=loss_fn, mesh=mesh,
                         param_sharding_fn=param_sharding_fn,
                         cross_pod=cross_pod, quant=quant)
        self.lisa = lisa if lisa is not None else LiSAConfig()
        self.use_cut = self.lisa.use_cut
        self.offload_optimizer = self.lisa.offload_optimizer
        self._setup_groups(self.lisa.m)
        self._setup_pipeline(self.lisa.pipeline_depth)

    def lr_at(self, step: int) -> float:
        # LiSA trains on a plain per-step schedule (no sweep structure)
        return self.schedule.at_cycle(step)

    def group_index_at(self, step: int) -> int:
        period = step // max(self.lisa.switch_every, 1)
        mix = (self.lisa.seed * 1_000_003 + period) % (2**31 - 1)
        return int(np.random.RandomState(mix).randint(self.k))

    def group_at(self, state: TrainState, step: Optional[int] = None) -> Group:
        step = int(state.step) if step is None else step
        return self.groups[self.group_index_at(step)]

    def init(self, params: PyTree, rng=None) -> TrainState:
        return TrainState(self._resident_params(params), {}, 0, {})

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        gi = self.group_index_at(step)
        # the sample is a pure fn of (seed, step), so the next depth-1
        # groups are knowable now; the pipeline skips prefetch when the
        # sampler lands back on gi inside the window
        next_gis = ([self.group_index_at(step + d)
                     for d in range(1, self._pipeline.depth)]
                    if self._pipeline else None)
        lr = self.lr_at(step)
        params, opt_state, loss = self._group_step(state, batch, gi, lr,
                                                   next_gis=next_gis)
        new_state = TrainState(params, opt_state, step + 1, state.extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name,
                           "group": self.groups[gi].label()}


# ------------------------------------------------------------------- FPFT

def fpft_step_body(cfg, optimizer: Optimizer, policy: Policy = FP32,
                   loss_fn: Optional[Callable] = None) -> Callable:
    """The un-jitted full-parameter step ``step(params, opt_state, batch,
    lr) -> (new_params, new_opt_state, loss)``; :func:`build_fpft_step`
    jits it plainly, ``FPFTStrategy`` compiles it with explicit shardings
    when it has a mesh."""
    model = get_family(cfg)
    loss_fn = loss_fn or model.loss_fn

    def step(params, opt_state, batch, lr):
        def loss_of(p):
            return loss_fn(cfg, p, batch, compute_dtype=policy.compute_dtype)

        loss, grads = jax.value_and_grad(loss_of)(params)
        with jax.named_scope("update"):
            new_params, new_state = optimizer.update(grads, opt_state, params,
                                                     lr)
        return new_params, new_state, loss

    return step


def fpft_crosspod_step_body(cfg, optimizer: Optimizer, policy: Policy = FP32,
                            loss_fn: Optional[Callable] = None,
                            cross_pod: Optional[CrossPodConfig] = None
                            ) -> Callable:
    """The full-parameter step with the cross-pod reduce in the gradient
    path: ``step(params, opt_state, residuals, batch, lr) -> (new_params,
    new_opt_state, new_residuals, loss)``.  ``residuals`` is the stacked
    per-pod EF tree from ``dist.compress.init_residuals(params, pods)``
    (``{}`` when compression is off — the same body serves both)."""
    model = get_family(cfg)
    loss_fn = loss_fn or model.loss_fn
    cp = cross_pod if cross_pod is not None else CrossPodConfig()

    def step(params, opt_state, residuals, batch, lr):
        def loss_and_grad(b):
            return jax.value_and_grad(
                lambda p: loss_fn(cfg, p, b,
                                  compute_dtype=policy.compute_dtype))(params)

        grads, new_res, loss = crosspod_reduce(loss_and_grad, params, batch,
                                               residuals, cp)
        new_params, new_state = optimizer.update(grads, opt_state, params, lr)
        return new_params, new_state, new_res, loss

    return step


def build_fpft_step(cfg, optimizer: Optimizer, policy: Policy = FP32,
                    loss_fn: Optional[Callable] = None) -> Callable:
    """Returns jitted ``step(params, opt_state, batch, lr) ->
    (new_params, new_opt_state, loss)`` updating ALL parameters."""
    donate = () if jax.devices()[0].platform == "cpu" else (0, 1)
    return jax.jit(fpft_step_body(cfg, optimizer, policy, loss_fn),
                   donate_argnums=donate)


@register_strategy("fpft")
class FPFTStrategy(Strategy):
    """Standard full-parameter fine-tuning — the paper's baseline."""

    name = "fpft"
    supports_cross_pod = True
    # every param trains every step — no frozen tree to codec-encode — but
    # the optimizer moment tree is first-class, so bf16 moments apply
    # (fpft_streamed inherits: bf16 moments also halve its wire bytes)
    supports_quant_moments = True

    def __init__(self, cfg, optimizer, *, schedule: Optional[LRSchedule] = None,
                 policy: Policy = FP32, loss_fn: Optional[Callable] = None,
                 mesh=None, param_sharding_fn: Optional[Callable] = None,
                 cross_pod: Optional[CrossPodConfig] = None,
                 quant: Optional[QuantConfig] = None):
        super().__init__(cfg, optimizer, schedule=schedule, policy=policy,
                         loss_fn=loss_fn, mesh=mesh,
                         param_sharding_fn=param_sharding_fn,
                         cross_pod=cross_pod, quant=quant)
        self._step_fn: Optional[tuple[Callable, Any]] = None

    def init(self, params: PyTree, rng=None) -> TrainState:
        if self.policy.name in ("bf16",):
            params = tree_cast(params, self.policy.param_dtype)
        params = self.place_params(params)
        if self.sharded:
            # born sharded: moments built on the default device first would
            # need a whole unsharded copy there
            shapes = jax.eval_shape(self.optimizer.init, params)
            opt_state = jax.jit(
                self.optimizer.init,
                out_shardings=dist_shardings.opt_state_shardings(
                    shapes, params, self.mesh))(params)
        else:
            opt_state = self.optimizer.init(params)
        extra = {}
        if self._cross_pod_on and self.cross_pod.compress:
            # per-pod EF residuals are training state: they checkpoint (and
            # elastic-resize) with everything else
            extra = {"ef_residual": init_residuals(params,
                                                   self.cross_pod.pods)}
        return TrainState(params, opt_state, 0, extra)

    def _fn(self, example=None) -> tuple[Callable, Any]:
        if self._step_fn is None:
            donate = () if jax.devices()[0].platform == "cpu" else (0, 1)
            if self._cross_pod_on:
                body = fpft_crosspod_step_body(self.cfg, self.optimizer,
                                               self.policy, self.loss_fn,
                                               self.cross_pod)
                donate = donate and donate + (2,)  # residuals update in place
                if self.sharded and example is not None:
                    ins, outs = dist_shardings.fpft_crosspod_step_shardings(
                        self.mesh, *example,
                        param_shardings_tree=self.param_shardings(example[0]))
                    self._step_fn = jax.jit(body, donate_argnums=donate,
                                            in_shardings=ins,
                                            out_shardings=outs), ins
                else:
                    self._step_fn = jax.jit(body, donate_argnums=donate), None
            elif self.sharded and example is not None:
                ins, outs = dist_shardings.fpft_step_shardings(
                    self.mesh, *example,
                    param_shardings_tree=self.param_shardings(example[0]))
                fn = jax.jit(fpft_step_body(self.cfg, self.optimizer,
                                            self.policy, self.loss_fn),
                             donate_argnums=donate, in_shardings=ins,
                             out_shardings=outs)
                self._step_fn = fn, ins
            else:
                self._step_fn = build_fpft_step(
                    self.cfg, self.optimizer, self.policy, self.loss_fn), None
        return self._step_fn

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        lr = self.schedule.at_cycle(step)
        if self._cross_pod_on:
            residuals = (state.extra or {}).get("ef_residual", {})
            with self._trace_ctx():
                fn, ins = self._fn((state.params, state.opt_state, residuals,
                                    batch))
                args = (state.params, state.opt_state, residuals, batch)
                if ins is not None:
                    with TraceAnnotation("repro.place_inputs"):
                        args = jax.device_put(args, ins[:4])
                with TraceAnnotation("repro.dispatch"):
                    params, opt_state, new_res, loss = fn(
                        *args, jnp.asarray(lr, jnp.float32))
            extra = dict(state.extra or {})
            if self.cross_pod.compress:
                extra["ef_residual"] = new_res
            new_state = TrainState(params, opt_state, step + 1, extra)
            return new_state, {"loss": loss, "lr": lr, "strategy": self.name}
        with self._trace_ctx():
            fn, ins = self._fn((state.params, state.opt_state, batch))
            args = (state.params, state.opt_state, batch)
            if ins is not None:
                with TraceAnnotation("repro.place_inputs"):
                    args = jax.device_put(args, ins[:3])
            with TraceAnnotation("repro.dispatch"):
                params, opt_state, loss = fn(*args,
                                             jnp.asarray(lr, jnp.float32))
        new_state = TrainState(params, opt_state, step + 1, state.extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name}


# --------------------------------------------------------- FPFT (streamed)

def fpft_grad_body(cfg, policy: Policy = FP32,
                   loss_fn: Optional[Callable] = None) -> Callable:
    """The gradient HALF of the full-parameter step: ``grads(params, batch)
    -> (loss, grads)``.  ``fpft_streamed`` jits this alone (no donation —
    the pre-step params feed the chunked update afterwards) and applies the
    optimizer chunk-by-chunk on the host-driven :class:`ChunkStream` loop;
    sharded it compiles under ``dist.shardings.fpft_grad_shardings``."""
    model = get_family(cfg)
    loss_fn = loss_fn or model.loss_fn

    def grads(params, batch):
        def loss_of(p):
            return loss_fn(cfg, p, batch, compute_dtype=policy.compute_dtype)

        return jax.value_and_grad(loss_of)(params)

    return grads


def fpft_crosspod_grad_body(cfg, policy: Policy = FP32,
                            loss_fn: Optional[Callable] = None,
                            cross_pod: Optional[CrossPodConfig] = None
                            ) -> Callable:
    """:func:`fpft_grad_body` with the cross-pod reduce in the gradient
    path: ``grads(params, residuals, batch) -> (loss, grads, new_residuals)``
    (sharded: ``dist.shardings.fpft_crosspod_grad_shardings``)."""
    model = get_family(cfg)
    loss_fn = loss_fn or model.loss_fn
    cp = cross_pod if cross_pod is not None else CrossPodConfig()

    def grads(params, residuals, batch):
        def loss_and_grad(b):
            return jax.value_and_grad(
                lambda p: loss_fn(cfg, p, b,
                                  compute_dtype=policy.compute_dtype))(params)

        g, new_res, loss = crosspod_reduce(loss_and_grad, params, batch,
                                           residuals, cp)
        return loss, g, new_res

    return grads


@register_strategy("fpft_streamed")
class StreamedFPFTStrategy(FPFTStrategy):
    """ChunkFT-style full-parameter fine-tuning: FPFT's update with the
    optimizer moments HOST-resident, streamed through a bounded device
    window during the update instead of living on device.

    The step splits in two.  (1) One jitted backward produces the full
    gradient tree (``fpft_grad_body`` — params are NOT donated; the
    pre-step values feed the update).  (2) A host-driven loop walks the
    :class:`ChunkLayout` partition of the param tree: for chunk i the
    stream uploads the congruent moment slices (``m``/``v`` for AdamW)
    while chunks ``i+1..i+depth-1`` prefetch behind it, one jitted
    elementwise ``optimizer.update`` call advances that chunk, and the
    updated moments drain back to host.  Device residency of optimizer
    state is therefore ``depth * chunk_bytes``-bounded (``memory_model``
    mode ``fpft_streamed``) instead of ``2 * zeta_1`` — the difference
    that fits 7B full-parameter AdamW on one 48 GB device under Mixed^Hi.

    Requires a **stream-safe** optimizer (``Optimizer.stream_safe``): the
    update must be elementwise with no cross-leaf coupling, so applying it
    per chunk is the SAME arithmetic as the resident tree-at-once update —
    bit-identical, through mid-stream checkpoint resume (test-enforced;
    checkpoints are interchangeable with plain ``fpft``, the stream is a
    transfer schedule, not state).  A global grad clip couples every leaf
    through one norm and is rejected at construction.

    Scalar state entries (AdamW's ``count``) ride every chunk call and keep
    the value from the last one — each chunk sees the same pre-step count,
    exactly as the resident update does."""

    name = "fpft_streamed"
    memory_mode = "fpft_streamed"

    def __init__(self, cfg, optimizer, *, stream: Optional[StreamConfig] = None,
                 **kwargs):
        super().__init__(cfg, optimizer, **kwargs)
        self.stream = stream if stream is not None else StreamConfig()
        if not getattr(optimizer, "stream_safe", False):
            raise ValueError(
                "fpft_streamed needs a stream-safe optimizer (elementwise "
                "update with no cross-leaf coupling; Optimizer.stream_safe) "
                f"— got {getattr(optimizer, 'name', optimizer)!r} with "
                "stream_safe=False.  Turn off grad_clip / the fused-kernel "
                "path, or use the resident 'fpft' strategy")
        self._grad_fn: Optional[tuple[Callable, Any]] = None
        self._chunk_fn: Optional[Callable] = None
        self.memory_stream_depth = self.stream.depth
        self.memory_stream_chunk_bytes = self.stream.chunk_bytes

    # ----------------------------------------------------------- gradients

    def _gfn(self, example=None) -> tuple[Callable, Any]:
        if self._grad_fn is None:
            if self._cross_pod_on:
                body = fpft_crosspod_grad_body(self.cfg, self.policy,
                                               self.loss_fn, self.cross_pod)
                if self.sharded and example is not None:
                    ins, outs = dist_shardings.fpft_crosspod_grad_shardings(
                        self.mesh, *example,
                        param_shardings_tree=self.param_shardings(example[0]))
                    self._grad_fn = jax.jit(body, in_shardings=ins,
                                            out_shardings=outs), ins
                else:
                    self._grad_fn = jax.jit(body), None
            else:
                body = fpft_grad_body(self.cfg, self.policy, self.loss_fn)
                if self.sharded and example is not None:
                    ins, outs = dist_shardings.fpft_grad_shardings(
                        self.mesh, *example,
                        param_shardings_tree=self.param_shardings(example[0]))
                    self._grad_fn = jax.jit(body, in_shardings=ins,
                                            out_shardings=outs), ins
                else:
                    self._grad_fn = jax.jit(body), None
        return self._grad_fn

    # -------------------------------------------------------- chunk update

    def _split_state(self, opt_state: PyTree,
                     params: PyTree) -> tuple[dict, dict]:
        """Partition ``opt_state`` into params-CONGRUENT subtrees (same
        structure and leaf shapes — AdamW's ``m``/``v``; these stream) and
        the rest (scalars like ``count``; these ride every chunk call)."""
        pdef = jax.tree.structure(params)
        pshapes = tuple(tuple(l.shape) for l in jax.tree.leaves(params))
        streamed, resident = {}, {}
        for key, sub in opt_state.items():
            leaves, sdef = jax.tree.flatten(sub)
            if (sdef == pdef
                    and tuple(tuple(l.shape) for l in leaves) == pshapes):
                streamed[key] = sub
            else:
                resident[key] = sub
        return streamed, resident

    def _chunk_update(self) -> Callable:
        """One jitted elementwise optimizer call over single-chunk trees
        (jax re-specializes per chunk shape; layouts cut at most two
        distinct chunk sizes per dtype bucket, so this stays a handful of
        compilations)."""
        if self._chunk_fn is None:
            opt = self.optimizer
            self._chunk_fn = jax.jit(
                lambda g, st, p, lr: opt.update(g, st, p, lr))
        return self._chunk_fn

    def _streamed_update(self, params: PyTree, grads: PyTree,
                         opt_state: PyTree, lr) -> tuple[PyTree, PyTree]:
        """The ChunkFT update sweep: moments in through the bounded window,
        one chunk updated per jitted call, updated moments drained to host.
        Returns ``(new_params, new_opt_state)`` bit-identical to
        ``optimizer.update(grads, opt_state, params, lr)``."""
        layout = ChunkLayout.build(params, self.stream.chunk_bytes)
        streamed, resident = self._split_state(opt_state, params)
        skeys = sorted(streamed)
        stream = ChunkStream(layout, depth=self.stream.depth)
        stream.begin(*(streamed[key] for key in skeys))
        upd = self._chunk_update()
        lr = jnp.asarray(lr, jnp.float32)
        p_chunks = []
        new_resident = dict(resident)
        for i in range(layout.num_chunks):
            schunks = stream.fetch(i)
            pc = layout.extract(params, i)
            gc = layout.extract(grads, i)
            if self.sharded:
                window = (pc, gc) + tuple(schunks)
                window = jax.device_put(
                    window,
                    dist_shardings.chunk_window_shardings(window, self.mesh))
                pc, gc = window[0], window[1]
                schunks = window[2:]
            st = {key: {"_c": c} for key, c in zip(skeys, schunks)}
            st.update(resident)
            new_p, new_st = upd({"_c": gc}, st, {"_c": pc}, lr)
            p_chunks.append(new_p["_c"])
            for key in resident:
                new_resident[key] = new_st[key]
            stream.offload(i, tuple(new_st[key]["_c"] for key in skeys))
        new_params = layout.combine(p_chunks)
        if self.sharded:
            new_params = jax.device_put(
                new_params, self.resident_param_shardings(new_params))
        new_streamed = stream.end()
        new_opt = dict(new_resident)
        # re-pin the reassembled moments host-side (combine computes on
        # device; host_put is the identity on CPU backends)
        new_opt.update({key: host_put(tree)
                        for key, tree in zip(skeys, new_streamed)})
        return new_params, new_opt

    # ---------------------------------------------------------------- api

    def init(self, params: PyTree, rng=None) -> TrainState:
        state = super().init(params, rng)
        streamed, resident = self._split_state(state.opt_state, state.params)
        if streamed:
            opt = dict(resident)
            opt.update({key: host_put(sub) for key, sub in streamed.items()})
            state = state.replace(opt_state=opt)
        return state

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        lr = self.schedule.at_cycle(step)
        params = state.params
        extra = state.extra
        if self._cross_pod_on:
            residuals = (state.extra or {}).get("ef_residual", {})
            with self._trace_ctx():
                fn, ins = self._gfn((params, residuals, batch))
                args = (params, residuals, batch)
                if ins is not None:
                    args = jax.device_put(args, ins[:3])
                loss, grads, new_res = fn(*args)
            if self.cross_pod.compress:
                extra = dict(state.extra or {})
                extra["ef_residual"] = new_res
        else:
            with self._trace_ctx():
                fn, ins = self._gfn((params, batch))
                args = (params, batch)
                if ins is not None:
                    args = jax.device_put(args, ins[:2])
                loss, grads = fn(*args)
        new_params, new_opt = self._streamed_update(params, grads,
                                                    state.opt_state, lr)
        new_state = TrainState(new_params, new_opt, step + 1, extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name}


# ------------------------------------------------------------------- MeZO

@register_strategy("mezo")
class MeZOStrategy(Strategy):
    """Zeroth-order SPSA fine-tuning (MeZO, Malladi et al. 2023): two forward
    passes, no backward, no optimizer state — memory ~= inference.  The z
    noise is regenerated from ``fold_in(rng, step)`` so resume is exact.

    Sharded runs force the *partitionable* threefry PRNG for the step: the
    legacy implementation generates different values once GSPMD partitions
    the bit-generation, which would make the SPSA perturbation (and hence
    the whole run) depend on the mesh shape.  Consequence: a sharded MeZO
    run reproduces any other sharded run of the same seed exactly, on any
    mesh, but not an unsharded run (whose steps keep the legacy stream)."""

    name = "mezo"
    memory_mode = "mezo"

    def __init__(self, cfg, optimizer=None, *, mezo: Optional[MeZOConfig] = None,
                 schedule: Optional[LRSchedule] = None, policy: Policy = FP32,
                 loss_fn: Optional[Callable] = None, mesh=None,
                 param_sharding_fn: Optional[Callable] = None,
                 quant: Optional[QuantConfig] = None):
        # quant is forwarded so the base class rejects it with the uniform
        # unsupported-declaration error (no frozen tree, no moment tree)
        super().__init__(cfg, optimizer, schedule=schedule, policy=policy,
                         loss_fn=loss_fn, mesh=mesh,
                         param_sharding_fn=param_sharding_fn, quant=quant)
        self.mezo = mezo if mezo is not None else MeZOConfig()
        self._step_fn: Optional[tuple[Callable, Any]] = None

    def init(self, params: PyTree, rng=None) -> TrainState:
        if rng is None:
            rng = jax.random.PRNGKey(self.mezo.seed)
        return TrainState(self.place_params(params), {}, 0,
                          {"rng": jnp.asarray(rng, jnp.uint32)})

    def _fn(self, example=None) -> tuple[Callable, Any]:
        if self._step_fn is None:
            cfg, lf = self.cfg, self.loss_fn
            cd, eps = self.policy.compute_dtype, self.mezo.eps

            def loss_of(p, b):
                return lf(cfg, p, b, compute_dtype=cd)

            step = lambda p, b, k, lr: mezo_step(loss_of, p, b, k, lr, eps)
            if self.sharded and example is not None:
                ins, outs = dist_shardings.mezo_step_shardings(
                    self.mesh, *example,
                    param_shardings_tree=self.param_shardings(example[0]))
                self._step_fn = jax.jit(step, in_shardings=ins,
                                        out_shardings=outs), ins
            else:
                self._step_fn = jax.jit(step), None
        return self._step_fn

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        key = jax.random.fold_in(jnp.asarray(state.extra["rng"], jnp.uint32),
                                 step)
        lr = self.schedule.at_cycle(step)
        rng_ctx = (jax.threefry_partitionable(True) if self.sharded
                   else contextlib.nullcontext())
        with self._trace_ctx(), rng_ctx:
            fn, ins = self._fn((state.params, batch))
            args = (state.params, batch)
            if ins is not None:
                args = jax.device_put(args, ins[:2])
            params, loss = fn(*args, key, jnp.asarray(lr, jnp.float32))
        new_state = TrainState(params, state.opt_state, step + 1, state.extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name}

    def peak_grad_params(self, params: PyTree) -> int:
        return 0            # two forward passes, no backward at all


# ------------------------------------------------------------------- LOMO

_tree_sqsum = opt_base.global_sq_norm


def _sgd_tree(params: PyTree, grads: PyTree, lr, scale, weight_decay: float):
    """The exact update of ``repro.optim.sgd`` with pre-scaled (clipped)
    gradients, applied to one fused segment."""
    def upd(p, g):
        g32 = (g * scale).astype(g.dtype).astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        return (p32 - lr * (g32 + weight_decay * p32)).astype(p.dtype)

    return jax.tree.map(upd, params, grads)


def _lomo_fused_body(cfg, pieces, grad_clip: float,
                     weight_decay: float) -> Callable:
    """The genuinely fused step for families exposing ``lomo_pieces``.

    One forward scan saves each layer's input; the backward is a hand-rolled
    REVERSE scan whose body runs one layer's ``jax.vjp`` (rematerializing
    that layer's forward, as with remat="layer") and applies the SGD update
    right there — so at any instant only a single layer's gradient is live,
    never the stacked (n_layers, ...) grad tree the standard scan transpose
    would produce.  With ``grad_clip`` > 0 the update needs the global grad
    norm first, so a norm-only reverse sweep runs before the update sweep
    (LOMO's two-backward clipping; each sweep still frees every gradient as
    it goes)."""
    embed_fn, block_fn, head_loss_fn = pieces

    def step(params, batch, lr):
        ep, lp, hp = params["embed"], params["layers"], params["head"]
        h0, embed_vjp = jax.vjp(lambda e: embed_fn(e, batch), ep)

        def fwd(h, layer_p):
            return block_fn(layer_p, h), h      # save the layer INPUT

        h_out, resid = jax.lax.scan(fwd, h0, lp)
        loss, head_vjp = jax.vjp(
            lambda H, E, x: head_loss_fn(H, E, x, batch), hp, ep, h_out)
        one = jnp.ones_like(loss)

        def layer_vjp(layer_p, h_in, dh):
            _, vjp = jax.vjp(lambda p, x: block_fn(p, x), layer_p, h_in)
            return vjp(dh)                      # (g_layer, dh_below)

        def embed_grad(dh0, g_embed_from_head):
            (g,) = embed_vjp(dh0)               # token-gather cotangent
            return jax.tree.map(jnp.add, g, g_embed_from_head)

        def norm_sweep():
            g_head, g_emb_h, dh = head_vjp(one)

            def body(dh, xs):
                g, dh = layer_vjp(*xs, dh)
                return dh, _tree_sqsum(g)       # grad reduced, then dead

            dh0, sqs = jax.lax.scan(body, dh, (lp, resid), reverse=True)
            # the exact global norm needs the ELEMENTWISE embedding-grad sum
            # (cross term between head-side and gather-side cotangents), so
            # for tied heads this sweep keeps g_emb_h live alongside one
            # layer's grad — the only place residency exceeds one segment
            return (_tree_sqsum(g_head) + jnp.sum(sqs)
                    + _tree_sqsum(embed_grad(dh0, g_emb_h)))

        def update_sweep(scale):
            g_head, g_emb_h, dh = head_vjp(one)
            new_hp = _sgd_tree(hp, g_head, lr, scale, weight_decay)
            # SGD is linear in the gradient, so the head-side embedding
            # cotangent (for tied heads a full (vocab, d) buffer; zeros
            # otherwise) is consumed NOW as its own increment — carrying the
            # weight-decay term, applied on the ORIGINAL params — instead of
            # being pinned live across the whole reverse scan waiting for
            # the gather-side grad.  The post-scan increment then adds no
            # second decay term, keeping the math one exact SGD step.
            sq_emb_h = _tree_sqsum(g_emb_h)
            ep_mid = _sgd_tree(ep, g_emb_h, lr, scale, weight_decay)

            def body(dh, xs):
                g, dh = layer_vjp(*xs, dh)
                return dh, (_sgd_tree(xs[0], g, lr, scale, weight_decay),
                            _tree_sqsum(g))     # grad consumed in-iteration

            dh0, (new_lp, sqs) = jax.lax.scan(body, dh, (lp, resid),
                                              reverse=True)
            (g_gather,) = embed_vjp(dh0)
            new_ep = _sgd_tree(ep_mid, g_gather, lr, scale, 0.0)
            # reported norm: segment-wise (the tied-head cross term between
            # the two embedding increments is dropped — keeping it would
            # pin both buffers; exact for untied heads).  The CLIP scale
            # never uses this: norm_sweep computes the exact global norm.
            sq = (_tree_sqsum(g_head) + jnp.sum(sqs) + sq_emb_h
                  + _tree_sqsum(g_gather))
            return {"embed": new_ep, "layers": new_lp, "head": new_hp}, sq

        if grad_clip and grad_clip > 0:
            sq = norm_sweep()
            new_params, _ = update_sweep(opt_base.clip_scale(grad_clip, sq))
        else:
            new_params, sq = update_sweep(jnp.float32(1.0))
        return new_params, loss, jnp.sqrt(sq)

    return step


# ------------------------------------------- staged pieces (LomoPieces)
#
# The generalized fused-backward driver for families exposing the staged
# ``models.base.LomoPieces`` protocol (moe / hybrid / xlstm / encdec; the
# dense transformer keeps its original 3-tuple body above).  One forward
# saves per-stage layer inputs; the reverse traversal below runs one
# layer's vjp per scan iteration and hands its gradient to a consume
# callback (SGD update, Adafactor update, or norm-only reduction), so
# gradient residency stays one fused grain + the small accumulating
# segments (embed, shared, the side cotangent).


def _tadd(a, b):
    """Leafwise add, None-transparent (None = empty cotangent)."""
    if a is None:
        return b
    if b is None:
        return a
    return jax.tree.map(jnp.add, a, b)


def _tzeros(t):
    return None if t is None else jax.tree.map(jnp.zeros_like, t)


def _pieces_forward(pieces: LomoPieces, ep, stages, sp, hp, batch):
    """Run the segmented forward, saving each stage's layer inputs.

    Returns ``(loss, head_vjp, saved)`` where ``saved[i] = (resid, side,
    init_vjp)`` — everything the reverse traversal needs.  ``init_vjp`` is
    the vjp of stage i's ``stage_inits`` w.r.t. ``(embed_p,
    prev_stage_out)``: pulling ``(dh0, dside)`` back through it yields that
    stage's embedding-gradient contribution and the cotangent seeding the
    previous stage's reverse scan."""
    saved = []
    prev = None
    for i, fn in enumerate(pieces.stage_fns):
        init_i = pieces.stage_inits[i]
        (h0, side), init_vjp = jax.vjp(
            lambda e, pv, init_i=init_i: init_i(e, pv, batch), ep, prev)

        def fwd(h, lp, fn=fn, side=side):
            return fn(lp, sp, side, h), h       # save the layer INPUT

        h_out, resid = jax.lax.scan(fwd, h0, stages[i])
        saved.append((resid, side, init_vjp))
        prev = h_out
    loss, head_vjp = jax.vjp(
        lambda H, E, x: pieces.head_loss_fn(H, E, x, batch), hp, ep, prev)
    return loss, head_vjp, saved


def _pieces_reverse(pieces: LomoPieces, sp, stages, saved, dh,
                    consume: Callable, stage_extra=None):
    """Reverse-scan every stage (last to first), consuming gradients.

    ``consume(i, layer_p, g_layer, extra_slice) -> ys`` runs inside stage
    i's reverse scan with ONE layer's full gradient; whatever pytree it
    returns rides the scan ys (per-stage stacked in ``ys_all[i]``).
    ``stage_extra[i]`` threads extra per-layer scan inputs (AdaLomo's
    moment slices).  Shared-segment and side cotangents accumulate in the
    scan carry; stage-init vjps chain ``dh`` backwards and collect the
    embedding gradient.  Returns ``(g_embed_from_inits, g_shared, ys_all)``.
    """
    g_emb = None
    g_sh = None
    ys_all = [None] * len(pieces.stage_fns)
    for i in reversed(range(len(pieces.stage_fns))):
        resid, side, init_vjp = saved[i]
        fn = pieces.stage_fns[i]
        extra = None if stage_extra is None else stage_extra[i]

        def body(carry, xs, fn=fn, side=side, i=i, has_extra=extra is not None):
            dh_c, dside, gsh = carry
            if has_extra:
                lp, h_in, ex = xs
            else:
                lp, h_in = xs
                ex = None
            _, vjp = jax.vjp(lambda p, s, sd, x: fn(p, s, sd, x),
                             lp, sp, side, h_in)
            g_layer, g_shared, g_side, dh_below = vjp(dh_c)
            return ((dh_below, _tadd(dside, g_side), _tadd(gsh, g_shared)),
                    consume(i, lp, g_layer, ex))

        xs = (stages[i], resid) if extra is None else (stages[i], resid, extra)
        carry0 = (dh, _tzeros(side), _tzeros(sp))
        (dh0, dside, gsh_i), ys_all[i] = jax.lax.scan(body, carry0, xs,
                                                      reverse=True)
        g_sh = _tadd(g_sh, gsh_i)
        g_e, dh = init_vjp((dh0, dside))
        g_emb = _tadd(g_emb, g_e)
    return g_emb, g_sh, ys_all


def _lomo_pieces_body(cfg, pieces: LomoPieces, grad_clip: float,
                      weight_decay: float) -> Callable:
    """The staged fused step with LOMO's SGD update (same two-backward
    clipping protocol as ``_lomo_fused_body``; the clip scale always comes
    from the norm-only sweep's exact global norm)."""

    def step(params, batch, lr):
        ep, stages, sp, hp = pieces.split(params)
        loss, head_vjp, saved = _pieces_forward(pieces, ep, stages, sp, hp,
                                                batch)
        one = jnp.ones_like(loss)

        def sweep(scale):
            """scale None -> norm-only (grads reduced to squared sums)."""
            g_head, g_emb_head, dh = head_vjp(one)
            update = scale is not None

            def consume(i, lp, g, ex):
                if update:
                    return (_sgd_tree(lp, g, lr, scale, weight_decay),
                            _tree_sqsum(g))
                return _tree_sqsum(g)

            g_emb, g_sh, ys = _pieces_reverse(pieces, sp, stages, saved, dh,
                                              consume)
            g_emb = _tadd(g_emb, g_emb_head)   # tied heads; zeros otherwise
            sq = (_tree_sqsum(g_head) + _tree_sqsum(g_emb)
                  + _tree_sqsum(g_sh))
            if not update:
                return None, sq + sum(jnp.sum(y) for y in ys)
            sq = sq + sum(jnp.sum(y[1]) for y in ys)
            new_ep = _sgd_tree(ep, g_emb, lr, scale, weight_decay)
            new_sp = (_sgd_tree(sp, g_sh, lr, scale, weight_decay)
                      if sp is not None else None)
            new_hp = _sgd_tree(hp, g_head, lr, scale, weight_decay)
            new_stages = tuple(y[0] for y in ys)
            return pieces.merge(new_ep, new_stages, new_sp, new_hp), sq

        if grad_clip and grad_clip > 0:
            _, sq = sweep(None)
            new_params, _ = sweep(opt_base.clip_scale(grad_clip, sq))
        else:
            new_params, sq = sweep(jnp.float32(1.0))
        return new_params, loss, jnp.sqrt(sq)

    return step


def _staged_pieces(model, cfg, compute_dtype) -> Optional[LomoPieces]:
    """The family's ``lomo_pieces`` as a staged :class:`LomoPieces` (legacy
    3-tuples are adapted), or None when the family has none."""
    if not hasattr(model, "lomo_pieces"):
        return None
    pieces = model.lomo_pieces(cfg, compute_dtype=compute_dtype)
    if isinstance(pieces, LomoPieces):
        return pieces
    return LomoPieces.from_embed_block_head(*pieces)


def lomo_pieces_of(cfg, policy: Policy = FP32) -> Optional[LomoPieces]:
    """Public probe used by strategies/tests: the staged pieces a config's
    family would train the fused path with (None -> fallback)."""
    return _staged_pieces(get_family(cfg), cfg, policy.compute_dtype)


# ---------------------------------------------------------------- AdaLomo


def adalomo_init_opt_state(cfg, params: PyTree) -> PyTree:
    """AdaLomo's resident optimizer state: Adafactor-style factored second
    moments for every leaf — O(r+c) floats per matrix — plus the shared
    step count.  Stacked segments (from the family's ``unit_spec``) factor
    PER LAYER, so a ``(L, r, c)`` trunk leaf stores ``vr (L, r)`` /
    ``vc (L, c)`` and a stacked bias ``(L, d)`` keeps a full per-layer
    ``v`` instead of being factored across layers."""
    model = get_family(cfg)
    stacked = {u.key for u in model.unit_spec(cfg) if u.kind == "stacked"}
    moments = {
        key: jax.tree.map(
            lambda p, _s=(key in stacked): moment_init(p, stacked=_s), sub)
        for key, sub in params.items()
    }
    return {"moments": moments, "count": jnp.zeros((), jnp.int32)}


def _ada_tree(params: PyTree, grads: PyTree, moms: PyTree, lr, beta2, scale,
              acfg: "AdaLomoConfig"):
    """One Adafactor update over a (sub-)tree with pre-scaled (clipped)
    gradients -> ``(new_params, new_moments)``.  ``matrix_rms=True`` makes
    the update-RMS clip per trailing matrix, so applying this to a whole
    stacked segment (fallback path) and to its per-layer slices inside the
    reverse scan (fused path) is the same arithmetic."""

    def upd(p, g, m):
        g = (g * scale).astype(g.dtype)
        return leaf_update(p, g, m, lr, beta2, eps1=acfg.eps1,
                           clip_threshold=acfg.clip_threshold,
                           weight_decay=acfg.weight_decay, matrix_rms=True,
                           relative_step=acfg.relative_step, eps2=acfg.eps2)

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(moms)
    out = [upd(p, g, m) for p, g, m in zip(flat_p, flat_g, flat_m)]
    return (treedef.unflatten([o[0] for o in out]),
            treedef.unflatten([o[1] for o in out]))


def _adalomo_pieces_body(cfg, pieces: LomoPieces,
                         acfg: "AdaLomoConfig") -> Callable:
    """The fused AdaLomo step: same reverse scans as LOMO, but each layer's
    gradient feeds an Adafactor update whose factored moments ride the scan
    as per-layer xs/ys slices (``pieces.split``/``merge`` restructure the
    moment tree exactly like the params — leading dims only).  Segments
    whose total gradient only exists at the end of the traversal (embed,
    zamba2's shared block) accumulate their gradient — one segment-sized
    buffer — and update once; Adafactor is nonlinear in the gradient, so
    unlike SGD those updates cannot be split into increments."""

    def step(params, opt_state, batch, lr):
        ep, stages, sp, hp = pieces.split(params)
        ep_m, stage_ms, sp_m, hp_m = pieces.split(opt_state["moments"])
        count = opt_state["count"] + 1
        beta2 = beta2_at(count, acfg.decay_rate)
        loss, head_vjp, saved = _pieces_forward(pieces, ep, stages, sp, hp,
                                                batch)
        one = jnp.ones_like(loss)

        def norm_sweep():
            g_head, g_emb_head, dh = head_vjp(one)
            g_emb, g_sh, ys = _pieces_reverse(
                pieces, sp, stages, saved, dh,
                lambda i, lp, g, ex: _tree_sqsum(g))
            g_emb = _tadd(g_emb, g_emb_head)
            return (_tree_sqsum(g_head) + _tree_sqsum(g_emb)
                    + _tree_sqsum(g_sh) + sum(jnp.sum(y) for y in ys))

        def update_sweep(scale):
            g_head, g_emb_head, dh = head_vjp(one)

            def consume(i, lp, g, mom):
                new_lp, new_m = _ada_tree(lp, g, mom, lr, beta2, scale, acfg)
                return new_lp, new_m, _tree_sqsum(g)

            g_emb, g_sh, ys = _pieces_reverse(pieces, sp, stages, saved, dh,
                                              consume, stage_extra=stage_ms)
            g_emb = _tadd(g_emb, g_emb_head)
            new_hp, new_hp_m = _ada_tree(hp, g_head, hp_m, lr, beta2, scale,
                                         acfg)
            new_ep, new_ep_m = _ada_tree(ep, g_emb, ep_m, lr, beta2, scale,
                                         acfg)
            if sp is not None:
                new_sp, new_sp_m = _ada_tree(sp, g_sh, sp_m, lr, beta2,
                                             scale, acfg)
            else:
                new_sp, new_sp_m = None, None
            sq = (_tree_sqsum(g_head) + _tree_sqsum(g_emb)
                  + _tree_sqsum(g_sh) + sum(jnp.sum(y[2]) for y in ys))
            new_params = pieces.merge(new_ep, tuple(y[0] for y in ys),
                                      new_sp, new_hp)
            new_moms = pieces.merge(new_ep_m, tuple(y[1] for y in ys),
                                    new_sp_m, new_hp_m)
            return new_params, new_moms, sq

        if acfg.grad_clip and acfg.grad_clip > 0:
            sq = norm_sweep()
            new_params, new_moms, _ = update_sweep(
                opt_base.clip_scale(acfg.grad_clip, sq))
        else:
            new_params, new_moms, sq = update_sweep(jnp.float32(1.0))
        return (new_params, {"moments": new_moms, "count": count}, loss,
                jnp.sqrt(sq))

    return step


def _adalomo_generic_body(cfg, loss_fn: Callable, compute_dtype,
                          acfg: "AdaLomoConfig") -> Callable:
    """Fallback for families without ``lomo_pieces`` (or a custom loss_fn):
    segment-tuple vjp exactly like LOMO's, with the Adafactor update applied
    per top-level segment.  The stacked-aware moment layout + per-matrix RMS
    make this the same arithmetic as the fused path, just with coarser
    gradient liveness (one whole segment at a time)."""

    def step(params, opt_state, batch, lr):
        keys = list(params)
        count = opt_state["count"] + 1
        beta2 = beta2_at(count, acfg.decay_rate)

        def loss_of(*parts):
            return loss_fn(cfg, dict(zip(keys, parts)), batch,
                           compute_dtype=compute_dtype)

        loss, pullback = jax.vjp(loss_of, *(params[key] for key in keys))
        one = jnp.ones_like(loss)

        def sweep(scale):
            gparts = pullback(one)
            sq = jnp.float32(0.0)
            new_p, new_m = {}, {}
            for key, g in reversed(list(zip(keys, gparts))):  # cotangent order
                sq = sq + _tree_sqsum(g)
                if scale is not None:
                    new_p[key], new_m[key] = _ada_tree(
                        params[key], g, opt_state["moments"][key], lr, beta2,
                        scale, acfg)
            if scale is None:
                return sq, None, None
            return (sq, {key: new_p[key] for key in keys},
                    {key: new_m[key] for key in keys})

        if acfg.grad_clip and acfg.grad_clip > 0:
            sq, _, _ = sweep(None)
            _, new_params, new_moms = sweep(
                opt_base.clip_scale(acfg.grad_clip, sq))
        else:
            sq, new_params, new_moms = sweep(jnp.float32(1.0))
        return (new_params, {"moments": new_moms, "count": count}, loss,
                jnp.sqrt(sq))

    return step


def adalomo_step_body(cfg, policy: Policy = FP32,
                      loss_fn: Optional[Callable] = None,
                      adalomo: Optional["AdaLomoConfig"] = None,
                      pieces=None) -> Callable:
    """The un-jitted AdaLomo step ``step(params, opt_state, batch, lr) ->
    (new_params, new_opt_state, loss, grad_norm)`` with ``opt_state`` from
    :func:`adalomo_init_opt_state`.  Dispatches like :func:`lomo_step_body`
    (and takes the same optional pre-resolved ``pieces``): staged/legacy
    ``lomo_pieces`` -> the fused per-layer reverse scan, otherwise the
    segment-vjp fallback.  ``launch.dryrun`` lowers this body directly for
    its ``--strategy adalomo`` cells."""
    acfg = adalomo if adalomo is not None else AdaLomoConfig()
    model = get_family(cfg)
    if loss_fn is None:
        if pieces is None and hasattr(model, "lomo_pieces"):
            pieces = model.lomo_pieces(cfg, compute_dtype=policy.compute_dtype)
        if pieces is not None:
            if not isinstance(pieces, LomoPieces):
                pieces = LomoPieces.from_embed_block_head(*pieces)
            return _adalomo_pieces_body(cfg, pieces, acfg)
    return _adalomo_generic_body(cfg, loss_fn or model.loss_fn,
                                 policy.compute_dtype, acfg)


def _lomo_generic_body(cfg, loss_fn: Callable, compute_dtype, grad_clip: float,
                       weight_decay: float) -> Callable:
    """Fallback for families without ``lomo_pieces`` (or a custom loss_fn):
    one ``jax.vjp`` over the TUPLE of top-level param segments, consumed in
    cotangent (head-first) order.  Gradient liveness is bounded by the
    largest top-level segment — coarser than the per-layer fused path, since
    a stacked trunk's grad arrives as one array from the scan transpose."""

    def step(params, batch, lr):
        keys = list(params)

        def loss_of(*parts):
            return loss_fn(cfg, dict(zip(keys, parts)), batch,
                           compute_dtype=compute_dtype)

        loss, pullback = jax.vjp(loss_of, *(params[key] for key in keys))
        one = jnp.ones_like(loss)

        def sweep(scale):
            """One backward; ``scale`` None -> reduce each segment's grad to
            its squared norm only (nothing retained)."""
            gparts = pullback(one)
            sq = jnp.float32(0.0)
            new = {}
            for key, g in reversed(list(zip(keys, gparts))):  # cotangent order
                sq = sq + _tree_sqsum(g)
                if scale is not None:
                    new[key] = _sgd_tree(params[key], g, lr, scale,
                                         weight_decay)
            return sq, {key: new[key] for key in keys} if scale is not None \
                else None

        if grad_clip and grad_clip > 0:
            sq, _ = sweep(None)
            _, new_params = sweep(opt_base.clip_scale(grad_clip, sq))
        else:
            sq, new_params = sweep(jnp.float32(1.0))
        return new_params, loss, jnp.sqrt(sq)

    return step


def lomo_step_body(cfg, policy: Policy = FP32, loss_fn: Optional[Callable] = None,
                   lomo: Optional[LOMOConfig] = None,
                   pieces=None) -> Callable:
    """The un-jitted LOMO step ``step(params, batch, lr) -> (new_params,
    loss, grad_norm)``.  Dispatches to the per-layer fused backward when the
    model family exposes ``lomo_pieces`` and no custom ``loss_fn`` overrides
    the forward; otherwise to the segment-wise vjp fallback.  ``pieces``
    lets a caller that already resolved the family's ``lomo_pieces`` (the
    strategies, which also read the fused grain off them) pass the same
    object in instead of re-building it.
    ``launch.dryrun`` lowers this body directly for its LOMO cells."""
    lomo = lomo if lomo is not None else LOMOConfig()
    model = get_family(cfg)
    if loss_fn is None:
        if pieces is None and hasattr(model, "lomo_pieces"):
            pieces = model.lomo_pieces(cfg, compute_dtype=policy.compute_dtype)
        if isinstance(pieces, LomoPieces):
            # staged protocol (moe/hybrid/xlstm/encdec): generalized driver
            return _lomo_pieces_body(cfg, pieces, lomo.grad_clip,
                                     lomo.weight_decay)
        if pieces is not None:   # legacy 3-tuple (dense transformer)
            return _lomo_fused_body(cfg, pieces, lomo.grad_clip,
                                    lomo.weight_decay)
    return _lomo_generic_body(cfg, loss_fn or model.loss_fn,
                              policy.compute_dtype, lomo.grad_clip,
                              lomo.weight_decay)


class _FusedBackwardStrategy(Strategy):
    """Shared machinery for the fused-backward strategies (LOMO/AdaLomo):
    one-time ``lomo_pieces`` resolution (fused path vs segment-vjp
    fallback, plus the fused grain feeding the memory accounting), the
    gradient-residency accounting itself, and the jitted-step cache with
    donation-safe shardings.  Subclasses set ``_donate`` (non-CPU donated
    arg positions), implement ``_step_shardings(example)``, and build
    ``self._body`` from the ONE pieces object ``_setup_fused`` resolved."""

    _donate: tuple = (0,)
    # part of the API: tests/test_stream_fpft.py pins the full rejection
    # message and docs/sharding.md cites it
    cross_pod_unsupported_reason = (
        "the fused backward consumes each piece's gradient inside the "
        "reverse scan, so no whole-gradient tree ever exists for the "
        "cross-pod reduce to compress (a per-piece reduce hook is a "
        "ROADMAP item); use fpft/fpft_streamed — or the grouped "
        "hift/lisa — for compressed cross-pod data parallelism")

    def _setup_fused(self, loss_fn) -> None:
        """Resolve the family's raw ``lomo_pieces`` exactly once; the same
        object feeds the step-body builder (``pieces=`` argument) and the
        memory accounting, so they can never disagree."""
        self._fused = loss_fn is None and hasattr(self.model, "lomo_pieces")
        self._pieces = None
        if self._fused:
            self._pieces = self.model.lomo_pieces(
                self.cfg, compute_dtype=self.policy.compute_dtype)
            if isinstance(self._pieces, LomoPieces):
                # staged pieces may fuse at super-block grain (zamba2/
                # xlstm): liveness_m consecutive units per fused grain
                self.memory_m = self._pieces.liveness_m
        self._step_fn: Optional[tuple[Callable, Any]] = None

    def _setup_stream(self, stream: Optional["StreamConfig"]) -> None:
        """Opt-in segment streaming (``stream=StreamConfig(...)``) for
        host-resident trees: the step's input segments (params; AdaLomo's
        factored moments too) upload through a ``depth``-bounded
        :class:`BundlePipeline` window — segment s+1's upload is dispatched
        while segment s's is still in flight, overlapping the transfers
        with each other and (async dispatch) with the previous step's
        compute — and the updated segments drain back to host after the
        step, off the critical path.  The jitted reverse scan itself still
        consumes the fully-uploaded tree (splitting the scan per segment is
        a ROADMAP follow-up), so this bounds transfer STAGING, not step
        residency; states are bit-identical to the unstreamed schedule
        (transfers only — test-enforced)."""
        self.stream = stream
        self._seg_pipe = (BundlePipeline(stream.depth)
                          if stream is not None else None)

    def _stream_in(self, tree: PyTree, prefix: str) -> PyTree:
        """Upload a dict-of-segments through the bounded window (no-op when
        streaming is off).  Pipeline keys are ``prefix:segment`` so params
        and moments share one window budget without colliding."""
        pipe = self._seg_pipe
        if pipe is None or not isinstance(tree, dict) or not tree:
            return tree
        keys = list(tree)
        out = {}
        for i, key in enumerate(keys):
            # keep depth-1 segment uploads in flight ahead of the active one
            for j in range(i, min(i + pipe.depth - 1, len(keys))):
                kj = f"{prefix}:{keys[j]}"
                if not pipe.holds(kj, tree[keys[j]]):
                    pipe.prefetch(kj, tree[keys[j]], None)
            out[key] = pipe.fetch(f"{prefix}:{key}", tree[key], None)
        return out

    def _stream_out(self, tree: PyTree, prefix: str) -> PyTree:
        """Deferred host offload of a step's output segments (no-op when
        streaming is off): D2H copies dispatch now and drain while the next
        step runs (:meth:`BundlePipeline.offload`)."""
        pipe = self._seg_pipe
        if pipe is None or not isinstance(tree, dict) or not tree:
            return tree
        return {key: pipe.offload(f"{prefix}:{key}", sub)
                for key, sub in tree.items()}

    def _step_shardings(self, example):
        raise NotImplementedError

    def _fn(self, example=None) -> tuple[Callable, Any]:
        if self._step_fn is None:
            donate = () if jax.devices()[0].platform == "cpu" \
                else self._donate
            if self.sharded and example is not None:
                ins, outs = self._step_shardings(example)
                self._step_fn = jax.jit(self._body, donate_argnums=donate,
                                        in_shardings=ins,
                                        out_shardings=outs), ins
            else:
                self._step_fn = jax.jit(self._body,
                                        donate_argnums=donate), None
        return self._step_fn

    def peak_grad_params(self, params: PyTree) -> int:
        if self._fused:
            # per-grain liveness: the reverse scan holds one fused grain's
            # grads (one unit for plain stacks; a super-block of
            # memory_m = liveness_m units for zamba2/xlstm pieces)
            units = self.model.unit_spec(self.cfg)
            return max(tree_size(split_params(params, g)[0])
                       for g in make_groups(units, self.memory_m))
        # generic path: one top-level segment at a time (a stacked trunk's
        # grad is a single array from the scan transpose)
        return max(tree_size(sub) for sub in params.values())


@register_strategy("lomo")
class LOMOStrategy(_FusedBackwardStrategy):
    """LOMO (Lv et al. 2023): full-parameter SGD with the update fused into
    the backward pass.  Numerically this IS one plain SGD step on all
    parameters — grads are taken at the pre-step params, clipped by global
    norm, and applied — but no full gradient tree is ever resident: each
    fused segment's gradient is consumed (param updated, buffer dead) before
    the next one materializes, and like MeZO the optimizer bundle is empty.
    The memory story is therefore params + one segment's grads, against
    FPFT/SGD's params + all grads (``memory_model`` mode="lomo").

    The optimizer argument is accepted for registry uniformity and ignored;
    SGD hyper-parameters live in :class:`LOMOConfig`."""

    name = "lomo"
    memory_mode = "lomo"

    def __init__(self, cfg, optimizer=None, *, lomo: Optional[LOMOConfig] = None,
                 schedule: Optional[LRSchedule] = None, policy: Policy = FP32,
                 loss_fn: Optional[Callable] = None, mesh=None,
                 param_sharding_fn: Optional[Callable] = None,
                 cross_pod: Optional[CrossPodConfig] = None,
                 stream: Optional[StreamConfig] = None,
                 quant: Optional[QuantConfig] = None):
        # cross_pod / quant are forwarded so the base class rejects them
        # with the uniform unsupported-declaration errors (the fused
        # backward has no whole-gradient-tree reduce point to compress, no
        # frozen tree to encode and no moment tree to narrow)
        super().__init__(cfg, optimizer, schedule=schedule, policy=policy,
                         loss_fn=loss_fn, mesh=mesh,
                         param_sharding_fn=param_sharding_fn,
                         cross_pod=cross_pod, quant=quant)
        self.lomo = lomo if lomo is not None else LOMOConfig()
        self._setup_fused(loss_fn)
        self._setup_stream(stream)
        self._body = lomo_step_body(cfg, policy=self.policy, loss_fn=loss_fn,
                                    lomo=self.lomo, pieces=self._pieces)

    def init(self, params: PyTree, rng=None) -> TrainState:
        if self.policy.name in ("bf16",):
            params = tree_cast(params, self.policy.param_dtype)
        return TrainState(self.place_params(params), {}, 0, {})

    def _step_shardings(self, example):
        return dist_shardings.lomo_step_shardings(
            self.mesh, *example,
            param_shardings_tree=self.param_shardings(example[0]))

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        lr = self.schedule.at_cycle(step)
        params_in = self._stream_in(state.params, "p")
        with self._trace_ctx():
            fn, ins = self._fn((params_in, batch))
            args = (params_in, batch)
            if ins is not None:
                args = jax.device_put(args, ins[:2])
            params, loss, gnorm = fn(*args, jnp.asarray(lr, jnp.float32))
        params = self._stream_out(params, "p")
        new_state = TrainState(params, state.opt_state, step + 1, state.extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name,
                           "grad_norm": gnorm}


# ---------------------------------------------------------------- AdaLomo

@register_strategy("adalomo")
class AdaLomoStrategy(_FusedBackwardStrategy):
    """AdaLomo (Lv et al. 2023): LOMO's fused backward with Adafactor-grade
    adaptivity.  Each reverse-scan iteration applies a factored second-moment
    update (row/col statistics + RMS-scaled step, the exact leaf math of
    ``repro.optim.adafactor``) to one layer the moment its gradient arrives —
    so like ``lomo`` no full gradient tree is ever resident, but unlike
    ``lomo`` the update is adaptive.  The price over LOMO's empty bundle is
    the factored statistics: O(r+c) floats per (r, c) matrix, kept in
    ``opt_state = {"moments", "count"}`` (``memory_model`` mode="adalomo"
    prices them; for a 7B model they are ~MBs against AdamW's ~52 GB).

    Families with ``lomo_pieces`` get the per-layer fused path (the moments
    ride the reverse scan as per-layer slices); others take the segment-vjp
    fallback — same arithmetic, coarser gradient liveness.  Segments whose
    gradient accumulates across the sweep (embeddings, zamba2's shared
    block) update once at the end: Adafactor is nonlinear in the gradient,
    so LOMO's increment-splitting trick does not apply to them.

    The optimizer argument is accepted for registry uniformity and ignored;
    hyper-parameters live in :class:`AdaLomoConfig`."""

    name = "adalomo"
    memory_mode = "adalomo"
    _donate = (0, 1)

    def __init__(self, cfg, optimizer=None, *,
                 adalomo: Optional[AdaLomoConfig] = None,
                 schedule: Optional[LRSchedule] = None, policy: Policy = FP32,
                 loss_fn: Optional[Callable] = None, mesh=None,
                 param_sharding_fn: Optional[Callable] = None,
                 cross_pod: Optional[CrossPodConfig] = None,
                 stream: Optional[StreamConfig] = None,
                 quant: Optional[QuantConfig] = None):
        # cross_pod / quant are forwarded so the base class rejects them
        # with the uniform unsupported-declaration errors (as LOMO)
        super().__init__(cfg, optimizer, schedule=schedule, policy=policy,
                         loss_fn=loss_fn, mesh=mesh,
                         param_sharding_fn=param_sharding_fn,
                         cross_pod=cross_pod, quant=quant)
        self.adalomo = adalomo if adalomo is not None else AdaLomoConfig()
        self._setup_fused(loss_fn)
        self._setup_stream(stream)
        self._body = adalomo_step_body(cfg, policy=self.policy,
                                       loss_fn=loss_fn, adalomo=self.adalomo,
                                       pieces=self._pieces)

    def init(self, params: PyTree, rng=None) -> TrainState:
        if self.policy.name in ("bf16",):
            params = tree_cast(params, self.policy.param_dtype)
        params = self.place_params(params)
        opt_state = adalomo_init_opt_state(self.cfg, params)
        if self.sharded:
            opt_state = jax.device_put(
                opt_state, dist_shardings.param_shardings(opt_state,
                                                          self.mesh))
        return TrainState(params, opt_state, 0, {})

    def _step_shardings(self, example):
        return dist_shardings.adalomo_step_shardings(
            self.mesh, *example,
            param_shardings_tree=self.param_shardings(example[0]))

    def step(self, state: TrainState, batch) -> tuple[TrainState, Metrics]:
        step = int(state.step)
        lr = self.schedule.at_cycle(step)
        params_in = self._stream_in(state.params, "p")
        opt_in = state.opt_state
        if self._seg_pipe is not None:
            opt_in = dict(opt_in)
            opt_in["moments"] = self._stream_in(opt_in["moments"], "m")
        with self._trace_ctx():
            fn, ins = self._fn((params_in, opt_in, batch))
            args = (params_in, opt_in, batch)
            if ins is not None:
                args = jax.device_put(args, ins[:3])
            params, opt_state, loss, gnorm = fn(*args,
                                                jnp.asarray(lr, jnp.float32))
        params = self._stream_out(params, "p")
        if self._seg_pipe is not None:
            opt_state = dict(opt_state)
            opt_state["moments"] = self._stream_out(opt_state["moments"], "m")
        new_state = TrainState(params, opt_state, step + 1, state.extra)
        return new_state, {"loss": loss, "lr": lr, "strategy": self.name,
                           "grad_norm": gnorm}


# ------------------------------------------------------------------ Runner

class Runner:
    """Mutable facade over ``(strategy, TrainState)`` — the driver surface.

    ``train/loop.py``, launchers, benchmarks and the legacy
    ``HiFTRunner``/``FPFTRunner`` shims all program against this one class;
    the functional API stays one attribute away (``runner.strategy``,
    ``runner.state``)."""

    def __init__(self, strategy: Strategy, params: PyTree, rng=None):
        self.strategy = strategy
        self.state = strategy.init(params, rng)
        self.last_metrics: Metrics = {}

    # ------------------------------------------------------------- views

    @property
    def params(self) -> PyTree:
        return self.state.params

    @property
    def step_count(self) -> int:
        return int(self.state.step)

    @property
    def k(self) -> int:
        return self.strategy.k

    @property
    def opt_state(self) -> PyTree:
        return self.state.opt_state

    @property
    def opt_states(self) -> PyTree:
        """Grouped strategies: bundles keyed by int group index (legacy view)."""
        os = self.state.opt_state
        if isinstance(os, dict) and all(
                isinstance(key, str) and key.isdigit() for key in os):
            return {int(key): v for key, v in os.items()}
        return os

    # -------------------------------------------------------------- step

    def train_step(self, batch) -> jnp.ndarray:
        with TraceAnnotation("repro.train_step", step=self.step_count):
            state, self.last_metrics = self.strategy.step(self.state, batch)
            with TraceAnnotation("repro.release"):
                self.state = state         # frees the previous state's arrays
        return self.last_metrics["loss"]

    def lr_for_step(self, step: Optional[int] = None) -> float:
        return self.strategy.lr_at(self.step_count if step is None else step)

    def group_for_step(self, step: Optional[int] = None) -> Group:
        return self.strategy.group_at(self.state, step)

    # ----------------------------------------------------------- metrics

    def peak_trainable_params(self) -> int:
        return self.strategy.peak_trainable_params(self.state.params)

    def total_params(self) -> int:
        return tree_size(self.state.params)

    # ----------------------------------------------------- checkpointing

    def state_dict(self) -> dict:
        return self.state.to_tree()

    def load_state_dict(self, state: dict) -> None:
        self.state = TrainState.from_tree(state)

    def __getattr__(self, name: str):
        # delegate static attributes (groups, order, units, cfg, hift, ...)
        if name.startswith("_") or "strategy" not in self.__dict__:
            raise AttributeError(name)
        return getattr(self.__dict__["strategy"], name)
