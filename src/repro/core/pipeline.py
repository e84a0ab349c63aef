"""Double-buffered optimizer-bundle pipeline for grouped strategies.

HiFT's per-step memory saving keeps inactive optimizer bundles on host
(the paper's MoveOptimizerState2CPU / MoveOptimizerState2GPU); the serial
hot loop pays for that on the critical path — the bundle upload happens
right before the jitted step and the offload right after.  But HiFT's
sweep order (``TrainState.extra["order"]``) makes the NEXT group knowable
one step ahead, and LiSA's sampled schedule is a pure function of
``(seed, step)``, so both can stream optimizer bytes overlapped with
compute (ChunkFT-style):

  - :meth:`BundlePipeline.prefetch` starts the host->device upload of
    group ``g+1``'s bundle right after group ``g``'s step is DISPATCHED,
    so the transfer runs while ``g`` computes;
  - :meth:`BundlePipeline.fetch` hands that device copy to group
    ``g+1``'s step (falling back to a fresh upload on a cache miss — a
    restored checkpoint, a forked state, a re-sampled LiSA group);
  - :meth:`BundlePipeline.offload` dispatches ``g``'s device->host copy
    but defers BLOCKING on it, so the drain overlaps step ``g+1``.

A bounded in-flight budget keeps at most ``depth`` bundles device-resident
(default 2: the active group's plus one prefetched-or-draining), so the
paper's k-fold optimizer-state claim degrades to exactly 2/k, never more —
``repro.core.memory_model`` accounts this as mode ``"hift_pipelined"`` and
the strategy conformance battery cross-checks it.

Donation-safe handshake with the sharded path: the prefetched device tree
is placed with the SAME ``dist.shardings.bundle_shardings`` spec the jitted
step was compiled with (``group_step_shardings`` arg 2), so the step's
in-step ``device_put`` is a no-op and the step may donate the buffer; the
pipeline pops its reference in :meth:`fetch` before the step consumes it,
leaving the donated buffer unaliased.

Correctness invariant (test-enforced, ``tests/test_pipeline.py``): every
value still round-trips host<->device unchanged, so a pipelined run is
bit-identical to the serial schedule — the pipeline only moves WHEN the
transfers happen, never what they carry.

The host/device placement primitives (:func:`host_put`,
:func:`device_put_async`) live here too; ``repro.core.strategy`` re-exports
them for compatibility.

Offloads write in place where they can: a revisited group's new bundle is
stored into the pinned-host buffers that already hold its previous bundle
(donated to a small compiled store whose output aliases them), so each
group's host buffers are allocated, and mapped for DMA, once per process
rather than at every offload.  The host tree passed as ``into`` is consumed.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


# --------------------------------------------------------------- placement

def _leaf_placements(tree: PyTree, memory_kind: str) -> PyTree:
    """Per-leaf sharding tree targeting ``memory_kind`` but PRESERVING each
    leaf's current device placement.  This is what keeps unsharded
    multi-device runs from funnelling every transfer through device 0: a
    leaf living on device 3 offloads to (and re-uploads from) device 3's
    pinned host memory, not ``jax.devices()[0]``'s.  Leaves without a
    sharding (numpy arrays fresh from a checkpoint) fall back to the
    default device."""
    fallback = jax.sharding.SingleDeviceSharding(jax.devices()[0],
                                                 memory_kind=memory_kind)

    def one(leaf):
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            return fallback
        return sharding.with_memory_kind(memory_kind)

    return jax.tree.map(one, tree)


def _host_placements(tree: PyTree, shardings: PyTree = None) -> PyTree:
    """Where :func:`host_put` places ``tree``: ``shardings`` with the memory
    kind pinned_host, or each leaf's own placement flipped to it."""
    if shardings is not None:
        return jax.tree.map(lambda s: s.with_memory_kind("pinned_host"),
                            shardings)
    return _leaf_placements(tree, "pinned_host")


def reusable(new: PyTree, old: PyTree, host: PyTree) -> bool:
    """True when ``old`` can take ``new`` in place: the same tree structure
    and, leaf by leaf, the same shape and dtype, each ``old`` leaf already
    placed on ``host`` (a pinned_host placement) and not yet consumed.  A
    first visit (``old`` None), a bundle restored from a checkpoint (numpy
    or device-memory leaves) or one of another layout is not."""
    if old is None:
        return False
    new_leaves, treedef = jax.tree.flatten(new)
    old_leaves, old_def = jax.tree.flatten(old)
    host_leaves = jax.tree.leaves(host)
    if old_def != treedef or len(host_leaves) != len(new_leaves):
        return False
    return all(getattr(o, "sharding", None) == h and o.shape == n.shape
               and o.dtype == n.dtype
               and not (isinstance(o, jax.Array) and o.is_deleted())
               for n, o, h in zip(new_leaves, old_leaves, host_leaves))


def compile_store(new: PyTree, old: PyTree, host: PyTree):
    """The store ``(new, old) -> host copy of new`` compiled with ``old``
    donated, so XLA may alias each output onto the ``old`` leaf of the same
    shape.  ``keep_unused`` keeps ``old`` a parameter of the program: the
    store never reads it, and jit would otherwise prune it and alias
    nothing."""
    return jax.jit(lambda n, o: n, donate_argnums=(1,), keep_unused=True,
                   out_shardings=host).lower(new, old).compile()


def aliases_every_output(compiled) -> bool:
    """True when every byte a compiled store writes to host memory lands in
    a donated host argument (``memory_analysis``), so the store allocates
    no host buffer."""
    stats = compiled.memory_analysis()
    out = getattr(stats, "host_output_size_in_bytes", 0)
    return out > 0 and stats.host_alias_size_in_bytes == out


# (bundle structure, leaf shapes/dtypes/placements, host placements) ->
# the compiled in-place store, or None where XLA gives it no alias: one
# entry per bundle layout, decided when it is compiled, never per offload
_STORES: dict = {}


def _store(new: PyTree, old: PyTree, host: PyTree):
    if not reusable(new, old, host):
        return None
    leaves, treedef = jax.tree.flatten(new)
    key = (treedef, tuple((x.shape, x.dtype, x.sharding) for x in leaves),
           tuple(jax.tree.leaves(host)))
    if key not in _STORES:
        compiled = compile_store(new, old, host)
        _STORES[key] = compiled if aliases_every_output(compiled) else None
    return _STORES[key]


def writes_in_place(tree: PyTree, into: PyTree = None,
                    shardings: PyTree = None) -> bool:
    """Whether ``host_put(tree, shardings, into=into)`` stores ``tree`` into
    ``into``'s buffers rather than into freshly allocated ones."""
    if jax.devices()[0].platform == "cpu":
        return False
    return _store(tree, into, _host_placements(tree, shardings)) is not None


def host_put(tree: PyTree, shardings: PyTree = None,
             into: PyTree = None) -> PyTree:
    """Move a pytree to host memory (the paper's MoveOptimizerState2CPU).

    On TPU this uses the pinned_host memory kind so the transfer back is an
    async DMA; on the CPU backend arrays are already host-resident.  When a
    ``shardings`` tree is given (mesh-sharded bundles), each leaf keeps its
    partitioning and only the memory kind changes, so a sharded optimizer
    bundle offloads without gathering.  Without one, the placement is
    derived per leaf from the tree's CURRENT sharding (memory kind flipped
    to pinned_host) — see :func:`_leaf_placements`.

    ``into`` is the host tree this one replaces (a group's previous
    bundle).  Where it is :func:`reusable` and the store aliases, ``tree``
    is written into ``into``'s pinned buffers, which are donated: ``into``
    must not be read afterwards (a pending upload from it still completes
    first).  Otherwise the copy goes to freshly allocated pinned buffers,
    which the runtime must map for DMA.

    A backend that refuses pinned_host raises here: keeping multi-GB
    optimizer state on device would silently undo the offload that HiFT's
    memory claim rests on."""
    if jax.devices()[0].platform == "cpu":
        return tree
    host = _host_placements(tree, shardings)
    store = _store(tree, into, host)
    if store is not None:
        return store(tree, into)
    return jax.device_put(tree, host)


def device_put_async(tree: PyTree, shardings: PyTree = None) -> PyTree:
    """MoveOptimizerState2GPU analogue — dispatches async, overlaps compute.

    With a ``shardings`` tree the transfer restores the mesh placement
    (device memory kind).  Without one, each leaf returns to its OWN
    device's default memory (sharding preserved, memory kind flipped back
    to "device") rather than funnelling through device 0."""
    if jax.devices()[0].platform == "cpu":
        return tree
    if shardings is None:
        shardings = _leaf_placements(tree, "device")
    return jax.device_put(tree, shardings)


# ---------------------------------------------------------------- pipeline

@dataclasses.dataclass
class PipelineStats:
    """Observability counters (reset with the pipeline, never checkpointed).

    ``max_resident`` counts device-resident bundles at their peak — the
    active step's bundle plus everything prefetched or draining — and is
    what the in-flight budget bounds (<= depth)."""
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    prefetches: int = 0
    offloads: int = 0
    budget_waits: int = 0
    max_resident: int = 0


class BundlePipeline:
    """Double-buffered host<->device scheduler for per-group optimizer
    bundles.  One instance per grouped strategy; it holds only REDUNDANT
    device copies of host-resident state (a transfer cache), so it is
    invisible to the Strategy purity contract: losing it (fresh process,
    checkpoint restore) costs a prefetch miss, never correctness.

    Cache-coherence rule: a prefetched entry is keyed by group AND by the
    identity of the host tree it was uploaded from.  :meth:`fetch` only
    serves an entry whose source IS the bundle the caller holds — a state
    restored from checkpoint, a forked ``TrainState``, or a LiSA re-sample
    therefore falls back to a plain upload instead of reading a stale
    device copy."""

    def __init__(self, depth: int = 2):
        if depth < 2:
            raise ValueError(f"pipeline depth must be >= 2, got {depth}; "
                             "use the serial path for depth 1")
        self.depth = depth
        # group key -> (source host tree, device copy)
        self._prefetched: dict[str, tuple[PyTree, PyTree]] = {}
        # host copies of deferred offloads, oldest first; an entry leaves
        # the deque when we BLOCK on it (D2H done => device buffer free)
        self._draining: deque[PyTree] = deque()
        self.stats = PipelineStats()

    # ------------------------------------------------------------- budget

    def device_resident(self, active: int = 1) -> int:
        """Device-resident bundle count: the active step's (``active``) plus
        prefetched copies plus offloads still draining."""
        return active + len(self._prefetched) + len(self._draining)

    def holds(self, key: str, source: PyTree = None) -> bool:
        """True when a prefetched copy for ``key`` is already in flight —
        lookahead drivers (:class:`ChunkStream`, the grouped strategies'
        depth>2 window) use this to avoid re-uploading on every step.  With
        ``source`` given, the in-flight copy only counts when it was
        uploaded from that exact host tree (the same identity rule
        :meth:`fetch` serves under)."""
        entry = self._prefetched.get(key)
        if entry is None:
            return False
        return source is None or entry[0] is source

    def _note_resident(self) -> None:
        self.stats.max_resident = max(self.stats.max_resident,
                                      self.device_resident())

    def _make_room(self, active: int) -> None:
        """Make room for one incoming device bundle: first block on the
        oldest draining offload(s) — on real hardware the drain was
        dispatched a full step ago and overlaps compute, so this wait is
        usually zero — then, if still over budget (stale cache entries from
        forked/restored states), evict prefetched copies oldest-first.
        Evicting only ever costs a future re-upload, never correctness."""
        def over():
            return (active + len(self._prefetched) + len(self._draining)
                    + 1 > self.depth)
        while over() and self._draining:
            self.stats.budget_waits += 1
            jax.block_until_ready(self._draining.popleft())
        while over() and self._prefetched:
            self._prefetched.pop(next(iter(self._prefetched)))

    # ------------------------------------------------------------ actions

    def fetch(self, key: str, bundle: PyTree,
              shardings: PyTree = None) -> PyTree:
        """Device copy of ``bundle`` for the ACTIVE step.  Serves the
        prefetched copy when its source matches, else uploads now (the
        serial path's behavior).  The entry is popped — after this call the
        pipeline holds no reference, so the jitted step may donate it."""
        entry = self._prefetched.pop(key, None)
        if entry is not None and entry[0] is bundle:
            self.stats.prefetch_hits += 1
            return entry[1]
        self.stats.prefetch_misses += 1
        self._make_room(active=0)   # the upload becomes the active bundle
        self._note_resident()
        return device_put_async(bundle, shardings)

    def prefetch(self, key: str, bundle: PyTree,
                 shardings: PyTree = None) -> None:
        """Start the async upload of the NEXT group's bundle.  Call right
        after dispatching the current step so the H2D transfer overlaps its
        compute.  Respects the in-flight budget first (see
        :meth:`_make_room`); replacing an existing entry for ``key`` frees
        the old copy."""
        self._prefetched.pop(key, None)
        self._make_room(active=1)
        self._prefetched[key] = (bundle, device_put_async(bundle, shardings))
        self.stats.prefetches += 1
        self._note_resident()

    def offload(self, key: str, new_bundle: PyTree,
                shardings: PyTree = None, into: PyTree = None) -> PyTree:
        """Deferred host offload of a step's output bundle: the D2H copy is
        DISPATCHED now (it runs once the step finishes, overlapping the next
        step) but this call does not block on it — the device buffer is
        accounted as draining until the budget reclaims it.  Before
        enqueueing, older drains are blocked down to ``depth - 2`` entries so
        the NEXT step's device bundle (prefetched or freshly initialized)
        still fits the budget.  Returns the host tree to store in
        ``TrainState.opt_state``.  ``into`` is the group's previous host
        bundle, written over in place where :func:`host_put` can."""
        while len(self._draining) > max(self.depth - 2, 0):
            self.stats.budget_waits += 1
            jax.block_until_ready(self._draining.popleft())
        if into is not None and any(h is into for h in self._draining):
            # a drain the store is about to donate: wait on it here, since
            # a donated tree cannot be waited on later
            self.stats.budget_waits += 1
            jax.block_until_ready(into)
            self._draining = deque(h for h in self._draining
                                   if h is not into)
        host = host_put(new_bundle, shardings, into=into)
        self._draining.append(host)
        self.stats.offloads += 1
        # the draining buffer IS the step's donated active buffer, so at
        # this instant nothing else counts as "active" (active=0)
        self.stats.max_resident = max(self.stats.max_resident,
                                      self.device_resident(active=0))
        return host

    def flush(self) -> None:
        """Block until every deferred offload has drained and drop all
        prefetched copies (e.g. before a deliberate synchronization point).
        State values are unaffected — this only empties the cache."""
        while self._draining:
            jax.block_until_ready(self._draining.popleft())
        self._prefetched.clear()


# ----------------------------------------------------- chunk-granular layer
#
# ChunkFT-style generalization: instead of moving whole optimizer BUNDLES,
# partition any params-congruent pytree into fixed-byte chunks and stream
# the chunks through the same bounded BundlePipeline window.  This is what
# lets full-parameter AdamW keep its moments host-resident and still update
# every parameter each step (strategy ``fpft_streamed``): the device never
# holds more than ``depth`` chunks of optimizer state at once.


@dataclasses.dataclass(frozen=True)
class ChunkLayout:
    """A fixed-byte chunking of a pytree, by ELEMENT ranges.

    Built once per tree structure (:meth:`build`), a layout partitions the
    flattened element stream of every dtype bucket (the per-dtype packed
    grouping of ``kernels.ops._bucket_layout``) into chunks of at most
    ``chunk_bytes`` bytes.  Chunks never span dtype buckets, so each
    extracted chunk is ONE 1-D array of uniform dtype.

    The pieces are element ranges ``(leaf_index, start, n)`` — dtype-blind —
    so one layout built from the param tree applies unchanged to every
    params-CONGRUENT tree (grads, AdamW's fp32 ``m``/``v``): chunk ``i`` of
    params, grads and moments always covers the same elements, which is what
    makes a per-chunk elementwise optimizer update bit-identical to the
    resident whole-tree update.

    Invariants (property-tested in ``tests/test_chunk_properties.py``):
    every element of the tree lands in exactly one chunk, and
    ``combine(extract(tree, i) for i)`` is bit-equal to ``tree``."""

    treedef: Any
    shapes: tuple            # per-leaf shapes, flatten order
    chunk_bytes: int
    # per chunk: tuple of (leaf_index, start_element, n_elements) pieces
    chunks: tuple

    @classmethod
    def build(cls, tree: PyTree, chunk_bytes: int) -> "ChunkLayout":
        """Partition ``tree`` into chunks of at most ``chunk_bytes`` bytes
        (measured in the tree's own dtypes; at least one element per chunk).
        Raises ``ValueError`` for a non-positive chunk size."""
        if chunk_bytes <= 0:
            raise ValueError(
                f"chunk_bytes must be > 0, got {chunk_bytes}; a zero-byte "
                "chunk can hold no element")
        from repro.kernels.ops import _bucket_layout
        flat, treedef = jax.tree.flatten(tree)
        spec = tuple((int(l.size), str(jnp.dtype(l.dtype).name),
                      str(jnp.dtype(l.dtype).name)) for l in flat)
        chunks = []
        for (dtype_name, _), idxs in _bucket_layout(spec):
            itemsize = jnp.dtype(dtype_name).itemsize
            per_chunk = max(chunk_bytes // itemsize, 1)
            pieces, room = [], per_chunk
            for i in idxs:
                start, left = 0, spec[i][0]
                while left:
                    take = min(left, room)
                    pieces.append((i, start, take))
                    start, left, room = start + take, left - take, room - take
                    if room == 0:
                        chunks.append(tuple(pieces))
                        pieces, room = [], per_chunk
            if pieces:
                chunks.append(tuple(pieces))
        return cls(treedef=treedef,
                   shapes=tuple(tuple(l.shape) for l in flat),
                   chunk_bytes=int(chunk_bytes), chunks=tuple(chunks))

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)

    def extract(self, tree: PyTree, i: int):
        """Chunk ``i`` of any layout-congruent tree as one 1-D array."""
        flat = self.treedef.flatten_up_to(tree)
        parts = [jnp.reshape(flat[li], (-1,))[s:s + n]
                 for li, s, n in self.chunks[i]]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def combine(self, chunks: list) -> PyTree:
        """Reassemble a full tree from all ``num_chunks`` chunk arrays —
        bit-equal to the tree the chunks were extracted from."""
        if len(chunks) != self.num_chunks:
            raise ValueError(f"combine needs all {self.num_chunks} chunks, "
                             f"got {len(chunks)}")
        segs: dict[int, list] = {}
        for chunk, pieces in zip(chunks, self.chunks):
            off = 0
            for li, start, n in pieces:
                segs.setdefault(li, []).append((start, chunk[off:off + n]))
                off += n
        leaves = []
        for li, shape in enumerate(self.shapes):
            parts = [a for _, a in sorted(segs[li], key=lambda t: t[0])]
            flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            leaves.append(jnp.reshape(flat, shape))
        return jax.tree.unflatten(self.treedef, leaves)


class ChunkStream:
    """Stream the chunks of one or more congruent host-resident trees
    through a bounded device window.

    Wraps a :class:`BundlePipeline` (so depth < 2 raises the same
    ``ValueError`` and the in-flight budget/coherence rules are shared) but
    keys entries by chunk index and prefetches a LOOKAHEAD window: after
    serving chunk ``i``, chunks ``i+1 .. i+depth-1`` start uploading, so at
    most ``depth`` chunks are device-resident while the consumer walks the
    stream front to back (``stats.max_resident`` asserts it).

    Usage, one sweep per training step::

        stream = ChunkStream(layout, depth=4)
        stream.begin(m_tree, v_tree)          # snapshot host chunks once
        for i in range(layout.num_chunks):
            m_c, v_c = stream.fetch(i)        # device window (hit from i>=1)
            ...update...
            stream.offload(i, (new_m_c, new_v_c))
        new_m, new_v = stream.end()           # reassembled host trees

    ``begin`` extracts every chunk ONCE so prefetch entries keep a stable
    source identity (the pipeline's coherence rule serves an entry only when
    its source object matches)."""

    def __init__(self, layout: ChunkLayout, depth: int = 2):
        self.layout = layout
        self.pipeline = BundlePipeline(depth)
        self._source: Optional[list] = None
        self._done: Optional[list] = None

    @property
    def depth(self) -> int:
        return self.pipeline.depth

    @property
    def stats(self) -> PipelineStats:
        return self.pipeline.stats

    def begin(self, *trees: PyTree) -> "ChunkStream":
        """Snapshot the host-side chunks of ``trees`` (all layout-congruent)
        and prime the lookahead window."""
        self._source = [tuple(self.layout.extract(t, i) for t in trees)
                        for i in range(self.layout.num_chunks)]
        self._done = [None] * self.layout.num_chunks
        self._lookahead(0)
        return self

    def _lookahead(self, next_i: int, shardings=None) -> None:
        # fill the window up to depth-1 chunks ahead of the active one
        hi = min(next_i + self.depth - 1, self.layout.num_chunks)
        for j in range(next_i, hi):
            if not self.pipeline.holds(str(j)):
                self.pipeline.prefetch(str(j), self._source[j], shardings)

    def fetch(self, i: int, shardings=None) -> tuple:
        """Device copies of chunk ``i`` of every tree passed to ``begin``,
        then top up the lookahead window (chunks ``i+1..i+depth-1``)."""
        if self._source is None:
            raise RuntimeError("ChunkStream.fetch before begin()")
        got = self.pipeline.fetch(str(i), self._source[i], shardings)
        self._lookahead(i + 1, shardings)
        return got

    def offload(self, i: int, new_chunks: tuple, shardings=None) -> None:
        """Dispatch chunk ``i``'s updated arrays back to host (deferred
        drain, as :meth:`BundlePipeline.offload`)."""
        self._done[i] = self.pipeline.offload(str(i), new_chunks, shardings)

    def end(self) -> list:
        """Host trees reassembled from every offloaded chunk — one per tree
        passed to ``begin``, in the same order."""
        missing = [i for i, c in enumerate(self._done) if c is None]
        if missing:
            raise RuntimeError(f"ChunkStream.end with chunks {missing[:4]}... "
                               "never offloaded")
        n_trees = len(self._done[0])
        out = [self.layout.combine([c[t] for c in self._done])
               for t in range(n_trees)]
        self._source = self._done = None
        return out
