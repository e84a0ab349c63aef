"""Jit'd public wrappers for the Pallas kernels + the shared tiled-update
substrate the fused optimizer kernels build on.

Every kernel resolves ``interpret=None`` through :func:`default_interpret`,
which reads the backend when the kernel is traced: compiled Mosaic on TPU,
XLA emulation of the kernel body everywhere else.  So the HiFT hot loop
selects the compiled path from the backend instead of hardcoding
interpretation.

The ``fused_*_update`` functions are the pytree-wide fused optimizer
updates (AdamW / SGD-momentum / AdaGrad — the paper's three headline
optimizers).  Leaves are bucketed by dtype and packed into ONE contiguous
(8,128)-tiled stream per bucket, so a whole HiFT group updates in one
Pallas launch per bucket instead of one per leaf, and the flat layout
(bucketing, sizes, padding) is derived once per tree structure
(:func:`_bucket_layout` is cached) rather than re-done every step.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp


def default_interpret(interpret=None) -> bool:
    """Resolve an ``interpret=None`` request from the backend at call time:
    compiled Mosaic on TPU, XLA interpretation everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# --------------------------------------------------------- tiled substrate

# one sublane multiple that satisfies every dtype's min tile: fp32 needs
# (8,128), bf16 (16,128), int8/fp8 (32,128)
_SUBLANES = 32


def tile_layout(n: int, block: int) -> tuple[int, int, tuple[int, ...]]:
    """``(rows, block_rows, grid)`` for a flat length-``n`` array laid out
    as (rows, 128) VPU tiles in blocks of ``block`` elements.

    ``rows`` is always a whole multiple of ``block_rows`` — the padding
    guarantees divisibility up front, so the grid needs no truthy-tail
    branch and every program instance sees a full block."""
    if n <= 0:
        raise ValueError(f"need a non-empty array, got n={n}")
    rows_min = -(-n // (128 * _SUBLANES)) * _SUBLANES
    block_rows = min(max(block // 128, _SUBLANES) // _SUBLANES * _SUBLANES,
                     rows_min)
    grid_n = -(-rows_min // block_rows)
    return grid_n * block_rows, block_rows, (grid_n,)


def pack_flat(x, rows: int, dtype=None):
    """Flatten, cast, zero-pad to ``rows * 128`` and tile as (rows, 128)."""
    flat = x.reshape(-1)
    if dtype is not None:
        flat = flat.astype(dtype)
    return jnp.pad(flat, (0, rows * 128 - flat.size)).reshape(rows, 128)


# VMEM-sized default block for the compiled path: ~10 streams x 1024 rows x
# 128 lanes x 4B = ~5 MB of the ~16 MB budget
_COMPILED_BLOCK = 131072


def elementwise_update_call(kernel, tiled: list, scalars: list,
                            out_dtypes: list, *, n: int, block: int = None,
                            interpret=None, donate: tuple = ()):
    """Run an elementwise-update Pallas kernel over flat streams.

    ``tiled`` arrays are packed to a common (rows, 128) layout (each keeps
    its own dtype); ``scalars`` ride as (1,) fp32 refs; outputs share the
    tile layout with dtypes ``out_dtypes`` and come back as length-``n``
    flat arrays.  ``block=None`` auto-sizes: VMEM-bounded blocks on the
    compiled path, ONE whole-array block under interpretation (the emulated
    grid loop costs ~10x more than the arithmetic it wraps, and there is no
    VMEM to respect).  ``donate`` maps input index -> output index through
    ``input_output_aliases`` so param/moment buffers update in place — on
    the compiled path only (interpret emulation keeps functional copies)."""
    from jax.experimental import pallas as pl

    interpret = default_interpret(interpret)
    if block is None:
        # interpretation: exactly ONE whole-array block — the emulated grid
        # loop re-slices the full buffers every iteration, so any grid > 1
        # costs orders of magnitude more than the arithmetic it wraps.  The
        # block must cover the PADDED size or the padding itself forces a
        # second grid step.
        block = _COMPILED_BLOCK if not interpret \
            else -(-n // (128 * _SUBLANES)) * (128 * _SUBLANES)
    rows, block_rows, grid = tile_layout(n, block)
    bufs = [pack_flat(x, rows) for x in tiled]
    sca = [jnp.asarray(s, jnp.float32).reshape(1) for s in scalars]
    tile = lambda: pl.BlockSpec((block_rows, 128), lambda i: (i, 0))
    scalar = lambda: pl.BlockSpec((1,), lambda i: (0,))
    aliases = {} if interpret else dict(donate)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[tile() for _ in bufs] + [scalar() for _ in sca],
        out_specs=[tile() for _ in out_dtypes],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), dt) for dt in out_dtypes],
        input_output_aliases=aliases,
        interpret=interpret,
    )(*bufs, *sca)
    return [o.reshape(-1)[:n] for o in outs]


# ----------------------------------------------------- packed tree updates

@lru_cache(maxsize=512)
def _bucket_layout(spec: tuple) -> tuple:
    """Group leaves by (param dtype, grad dtype) so each bucket packs into
    one contiguous flat stream.  ``spec`` is the tree's static signature —
    ``(size, p_dtype, g_dtype)`` per leaf in flatten order — so the layout
    is computed once per group/tree structure and cached."""
    buckets: dict = {}
    for i, (_, pdt, gdt) in enumerate(spec):
        buckets.setdefault((pdt, gdt), []).append(i)
    return tuple((key, tuple(idxs)) for key, idxs in sorted(buckets.items()))


def _packed_update(fn, params, grads, states: tuple):
    """Apply a single-array fused update ``fn(p, g, *state_leaves)`` over a
    pytree, one launch per dtype bucket.  ``states`` are param-shaped fp32
    moment trees; returns ``(new_params, new_states)`` with leaves restored
    to their original shapes/dtypes."""
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_s = [treedef.flatten_up_to(s) for s in states]
    spec = tuple((int(p.size), jnp.dtype(p.dtype).name, jnp.dtype(g.dtype).name)
                 for p, g in zip(flat_p, flat_g))
    out_p = list(flat_p)
    out_s = [list(s) for s in flat_s]
    for _, idxs in _bucket_layout(spec):
        if len(idxs) == 1:
            # fn already returns leaf-shaped arrays (0-d scalars included)
            i, = idxs
            res = fn(flat_p[i], flat_g[i], *(s[i] for s in flat_s))
            out_p[i] = res[0]
            for j in range(len(states)):
                out_s[j][i] = res[1 + j]
            continue
        res = fn(jnp.concatenate([flat_p[i].reshape(-1) for i in idxs]),
                 jnp.concatenate([flat_g[i].reshape(-1) for i in idxs]),
                 *(jnp.concatenate([s[i].reshape(-1) for i in idxs])
                   for s in flat_s))
        off = 0
        for i in idxs:
            size, shape = spec[i][0], flat_p[i].shape
            out_p[i] = res[0][off:off + size].reshape(shape)
            for j in range(len(states)):
                out_s[j][i] = res[1 + j][off:off + size].reshape(shape)
            off += size
    return (treedef.unflatten(out_p),
            tuple(treedef.unflatten(o) for o in out_s))


def fused_adamw_update(params, grads, m, v, *, lr, b1, b2, eps, weight_decay,
                       c1, c2):
    """Pytree-wide fused AdamW (one Pallas launch per dtype bucket)."""
    from repro.kernels.fused_adamw import fused_adamw_pallas
    new_p, (new_m, new_v) = _packed_update(
        partial(fused_adamw_pallas, lr=lr, b1=b1, b2=b2, eps=eps,
                weight_decay=weight_decay, c1=c1, c2=c2),
        params, grads, (m, v))
    return new_p, new_m, new_v


def fused_sgdm_update(params, grads, mu, *, lr, momentum, weight_decay):
    """Pytree-wide fused SGD-momentum (one Pallas launch per dtype bucket)."""
    from repro.kernels.fused_sgdm import fused_sgdm_pallas
    new_p, (new_mu,) = _packed_update(
        partial(fused_sgdm_pallas, lr=lr, momentum=momentum,
                weight_decay=weight_decay),
        params, grads, (mu,))
    return new_p, new_mu


def fused_adagrad_update(params, grads, accum, *, lr, eps, weight_decay):
    """Pytree-wide fused AdaGrad (one Pallas launch per dtype bucket)."""
    from repro.kernels.fused_adagrad import fused_adagrad_pallas
    new_p, (new_a,) = _packed_update(
        partial(fused_adagrad_pallas, lr=lr, eps=eps,
                weight_decay=weight_decay),
        params, grads, (accum,))
    return new_p, new_a


# ------------------------------------------------------------ misc kernels

@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 256,
                    block_k: int = 256):
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k)


@jax.jit
def dequant_matmul(x, leaf):
    """``x @ dequantize(leaf)`` with the int8/NF4 decode fused into the
    matmul block — no materialized fp32 weight (kernels/fused_dequant_matmul)."""
    from repro.kernels.fused_dequant_matmul import fused_dequant_matmul
    return fused_dequant_matmul(x, leaf)


@partial(jax.jit, static_argnames=("chunk",))
def ssm_scan(x, a_log, b, c, chunk: int = 128):
    from repro.kernels.ssm_scan import ssm_scan_pallas
    return ssm_scan_pallas(x, a_log, b, c, chunk=chunk)
