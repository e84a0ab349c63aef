"""Pallas TPU flash-attention kernels (target: TPU v5e; validated in
interpret mode on CPU against ``kernels.ref``).  ``interpret=None``
resolves from the backend (``kernels.ops.default_interpret``).

TPU adaptation of the CUDA flash algorithm:
  - grid = (B*H, S/block_q): each program owns one q block in VMEM and
    streams kv blocks HBM->VMEM via the BlockSpec index_map; accumulation
    runs on the MXU with fp32 accumulators in VMEM scratch.
  - block shapes are MXU-aligned (block_q x head_dim with head_dim >= 128
    preferred; the lane dim is the 128-wide minor axis).
  - online softmax carries (m, l, acc) in VMEM across the kv loop — no
    O(S^2) HBM traffic, which is the whole point on a 819 GB/s HBM part.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import default_interpret


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, causal: bool,
                  scale: float, seq_len: int):
    """One (batch*head, q-block) program: loop kv blocks in VMEM."""
    block_q, head_dim = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    q_idx = pl.program_id(1)

    nk = seq_len // block_k

    def body(kj, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(kj * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kj * block_k, block_k), :]
        s = q @ k_blk.astype(jnp.float32).T                      # (bq, bk) MXU
        if causal:
            q_pos = q_idx * block_q + jax.lax.iota(jnp.int32, block_q)[:, None]
            k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)[None, :]
            s = jnp.where(q_pos >= k_pos, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + p @ v_blk.astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)

    if causal:
        # only kv blocks up to (and including) the q block's diagonal
        upper = q_idx * block_q // block_k + 1
        m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))

    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, block_q: int = 256,
                           block_k: int = 256, interpret: bool = None):
    """q/k/v: (B, S, H, hd) (kv heads already repeated to H).

    ``interpret=None`` compiles for the MXU on TPU and emulates the kernel
    body elsewhere (``kernels.ops.default_interpret``).
    """
    b, s, h, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    scale = 1.0 / math.sqrt(hd)

    # (B, S, H, hd) -> (B*H, S, hd): each program owns one head's q block
    qr = jnp.moveaxis(q, 2, 1).reshape(b * h, s, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * h, s, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * h, s, hd)

    grid = (b * h, s // block_q)
    kernel = functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                               scale=scale, seq_len=s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        interpret=default_interpret(interpret),
    )(qr, kr, vr)
    return jnp.moveaxis(out.reshape(b, h, s, hd), 1, 2)


# ------------------------------------------------------------ flash decode
#
# The serving shape: ONE query per sequence (the token being decoded)
# against a KV cache, with per-slot validity windows [start, length).
# ``starts`` carries the engine's left-pad offsets, ``lengths`` the filled
# cache prefix (position + 1).  GQA is handled in-kernel: the kv-head block
# a program streams is selected by integer index arithmetic, so the cache
# is never repeated to n_heads in HBM.


def _decode_kernel(starts_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref, *,
                   block_k: int, scale: float, seq_len: int, n_heads: int):
    """One (batch*head,) program: stream kv blocks of one sequence."""
    i = pl.program_id(0)
    b = i // n_heads
    start = starts_ref[b]
    length = lengths_ref[b]
    q = q_ref[...].astype(jnp.float32) * scale               # (1, hd)
    hd = q.shape[-1]
    nk = seq_len // block_k

    def body(kj, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(kj * block_k, block_k), :]
        v_blk = v_ref[pl.ds(kj * block_k, block_k), :]
        s = q @ k_blk.astype(jnp.float32).T                  # (1, bk)
        pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)[None, :]
        s = jnp.where((pos >= start) & (pos < length), s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + p @ v_blk.astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((1,), -1e30, jnp.float32)
    l0 = jnp.zeros((1,), jnp.float32)
    acc0 = jnp.zeros((1, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_decode_pallas(q, k, v, lengths, starts=None, *, block_k: int = 128,
                        interpret: bool = None):
    """Single-query flash attention over a CONTIGUOUS KV cache.

    q: (B, H, hd); k/v: (B, S, KV, hd) with KV | H (GQA: each program picks
    its kv head by index, no HBM-side head repetition); lengths: (B,) int32
    — valid keys are positions ``[starts[b], lengths[b])``; ``starts=None``
    means no left-pad region.  Returns (B, H, hd).  Validated against
    ``ref.flash_decode_ref``; interpreted on CPU, compiled on TPU.
    """
    b, s, kvh, hd = k.shape
    h = q.shape[1]
    n_rep = h // kvh
    block_k = min(block_k, s)
    assert s % block_k == 0, (s, block_k)
    scale = 1.0 / math.sqrt(hd)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)

    qr = q.reshape(b * h, 1, hd)
    # (B, S, KV, hd) -> (B*KV, S, hd); program i reads kv row i // n_rep
    # (i = bi*H + hi maps to bi*KV + hi // n_rep exactly because H = KV*n_rep)
    kr = jnp.moveaxis(k, 2, 1).reshape(b * kvh, s, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(b * kvh, s, hd)

    kernel = functools.partial(_decode_kernel, block_k=block_k, scale=scale,
                               seq_len=s, n_heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # starts, lengths
        grid=(b * h,),
        in_specs=[
            pl.BlockSpec((None, 1, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i, *_: (i // n_rep, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i, *_: (i // n_rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, hd), lambda i, *_: (i, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, 1, hd), q.dtype),
        interpret=default_interpret(interpret),
    )(starts.astype(jnp.int32), lengths.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, h, hd)


def _paged_decode_kernel(bt_ref, starts_ref, lengths_ref, q_ref, k_ref, v_ref,
                         o_ref, acc_ref, m_ref, l_ref, *, block_size: int,
                         scale: float, n_heads: int):
    """One (batch*head, logical-block) program over a PAGED cache.

    The grid's inner dim walks the slot's logical blocks; the BlockSpec
    index_map has already resolved logical -> physical through the
    scalar-prefetched block table, so k_ref/v_ref hold one physical page.
    The online-softmax carry lives in scratch, persisting across the inner
    grid dim (TPU grids iterate sequentially); j == 0 initializes it and the
    last j normalizes into o_ref.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    b = i // n_heads

    @pl.when(j == 0)
    def _():
        m_ref[0] = -1e30
        l_ref[0] = 0.0
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32) * scale               # (1, hd)
    k_blk = k_ref[...].astype(jnp.float32)                   # (bs, hd)
    v_blk = v_ref[...].astype(jnp.float32)
    s = q @ k_blk.T                                          # (1, bs)
    pos = j * block_size + jax.lax.iota(jnp.int32, block_size)[None, :]
    s = jnp.where((pos >= starts_ref[b]) & (pos < lengths_ref[b]), s, -1e30)

    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, s.max())
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[0] = l_ref[0] * corr + p.sum()
    acc_ref[...] = acc_ref[...] * corr + p @ v_blk
    m_ref[0] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[0], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode_pallas(q, k_pool, v_pool, block_tables, lengths,
                              starts=None, *, interpret: bool = None):
    """Single-query flash attention over a PAGED KV cache.

    q: (B, H, hd); k_pool/v_pool: (n_blocks, block_size, KV, hd) — the
    shared physical page pool; block_tables: (B, max_blocks) int32 mapping
    each slot's logical blocks to physical pages (unused entries must still
    index a real page — the engine points them at the reserved null page);
    lengths/starts: (B,) int32 validity windows as in
    :func:`flash_decode_pallas`.  Returns (B, H, hd).

    The block table and the validity scalars ride
    ``PrefetchScalarGridSpec``: they are resolved BEFORE the kernel body
    runs, so the logical->physical translation happens in the BlockSpec
    index_map and each program DMAs exactly one physical page — the paged
    gather never materializes a contiguous copy of the cache.
    """
    n_blocks, block_size, kvh, hd = k_pool.shape
    b, h, _ = q.shape
    n_rep = h // kvh
    max_blocks = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)

    qr = q.reshape(b * h, 1, hd)
    # (n_blocks, bs, KV, hd) -> (KV, n_blocks, bs, hd): the index_map picks
    # (kv_head, physical_page) and each program sees one (bs, hd) page
    kp = jnp.moveaxis(k_pool, 2, 0)
    vp = jnp.moveaxis(v_pool, 2, 0)

    def page_map(i, j, bt_ref, *_):
        return ((i % h) // n_rep, bt_ref[i // h, j], 0, 0)

    kernel = functools.partial(_paged_decode_kernel, block_size=block_size,
                               scale=scale, n_heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # block_tables, starts, lengths
        grid=(b * h, max_blocks),
        in_specs=[
            pl.BlockSpec((None, 1, hd), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((None, None, block_size, hd), page_map),
            pl.BlockSpec((None, None, block_size, hd), page_map),
        ],
        out_specs=pl.BlockSpec((None, 1, hd), lambda i, j, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),      # acc
            pltpu.SMEM((1,), jnp.float32),         # m
            pltpu.SMEM((1,), jnp.float32),         # l
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, 1, hd), q.dtype),
        interpret=default_interpret(interpret),
    )(block_tables.astype(jnp.int32), starts.astype(jnp.int32),
      lengths.astype(jnp.int32), qr, kp, vp)
    return out.reshape(b, h, hd)
