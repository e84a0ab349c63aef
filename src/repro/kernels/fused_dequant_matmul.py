"""Pallas dequant-into-matmul for quantized resident weights.

The frozen tree's forward cost under quantized residency: instead of a
separate dequant pass materializing an fp32 copy of the weight (exactly
the HBM the codec exists to avoid), the matmul kernel streams int8/NF4
codes + per-tile scales HBM->VMEM and decodes INSIDE the block — each
(K, 128) weight column block exists in fp32 only transiently in VMEM,
feeding the MXU directly (``preferred_element_type=jnp.float32``).

Covers 2-d ``(K, N)`` quantized leaves (the per-layer projection shape
the forward path consumes); stacked ndim>=3 group leaves are dequantized
in-jit by the strategy layer instead (see ``docs/quantization.md`` for
the coverage matrix).  NF4 decode is gather-free: nibble unpack with
bit ops, then a 16-way select chain against the codebook — the exact
reverse of ``dist.quant._nf4_encode``'s midpoint-count encode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.dist.quant import NF4_CODEBOOK, is_quantized, quant_format, \
    quant_shape
from repro.kernels.ops import default_interpret

_LANE = 128


def _decode_block(q, s, *, fmt, k, w_dtype):
    """Codes block -> fp32 weight block, entirely in VMEM.

    q: (K, 128) int8 or (K, 64) packed uint8; s: (K, n_tiles) per-(1,128)
    scales covering this block's lanes.  The decoded product rounds
    through ``w_dtype`` (the codec template's dtype) before feeding the
    MXU, so a bf16-template leaf decodes to the same bits
    ``dequantize_leaf`` materializes — the bit-equality contract with
    ``dequant_matmul_ref``."""
    if fmt == "int8":
        w = q.astype(jnp.float32)
    else:
        lo = q & jnp.uint8(0xF)
        hi = (q >> 4) & jnp.uint8(0xF)
        idx = jnp.stack([lo, hi], axis=-1).reshape(q.shape[0],
                                                   2 * q.shape[1])
        w = jnp.full(idx.shape, NF4_CODEBOOK[0], jnp.float32)
        for i in range(1, 16):
            w = jnp.where(idx == i, jnp.float32(NF4_CODEBOOK[i]), w)
    se = jnp.broadcast_to(s[:, :, None], (k, s.shape[1], _LANE))
    w = w * se.reshape(k, s.shape[1] * _LANE)
    if w_dtype != jnp.float32:
        w = w.astype(w_dtype).astype(jnp.float32)
    return w


def _dequant_matmul_kernel(x_ref, q_ref, s_ref, o_ref, *, fmt, k, w_dtype):
    w = _decode_block(q_ref[...], s_ref[...], fmt=fmt, k=k, w_dtype=w_dtype)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(x, w, preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def fused_dequant_matmul(x, leaf, *, block_m: int = None,
                         interpret: bool = None):
    """``x @ dequantize_leaf(leaf)`` with the dequant fused into the
    matmul.  ``x``: (M, K); ``leaf``: a 2-d quantized ``{"q","s","t"}``
    dict of shape (K, N).  Returns (M, N) in ``x.dtype`` (fp32 MXU
    accumulation), with no materialized fp32 weight copy."""
    from jax.experimental import pallas as pl

    if not is_quantized(leaf):
        raise ValueError("fused_dequant_matmul needs a quantized "
                         '{"q","s","t"} leaf; got an unquantized array — '
                         "use jnp.dot directly")
    kdim, n = quant_shape(leaf)
    if x.ndim != 2 or x.shape[1] != kdim:
        raise ValueError(f"x {x.shape} does not contract with quantized "
                         f"leaf {(kdim, n)}")
    fmt = quant_format(leaf)
    interpret = default_interpret(interpret)

    m = x.shape[0]
    if block_m is None:
        block_m = min(-(-m // 8) * 8, _LANE)
    mp = -(-m // block_m) * block_m
    np_ = -(-n // _LANE) * _LANE
    xp = jnp.pad(x, ((0, mp - m), (0, 0)))
    q, s = leaf["q"], leaf["s"]
    if fmt == "int8":
        qp = jnp.pad(q, ((0, 0), (0, np_ - n)))
        bq = _LANE
    else:
        # packed 2/byte: pad to np_//2 columns with 0x77 (code 7 = 0.0)
        qp = jnp.pad(q, ((0, 0), (0, np_ // 2 - q.shape[1])),
                     constant_values=0x77)
        bq = _LANE // 2
    # per-(1,128) scale grid is already exactly np_//128 columns wide
    grid = (mp // block_m, np_ // _LANE)
    kernel = functools.partial(_dequant_matmul_kernel, fmt=fmt, k=kdim,
                               w_dtype=leaf["t"].dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, kdim), lambda i, j: (i, 0)),
            pl.BlockSpec((kdim, bq), lambda i, j: (0, j)),
            pl.BlockSpec((kdim, 1), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, _LANE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=interpret,
        name="fused_dequant_matmul",
    )(xp, qp, s)
    return out[:m, :n]
