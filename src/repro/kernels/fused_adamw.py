"""Pallas fused AdamW update — the HiFT hot loop (one group per step).

TPU adaptation of LOMO's fused-update idea: param, grad, m, v stream
HBM->VMEM tile by tile; the whole bias-corrected update runs in one VMEM
pass (8 elementwise ops + rsqrt) and writes back param/m/v — vs 4 separate
HBM sweeps for an unfused update.  Tiles are (8, 128)-aligned for the VPU;
the shared layout/launch substrate lives in ``repro.kernels.ops``
(``tile_layout`` pads so the grid always divides evenly, and the packed
``fused_adamw_update`` fuses a whole group into one launch per dtype
bucket).  On the compiled path the param/m/v inputs are DONATED
(``input_output_aliases``), so the update is in-place in HBM.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels.ops import elementwise_update_call


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, c1_ref, c2_ref,
                  po_ref, mo_ref, vo_ref, *, b1, b2, eps, weight_decay):
    g = g_ref[...].astype(jnp.float32)
    # moments load in their RESIDENT dtype and dequantize (astype) in VMEM —
    # identity for fp32, the fused bf16-moment path for quantized residency;
    # the arithmetic is always fp32 either way
    m = b1 * m_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    # jnp.square, not g * g: XLA compiles the two differently at the last
    # bit, and the unfused repro.optim.adamw (the bit-compare oracle) squares
    v = b2 * v_ref[...].astype(jnp.float32) + (1.0 - b2) * jnp.square(g)
    mhat = m / c1_ref[0]
    vhat = v / c2_ref[0]
    p32 = p_ref[...].astype(jnp.float32)
    step = lr_ref[0] * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p32)
    po_ref[...] = (p32 - step).astype(po_ref.dtype)
    mo_ref[...] = m.astype(mo_ref.dtype)
    vo_ref[...] = v.astype(vo_ref.dtype)


def fused_adamw_pallas(p, g, m, v, *, lr, b1=0.9, b2=0.999, eps=1e-8,
                       weight_decay=0.0, c1=1.0, c2=1.0, block: int = None,
                       interpret: bool = None):
    """Single-array fused update.  Arrays are flattened, zero-padded to a
    whole number of (block_rows, 128) VPU tiles and streamed block by block;
    ``interpret=None`` auto-selects from the backend (compiled on TPU).
    Moments stay in THEIR dtype end to end (fp32 default, bf16 under
    quantized residency): the kernel dequantizes into the update and
    re-rounds on store, so no fp32 moment copy is ever materialized."""
    shape, dtype = p.shape, p.dtype
    kernel = functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps,
                               weight_decay=weight_decay)
    po, mo, vo = elementwise_update_call(
        kernel,
        [p, g, m, v],
        [lr, c1, c2],
        [dtype, m.dtype, v.dtype],
        n=p.size, block=block, interpret=interpret,
        donate=((0, 0), (2, 1), (3, 2)))
    return po.reshape(shape), mo.reshape(shape), vo.reshape(shape)
