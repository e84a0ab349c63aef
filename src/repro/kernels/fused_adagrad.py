"""Pallas fused AdaGrad update (paper Tables 8-12 baseline optimizer).

param, grad, accumulator stream HBM->VMEM tile by tile; the accumulator
update + rsqrt-scaled step run in one VMEM pass, mirroring
``repro.optim.adagrad`` exactly (weight decay folded into the gradient
BEFORE squaring, as there).  Bit-compared against the unfused update in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels.ops import elementwise_update_call


def _fused_adagrad_kernel(p_ref, g_ref, a_ref, lr_ref, po_ref, ao_ref, *,
                    eps, weight_decay):
    p32 = p_ref[...].astype(jnp.float32)
    g32 = g_ref[...].astype(jnp.float32) + weight_decay * p32
    # accumulator dequantizes (astype) from its resident dtype in VMEM —
    # identity for fp32, fused bf16-moment path under quantized residency
    a = a_ref[...].astype(jnp.float32) + jnp.square(g32)
    step = lr_ref[0] * g32 / (jnp.sqrt(a) + eps)
    po_ref[...] = (p32 - step).astype(po_ref.dtype)
    ao_ref[...] = a.astype(ao_ref.dtype)


def fused_adagrad_pallas(p, g, accum, *, lr, eps=1e-10, weight_decay=0.0,
                         block: int = None, interpret: bool = None):
    """Single-array fused AdaGrad update; layout/donation as
    ``fused_adamw_pallas`` (param + accumulator donated on compiled
    backends)."""
    shape, dtype = p.shape, p.dtype
    kernel = functools.partial(_fused_adagrad_kernel, eps=eps,
                               weight_decay=weight_decay)
    po, ao = elementwise_update_call(
        kernel,
        [p, g, accum],
        [lr],
        [dtype, accum.dtype],
        n=p.size, block=block, interpret=interpret,
        donate=((0, 0), (2, 1)))
    return po.reshape(shape), ao.reshape(shape)
