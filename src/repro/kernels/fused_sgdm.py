"""Pallas fused SGD-momentum update (paper Tables 8-12 baseline optimizer).

Same shape as ``fused_adamw``: param, grad, momentum stream HBM->VMEM tile
by tile and the whole heavy-ball update (decoupled weight decay folded into
the gradient, exactly as ``repro.optim.sgd.sgdm``) runs in one VMEM pass —
one HBM sweep instead of three.  Bit-compared against the unfused update in
``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels.ops import elementwise_update_call


def _fused_sgdm_kernel(p_ref, g_ref, mu_ref, lr_ref, po_ref, muo_ref, *,
                 momentum, weight_decay):
    p32 = p_ref[...].astype(jnp.float32)
    g32 = g_ref[...].astype(jnp.float32) + weight_decay * p32
    # momentum dequantizes (astype) from its resident dtype in VMEM —
    # identity for fp32, fused bf16-moment path under quantized residency
    mu = momentum * mu_ref[...].astype(jnp.float32) + g32
    po_ref[...] = (p32 - lr_ref[0] * mu).astype(po_ref.dtype)
    muo_ref[...] = mu.astype(muo_ref.dtype)


def fused_sgdm_pallas(p, g, mu, *, lr, momentum=0.9, weight_decay=0.0,
                      block: int = None, interpret: bool = None):
    """Single-array fused heavy-ball update; layout/donation as
    ``fused_adamw_pallas`` (param + momentum donated on compiled
    backends)."""
    shape, dtype = p.shape, p.dtype
    kernel = functools.partial(_fused_sgdm_kernel, momentum=momentum,
                               weight_decay=weight_decay)
    po, muo = elementwise_update_call(
        kernel,
        [p, g, mu],
        [lr],
        [dtype, mu.dtype],
        n=p.size, block=block, interpret=interpret,
        donate=((0, 0), (2, 1)))
    return po.reshape(shape), muo.reshape(shape)
