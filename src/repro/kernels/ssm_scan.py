"""Pallas chunked SSM/gated-linear scan (Mamba2 SSD / mLSTM core).

TPU adaptation: instead of the CUDA warp-level parallel scan, the chunk is
the unit of MXU work — each program owns one (batch, head) pair, walks
chunks SEQUENTIALLY carrying the (P, N) state in VMEM scratch, and does the
intra-chunk work as dense (Lc x Lc) MXU matmuls.  The sequential chunk walk
is cheap because the state is tiny (P x N = 64x64 fp32 = 16 KB) while the
matmuls saturate the MXU — the SSD duality maps cleanly onto a systolic
part.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ops import default_interpret


def _ssm_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hf_ref, *, chunk: int,
                seq_len: int):
    """One (batch*head) program.  x: (S, P); a: (S, 1); b/c: (S, N)."""
    S, P = x_ref.shape
    N = b_ref.shape[-1]
    nc = seq_len // chunk

    def body(ci, h):
        sl = pl.ds(ci * chunk, chunk)
        x = x_ref[sl, :].astype(jnp.float32)   # (Lc, P)
        a = a_ref[sl, :].astype(jnp.float32)   # (Lc, 1)
        b = b_ref[sl, :].astype(jnp.float32)   # (Lc, N)
        c = c_ref[sl, :].astype(jnp.float32)   # (Lc, N)

        a_log = a[:, 0]
        cum = jnp.cumsum(a_log)                                     # (Lc,)
        # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
        diff = cum[:, None] - cum[None, :]
        li = jax.lax.iota(jnp.int32, chunk)
        mask = li[:, None] >= li[None, :]
        Lmat = jnp.where(mask, jnp.exp(diff), 0.0)
        scores = (c @ b.T) * Lmat                                   # (Lc, Lc) MXU
        y = scores @ x                                              # (Lc, P) MXU
        # inter-chunk: contribution of the entering state
        decay_from_start = jnp.exp(cum)                             # (Lc,)
        y = y + decay_from_start[:, None] * (c @ h.T)               # (Lc, P)
        y_ref[sl, :] = y.astype(y_ref.dtype)
        # update state: h' = exp(total) h + sum_j exp(total-cum_j) b_j x_j
        total = cum[-1]
        decay_to_end = jnp.exp(total - cum)                         # (Lc,)
        h_new = jnp.exp(total) * h + (x.T * decay_to_end[None, :]) @ b  # (P, N)
        return h_new

    h = jax.lax.fori_loop(0, nc, body, jnp.zeros((P, N), jnp.float32))
    hf_ref[...] = h


def ssm_scan_pallas(x, a_log, b, c, *, chunk: int = 128,
                    interpret: bool = None):
    """x: (B, S, H, P) pre-scaled inputs; a_log: (B, S, H) log decays;
    b/c: (B, S, N).  Returns (y (B,S,H,P), h_final (B,H,P,N)).

    Heads fold into the grid's batch dim; b/c are broadcast per head.
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0

    xr = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    ar = jnp.moveaxis(a_log, 2, 1).reshape(B * H, S, 1)
    br = jnp.broadcast_to(b[:, None], (B, H, S, N)).reshape(B * H, S, N)
    cr = jnp.broadcast_to(c[:, None], (B, H, S, N)).reshape(B * H, S, N)

    kernel = functools.partial(_ssm_kernel, chunk=chunk, seq_len=S)
    y, hf = pl.pallas_call(
        kernel,
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec((None, S, P), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, S, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, S, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, S, N), lambda i: (i, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((None, S, P), lambda i: (i, 0, 0)),
                   pl.BlockSpec((None, P, N), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
                   jax.ShapeDtypeStruct((B * H, P, N), jnp.float32)],
        interpret=default_interpret(interpret),
    )(xr, ar, br, cr)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return y, hf.reshape(B, H, P, N)
