"""Pallas TPU kernel for the Mamba2 SSD intra-chunk computation.

Per (batch, chunk, head) grid cell, entirely in VMEM:

    CB      = C @ B^T                      (L,L)   MXU matmul
    M       = CB * exp(seg) * dt_j * causal
    y_intra = M @ X_h                      (L,L)@(L,P) MXU matmul
    state   = (B^T * exp(cum_L - cum) * dt) @ X_h   (N,L)@(L,P)

L (chunk) = 128-256 and P = 64 keep every tile MXU-aligned; the (L,L)
decay matrix never leaves VMEM -- this is the memory win over the XLA path,
which materializes the (B,NC,L,L,H) tensor in HBM.

Block layout: the TPU tiles the last two dims of every block, which must
be multiples of (8, 128) or span the whole array dim.  One head is a
single index of the ``H`` axis, so the wrapper moves ``H`` ahead of the
sequence axis -- x/y as (B,NC,H,L,P), dt/cum as (B,NC,H,1,L) rows -- and
every block's last two dims are whole array dims.  The kernel needs
``cum`` along both matrix axes (``seg[i, j] = cum[i] - cum[j]``); the
column form comes from one in-VMEM (L,L) transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -2.0 ** 30


def _ssd_kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref, st_ref, *,
                l: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)              # (L,P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)            # (1,L)
    cum = cum_ref[0, 0, 0].astype(jnp.float32)          # (1,L)
    bm = b_ref[0, 0].astype(jnp.float32)                # (L,N)
    cm = c_ref[0, 0].astype(jnp.float32)                # (L,N)

    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L,L)
    cum_j = jnp.broadcast_to(cum, (l, l))                # [i, j] = cum[j]
    seg = cum_j.T - cum_j                                # cum[i] - cum[j]
    rows = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    decay = jnp.exp(jnp.where(rows >= cols, seg, NEG_INF))
    m = cb * decay * dt
    y_ref[0, 0, 0] = jax.lax.dot_general(
        m, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    w_state = jnp.exp(cum[:, l - 1:] - cum) * dt         # (1,L)
    st_ref[0, 0, 0] = jax.lax.dot_general(
        bm.T * w_state, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(st_ref.dtype)


def ssd_intra_chunk_pallas(xc, dtc, cum, bc, cc, *, interpret: bool = False):
    """xc (B,NC,L,H,P), dtc/cum (B,NC,L,H), bc/cc (B,NC,L,N) ->
    (y_intra (B,NC,L,H,P) f32, states (B,NC,H,N,P) f32)."""
    bsz, nc, l, h, p = xc.shape
    n = bc.shape[-1]
    xh = jnp.swapaxes(xc, 2, 3)                          # (B,NC,H,L,P)
    dt_rows = jnp.swapaxes(dtc, 2, 3)[:, :, :, None, :]  # (B,NC,H,1,L)
    cum_rows = jnp.swapaxes(cum, 2, 3)[:, :, :, None, :]
    head = lambda b, c, hh: (b, c, hh, 0, 0)
    shared = lambda b, c, hh: (b, c, 0, 0)
    kernel = functools.partial(_ssd_kernel, l=l)
    y, st = pl.pallas_call(
        kernel,
        grid=(bsz, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, l, p), head),
            pl.BlockSpec((1, 1, 1, 1, l), head),
            pl.BlockSpec((1, 1, 1, 1, l), head),
            pl.BlockSpec((1, 1, l, n), shared),
            pl.BlockSpec((1, 1, l, n), shared),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, l, p), head),
            pl.BlockSpec((1, 1, 1, n, p), head),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dt_rows, cum_rows, bc, cc)
    return jnp.swapaxes(y, 2, 3), st
