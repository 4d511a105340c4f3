"""jit'd, differentiable wrapper for the SSD kernel.

The Pallas kernel is forward-only; the backward pass is the VJP of the
jnp reference (``ssd_intra_chunk_reference``), recomputed from the saved
inputs, so training with ``kernel_mode="pallas"`` gets exact reference
gradients.
"""
from __future__ import annotations

import functools

import jax

from .kernel import ssd_intra_chunk_pallas
from .ref import ssd_intra_chunk_reference, ssd_reference


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_intra_chunk(xc, dtc, cum, bc, cc, interpret):
    return ssd_intra_chunk_pallas(xc, dtc, cum, bc, cc, interpret=interpret)


def _ssd_intra_chunk_fwd(xc, dtc, cum, bc, cc, interpret):
    out = ssd_intra_chunk_pallas(xc, dtc, cum, bc, cc, interpret=interpret)
    return out, (xc, dtc, cum, bc, cc)


def _ssd_intra_chunk_bwd(interpret, res, cts):
    del interpret
    return jax.vjp(ssd_intra_chunk_reference, *res)[1](cts)


_ssd_intra_chunk.defvjp(_ssd_intra_chunk_fwd, _ssd_intra_chunk_bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(xc, dtc, cum, bc, cc, interpret: bool = False):
    return _ssd_intra_chunk(xc, dtc, cum, bc, cc, interpret)


__all__ = ["ssd_intra_chunk", "ssd_intra_chunk_reference", "ssd_reference"]
