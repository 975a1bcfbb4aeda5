"""Pallas TPU kernel: fused FedAvg parameter aggregation (paper Eq. 5).

theta_g[n] = sum_c w[c] * theta[c, n]

This is the hot op of every aggregation event: a pure memory-bound
weighted reduction over the client-stacked parameter matrix (C x N, with
N up to tens of billions). Fusing the C-way weighted sum into one kernel
makes a single HBM pass over the stacked parameters instead of C separate
scale+add passes (C-fold HBM traffic reduction — see benchmarks).

Tiling: lane blocks of the flattened parameter vector. Each grid step
loads a (C, BLOCK) tile into VMEM, multiplies by the (C, 1) weight column
(broadcast from VMEM), reduces over C on the VPU, and writes a
(1, BLOCK) tile. BLOCK shrinks as C grows so that the double-buffered
tile and the f32 temporaries stay inside one VMEM budget
(`kernels/tiling.py`); the output is 2-D so that any multiple of 128
lanes is a legal block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


DEFAULT_BLOCK = 16384


def _fedavg_kernel(w_ref, x_ref, o_ref):
    # x_ref: (C, BLOCK) VMEM tile; w_ref: (C, 1); o_ref: (1, BLOCK)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)            # (C, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def _block(C, N, dtype, max_block):
    """Per lane: the double-buffered input tile, the f32 upcast and
    product temporaries, and the double-buffered (1, BLOCK) output."""
    isz = jnp.dtype(dtype).itemsize
    per_lane = (2 * tiling.padded_rows(C, isz) * isz
                + 2 * tiling.padded_rows(C, 4) * 4
                + 2 * tiling.padded_rows(1, isz) * isz)
    return tiling.lane_block(N, per_lane, max_block=max_block,
                             fixed_bytes=tiling.column_bytes(C))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fedavg_agg(stacked, weights, *, block=DEFAULT_BLOCK, interpret=False):
    """stacked: (C, N) — client-stacked flat parameters; weights: (C,).

    Returns (N,) aggregated parameters. `block` caps the lane block,
    which shrinks with C to fit VMEM. N is padded to a block multiple
    internally; the pad is sliced off before returning.
    """
    C, N = stacked.shape
    block = _block(C, N, stacked.dtype, block)
    pad = (-N) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Np = N + pad

    out = pl.pallas_call(
        _fedavg_kernel,
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((C, 1), lambda i: (0, 0)),       # weights column
            pl.BlockSpec((C, block), lambda i: (0, i)),   # param tile
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), stacked.dtype),
        interpret=interpret,
    )(weights[:, None], stacked)
    return out[0, :N]
