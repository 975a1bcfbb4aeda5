"""Pallas TPU kernel: fused dequantize + weighted FedAvg reduce.

theta_g[n] = sum_c w[c] * s[c] * q[c, n]

The communication hot path (DESIGN.md §12): int8-quantized client
uploads (QSGD wire format — one int8 matrix plus a per-client float32
scale) are dequantized and reduced in a single pass over the same
(C, N) ravel layout `fedavg_agg` uses.  Folding the per-client
`scale * weight` product into the reduction means the kernel streams
the int8 matrix through VMEM exactly once — one HBM traversal at 1/4
the bytes of decode-then-`fedavg_agg`, which would materialize the
dense float32 matrix (4x the traffic) and then read it again.

Tiling mirrors `fedavg_agg`: 1-D grid over flattened-parameter blocks,
each step loads a (C, BLOCK) int8 tile and the (C, 1) scale*weight
column, upcasts on the VPU, reduces over C, writes a (1, BLOCK) float32
tile. BLOCK comes from the shared VMEM budget (`kernels/tiling.py`),
which counts the int8 tile at its (32, 128) minimum VMEM tiling. On the
CPU the kernel runs in interpret mode for tests and `dequant_agg_jnp` is
the production path — see `ops.dequant_aggregate`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


DEFAULT_BLOCK = 16384


def _dequant_agg_kernel(sw_ref, x_ref, o_ref):
    # x_ref: (C, BLOCK) int8 VMEM tile; sw_ref: (C, 1) scale*weight;
    # o_ref: (1, BLOCK)
    x = x_ref[...].astype(jnp.float32)
    sw = sw_ref[...].astype(jnp.float32)          # (C, 1)
    o_ref[...] = jnp.sum(x * sw, axis=0, keepdims=True).astype(o_ref.dtype)


def _block(C, N, dtype, max_block):
    """Per lane: the double-buffered quantized tile, the f32 upcast and
    product temporaries, and the double-buffered (1, BLOCK) f32 output."""
    isz = jnp.dtype(dtype).itemsize
    per_lane = (2 * tiling.padded_rows(C, isz) * isz
                + 2 * tiling.padded_rows(C, 4) * 4
                + 2 * tiling.padded_rows(1, 4) * 4)
    return tiling.lane_block(N, per_lane, max_block=max_block,
                             fixed_bytes=tiling.column_bytes(C))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def dequant_agg(values, scales, weights, *, block=DEFAULT_BLOCK,
                interpret=False):
    """values: (C, N) int8 quantized uploads; scales/weights: (C,).

    Returns the (N,) float32 aggregate of the dequantized uploads,
    sum_c weights[c] * scales[c] * values[c, :].  `block` caps the lane
    block, which shrinks with C to fit VMEM. N is padded to a block
    multiple internally; the pad is sliced off before returning.
    """
    C, N = values.shape
    block = _block(C, N, values.dtype, block)
    pad = (-N) % block
    if pad:
        values = jnp.pad(values, ((0, 0), (0, pad)))
    Np = N + pad
    sw = (scales.astype(jnp.float32) * weights.astype(jnp.float32))

    out = pl.pallas_call(
        _dequant_agg_kernel,
        grid=(Np // block,),
        in_specs=[
            pl.BlockSpec((C, 1), lambda i: (0, 0)),       # scale*weight col
            pl.BlockSpec((C, block), lambda i: (0, i)),   # int8 tile
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), jnp.float32),
        interpret=interpret,
    )(sw[:, None], values)
    return out[0, :N]


def dequant_agg_jnp(values, scales, weights):
    """Pure-jnp reference and CPU production path (one fused XLA op)."""
    sw = scales.astype(jnp.float32) * weights.astype(jnp.float32)
    return jnp.sum(values.astype(jnp.float32) * sw[:, None], axis=0)
