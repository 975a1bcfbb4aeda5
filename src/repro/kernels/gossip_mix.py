"""Pallas TPU kernel: masked gossip mixing (DESIGN.md §15).

mixed[c, n] = sum_j mix[c, j] * theta[j, n]

One synchronous gossip exchange under dynamic membership is a dense
(C, C) row-stochastic matmul against the client-stacked parameter
matrix — the mixing matrix changes EVERY ROUND under churn (masked rows
for dead clients, heartbeat-decayed supports, moving-target ring
re-randomization), so unlike the static-ring path it cannot be folded
into a constant. Fusing the mix into one kernel makes a single HBM pass
over the stacked parameters per round: each grid step loads a
(C, BLOCK) tile into VMEM, applies the mix on the MXU at full f32
precision, and writes the mixed tile.

Tiling: a 2-D grid, lane blocks outer and blocks of ROWS mix rows
inner. The (C, BLOCK) parameter tile stays resident across the inner
steps; the mix is fetched once when it fits VMEM whole (ROWS = C) and
row block by row block when it does not (C = 1024: a 4 MiB matrix).
Both sizes come from the shared VMEM budget (`kernels/tiling.py`),
which counts the three bf16 parts an f32 matmul operand is split into.

`gossip_mix_jnp` is the pure-jnp reference (also the CPU production
path and the form the fused executor traces into its round scan);
parity between the two is pinned in tests/test_kernels.py-style checks
inside tests/test_faults.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


DEFAULT_BLOCK = 8192


def gossip_mix_jnp(stacked, mix):
    """Reference: (C, N) client stack x (C, C) row-stochastic mix, at
    full f32 precision (a TPU's default f32 matmul multiplies in bf16)."""
    return jnp.matmul(jnp.asarray(mix, jnp.float32),
                      stacked.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST
                      ).astype(stacked.dtype)


def _gossip_kernel(m_ref, x_ref, o_ref):
    # m_ref: (ROWS, C) mix rows; x_ref: (C, BLOCK) VMEM tile;
    # o_ref: (ROWS, BLOCK)
    x = x_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(
        m, x, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


# VMEM bytes per element of an f32 matmul operand beyond its input
# buffers: the f32 value and the three bf16 parts a full-precision
# matmul splits it into
_OPERAND_BYTES = 4 + 3 * 2


def _mix_bytes(rows, C):
    """A (rows, C) f32 mix block: double-buffered, plus its bf16 parts."""
    return (2 * 4 + 3 * 2) * tiling.padded_rows(rows, 4) * C


def _rows(C):
    """Mix rows per grid step: all C when the whole mix takes at most
    half the budget, else the largest multiple of 8 dividing C whose
    block does."""
    half = tiling.VMEM_BUDGET_BYTES // 2
    if _mix_bytes(C, C) <= half:
        return C
    for r in range(C - C % 8, 7, -8):
        if C % r == 0 and _mix_bytes(r, C) <= half:
            return r
    return C


def _block(C, rows, N, dtype, max_block):
    """Per lane: the double-buffered parameter tile with its matmul
    operand, and the double-buffered output tile with its f32 result."""
    isz = jnp.dtype(dtype).itemsize
    per_lane = ((2 * isz + _OPERAND_BYTES) * tiling.padded_rows(C, 4)
                + (2 * isz + 4) * tiling.padded_rows(rows, 4))
    return tiling.lane_block(N, per_lane, max_block=max_block,
                             fixed_bytes=_mix_bytes(rows, C))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def gossip_mix_agg(stacked, mix, *, block=DEFAULT_BLOCK, interpret=False):
    """stacked: (C, N) flat client parameters; mix: (C, C) row-stochastic
    mixing matrix (possibly per-round / masked). Returns the (C, N)
    mixed stack. `block` caps the lane block, which shrinks with C to
    fit VMEM. N is padded to a block multiple internally; the pad is
    sliced off before returning."""
    C, N = stacked.shape
    rows = _rows(C)
    block = _block(C, rows, N, stacked.dtype, block)
    pad = (-N) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Np = N + pad

    out = pl.pallas_call(
        _gossip_kernel,
        grid=(Np // block, C // rows),
        in_specs=[
            pl.BlockSpec((rows, C), lambda i, j: (j, 0)),     # mix rows
            pl.BlockSpec((C, block), lambda i, j: (0, i)),    # param tile
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((C, Np), stacked.dtype),
        interpret=interpret,
    )(mix, stacked)
    return out[:, :N]
