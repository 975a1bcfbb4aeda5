"""Pallas TPU kernel: coordinate-wise trimmed-mean / median aggregation —
the robust counterpart of `fedavg_agg` (DESIGN.md §8).

    theta_g[n] = mean over the order statistics of rank lo..hi-1 of
                 {theta[c, n] : c in clients}

Trimming the `f` smallest and `f` largest values per coordinate
(lo = f, hi = C - f) bounds the influence of up to f Byzantine clients;
lo = (C-1)//2 with hi = C - lo is exactly the coordinate-wise median for
odd AND even C (one or two surviving order statistics).

This is a selection kernel built on a **tiled bitonic sorting network**
over the client axis (shared by median and trimmed-mean). The previous
implementation computed each value's rank directly — a fori_loop over
the C rows, O(C^2) vectorized compares per tile — which left the robust
path ~95x slower than the `fedavg_agg` weighted reduction at C=64
(BENCH_ci.json, PR 4). The network replaces that with
O(C log^2 C) compare-exchange stages, each a fully-vectorized
min/max over the (C, BLOCK) tile:

* the client axis is padded to the next power of two with +inf rows
  (they sort to ranks C..Cp-1, above every kept order statistic);
* a bitonic stage (k, j) partners row i with row i^j; the partner
  pairs and the sort direction are both BLOCK-STRUCTURED in i, so every
  stage is expressed as a reshape + contiguous-slice min/max with *no*
  per-element direction mask: direction flips with bit log2(k/2j) of
  the pair-block index, i.e. in contiguous runs of k/(2j) blocks, and
  the final k = Cp merge is ascending everywhere;
* consecutive substages (j, j/2) are fused into ONE pass (`_merge4`):
  same comparator count, half the materialized intermediates — the
  network is bandwidth-bound, so this halves its wall time;
* ranks are then positions: rows lo..hi-1 of the sorted tile are summed
  and scaled — no rank bookkeeping, no data-dependent movement.

Ties need no index tie-break: sorted tied values are interchangeable, so
the kept-window SUM is identical to the sort-based reference
(`ref.trimmed_mean_ref`, the correctness oracle).

The same network, applied to the whole (C, N) matrix instead of a tile,
is exposed as `trimmed_mean_jnp` — the production CPU path
(`kernels/ops.py` dispatch): XLA:CPU's generic `sort` is comparator-
driven and ~8x slower than the vectorized network at C=64, which is
what held the robust/fedavg latency ratio at ~95x.

Tiling: lane blocks of the flattened parameter vector, like `fedavg_agg`.
Each grid step loads a (C, BLOCK) tile into VMEM and writes a (1, BLOCK)
tile; the network runs in VMEM on the VPU on the power-of-two padded
(Cp, BLOCK) f32 tile. BLOCK comes from the shared VMEM budget
(`kernels/tiling.py`), counting `_NETWORK_COPIES` live copies of that
padded tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling


DEFAULT_BLOCK = 8192
# (Cp, BLOCK) f32 copies the network may keep live at once, counted as
# an upper bound: the padded tile, one fused `_merge4` pass's input,
# its min/max intermediates and its stacked output. Mosaic holds less
# (the v5e compile accepts blocks 4x wider at C = 64 and 1024).
_NETWORK_COPIES = 6


def _pow2_pad_rows(x, value):
    """Pad the leading (client) axis up to the next power of two."""
    C = x.shape[0]
    Cp = 1 << max(0, (C - 1).bit_length())
    if Cp != C:
        x = jnp.concatenate(
            [x, jnp.full((Cp - C,) + x.shape[1:], value, x.dtype)])
    return x


def _merge4(a, b, c, d):
    """Two consecutive ascending compare-exchange substages (distances
    2h then h) on the four h-row slices of a 4h-row group, as ONE pass:
    (a,c),(b,d) exchange, then (a,b),(c,d). Same comparator count as
    the two separate substages, half the materialized intermediates —
    the network is memory-bound, so this halves its wall time."""
    lo_ac, hi_ac = jnp.minimum(a, c), jnp.maximum(a, c)
    lo_bd, hi_bd = jnp.minimum(b, d), jnp.maximum(b, d)
    return (jnp.minimum(lo_ac, lo_bd), jnp.maximum(lo_ac, lo_bd),
            jnp.minimum(hi_ac, hi_bd), jnp.maximum(hi_ac, hi_bd))


def _cx_single(x, Cp, tail, k, j):
    """One compare-exchange substage at distance j of merge phase k."""
    if k == Cp:
        # final merge: every pair sorts ascending
        y = x.reshape((Cp // (2 * j), 2, j) + tail)
        a, b = y[:, 0], y[:, 1]
        return jnp.stack([jnp.minimum(a, b), jnp.maximum(a, b)],
                         axis=1).reshape((Cp,) + tail)
    # direction = bit log2(k/(2j)) of the pair-block index: p ascending
    # blocks then p descending blocks, repeating
    p = k // (2 * j)
    q = Cp // (2 * j * 2 * p)
    y = x.reshape((q, 2, p, 2, j) + tail)
    a, b = y[:, :, :, 0], y[:, :, :, 1]
    lo, hi = jnp.minimum(a, b), jnp.maximum(a, b)
    first = jnp.stack([lo[:, 0], hi[:, 1]], axis=1)
    second = jnp.stack([hi[:, 0], lo[:, 1]], axis=1)
    return jnp.stack([first, second], axis=3).reshape((Cp,) + tail)


def _cx_double(x, Cp, tail, k, j):
    """Substages (j, j//2) of merge phase k fused into one pass
    (`_merge4`). Requires j >= 2; all four quarter-slices of a 2j-row
    group share one sort direction (it is bit log2(k) of the row index,
    and the group spans offsets < 2j <= k), so the direction handling
    is the same contiguous block split as the single substage."""
    h = j // 2
    if k == Cp:
        y = x.reshape((Cp // (2 * j), 2, 2, h) + tail)
        rows = _merge4(y[:, 0, 0], y[:, 0, 1], y[:, 1, 0], y[:, 1, 1])
        return jnp.stack(rows, axis=1).reshape((Cp,) + tail)
    p = k // (2 * j)
    q = Cp // (2 * j * 2 * p)
    y = x.reshape((q, 2, p, 2, 2, h) + tail)
    a, b = y[:, :, :, 0, 0], y[:, :, :, 0, 1]
    c, d = y[:, :, :, 1, 0], y[:, :, :, 1, 1]
    asc = _merge4(a[:, 0], b[:, 0], c[:, 0], d[:, 0])
    desc = _merge4(a[:, 1], b[:, 1], c[:, 1], d[:, 1])[::-1]
    out = jnp.stack([jnp.stack(asc, axis=2), jnp.stack(desc, axis=2)],
                    axis=1)                      # (q, 2, p, 4, h) + tail
    return out.reshape((Cp,) + tail)


def bitonic_sorted(x):
    """Sort a (C, ...) array along axis 0, ascending, via a bitonic
    network of contiguous-slice min/max stages (no `where`, no gather —
    see module docstring). Consecutive substages are fused pairwise
    (`_cx_double`) to halve the memory traffic of this bandwidth-bound
    network. C is padded to a power of two with +inf; the padded rows
    come back at the bottom. Traceable and Pallas-safe (all reshapes
    split/merge the leading axis only)."""
    x = _pow2_pad_rows(x, jnp.inf)
    Cp = x.shape[0]
    tail = x.shape[1:]
    k = 2
    while k <= Cp:
        j = k // 2
        while j >= 1:
            if j >= 2:
                x = _cx_double(x, Cp, tail, k, j)
                j //= 4
            else:
                x = _cx_single(x, Cp, tail, k, j)
                j //= 2
        k *= 2
    return x


def _select_window(sorted_x, lo: int, hi: int, out_dtype):
    """Mean of the rank-lo..hi-1 rows of an ascending-sorted stack."""
    return (jnp.sum(sorted_x[lo:hi], axis=0) / (hi - lo)).astype(out_dtype)


def _trimmed_kernel(x_ref, o_ref, *, lo: int, hi: int):
    # x_ref: (C, BLOCK) VMEM tile; o_ref: (1, BLOCK)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = _select_window(bitonic_sorted(x), lo, hi,
                                o_ref.dtype)[None]


def _block(C, N, dtype, max_block):
    """Per lane: the double-buffered input tile, the network's live f32
    copies of the power-of-two padded tile, and the double-buffered
    (1, BLOCK) output."""
    isz = jnp.dtype(dtype).itemsize
    Cp = 1 << max(0, (C - 1).bit_length())
    per_lane = (2 * tiling.padded_rows(C, isz) * isz
                + _NETWORK_COPIES * tiling.padded_rows(Cp, 4) * 4
                + 2 * tiling.padded_rows(1, isz) * isz)
    return tiling.lane_block(N, per_lane, max_block=max_block)


def _check_trim(C: int, trim: int):
    if not 0 <= 2 * trim < C:
        raise ValueError(f"trim={trim} invalid for C={C} clients "
                         f"(need 0 <= 2*trim < C)")


@functools.partial(jax.jit, static_argnames=("trim", "block", "interpret"))
def trimmed_mean_agg(stacked, trim: int, *, block=DEFAULT_BLOCK,
                     interpret=False):
    """stacked: (C, N) client-stacked flat parameters. Returns the (N,)
    coordinate-wise mean of the order statistics with the `trim` smallest
    and `trim` largest per coordinate removed (trim=0 is the plain mean;
    trim=(C-1)//2 is the median). Requires 0 <= 2*trim < C. `block`
    caps the lane block, which shrinks with C to fit VMEM."""
    C, N = stacked.shape
    _check_trim(C, trim)
    lo, hi = trim, C - trim
    block = _block(C, N, stacked.dtype, block)
    pad = (-N) % block
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Np = N + pad

    out = pl.pallas_call(
        functools.partial(_trimmed_kernel, lo=lo, hi=hi),
        grid=(Np // block,),
        in_specs=[pl.BlockSpec((C, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Np), stacked.dtype),
        interpret=interpret,
    )(stacked)
    return out[0, :N]


def median_agg(stacked, *, block=DEFAULT_BLOCK, interpret=False):
    """Coordinate-wise median: maximal trim. Odd C keeps the single middle
    order statistic; even C averages the two middle ones."""
    C = stacked.shape[0]
    return trimmed_mean_agg(stacked, (C - 1) // 2, block=block,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("trim",))
def trimmed_mean_jnp(stacked, trim: int):
    """The kernel's bitonic selection applied to the whole (C, N) matrix
    as plain jnp — the production CPU path (and the in-scan fused-
    executor path on CPU, where it traces into the round `lax.scan`).
    Matches `ref.trimmed_mean_ref` to float tolerance, ~8x faster than
    XLA:CPU's comparator sort at C=64."""
    C, N = stacked.shape
    _check_trim(C, trim)
    s = bitonic_sorted(stacked.astype(jnp.float32))
    return _select_window(s, trim, C - trim, stacked.dtype)


def median_jnp(stacked):
    """CPU-path coordinate-wise median (maximal trim)."""
    return trimmed_mean_jnp(stacked, (stacked.shape[0] - 1) // 2)
