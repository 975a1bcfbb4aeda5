"""One VMEM budget for the lane-tiled aggregation kernels.

`fedavg_agg`, `dequant_agg`, `trimmed_mean_agg` and `gossip_mix_agg`
stream a client-stacked (C, N) matrix through VMEM in (C, block) tiles,
one grid step per lane block. What a step holds grows with C, so a fixed block that fits at
C = 32 runs out of VMEM at C = 1024. Each kernel therefore states how
many VMEM bytes it holds per lane column (double-buffered input and
output tiles plus its in-kernel f32 temporaries) and `lane_block` picks
the widest block that fits the budget.
"""
from __future__ import annotations

LANE = 128
# TPU v5e gives a Pallas kernel 16 MiB of scoped VMEM by default; a
# quarter of it is left to Mosaic's own scratch.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


def padded_rows(rows: int, itemsize: int) -> int:
    """Rows a (rows, block) VMEM tile really occupies: the sublane
    dimension is padded to 8 rows of 32-bit words (16 of bf16, 32 of
    int8)."""
    tile = 8 * (4 // itemsize)
    return -(-rows // tile) * tile


def column_bytes(rows: int) -> int:
    """VMEM bytes of a (rows, 1) f32 column block after lane padding to
    128 (the per-client weight column), double-buffered."""
    return 2 * padded_rows(rows, 4) * LANE * 4


def lane_block(n: int, bytes_per_lane: int, *, max_block: int,
               fixed_bytes: int = 0) -> int:
    """Block width for a kernel that streams an (rows, n) matrix in
    (rows, block) tiles.

    `bytes_per_lane` is the VMEM the kernel holds per lane column and
    `fixed_bytes` what it holds whatever the block (a weight column or
    a mixing matrix). Returns `n` itself when it fits (a full-extent
    block is legal at any width); otherwise the largest multiple of 128
    lanes that fits the budget, capped at `max_block`."""
    fit = (VMEM_BUDGET_BYTES - fixed_bytes) // bytes_per_lane
    block = max(LANE, min(max_block, fit) // LANE * LANE)
    return n if n <= block else block
