"""Share of its roofline, in %, that the `trimmed_mean_agg` Pallas
kernel (the coordinate-wise median) reaches over the traced window: one
read of each call's (C, N) stack and one write of the N-vector,
(C*N + N) * 4 bytes at the chip's peak bandwidth, whatever passes its
sorting network makes, over the summed device time of its events."""
from chip_bench import costs


def read(ctx):
    t = ctx["trace"]
    events = [e for k, evs in (t or {}).get("kernels", {}).items()
              if k.startswith("trimmed_mean_agg") for e in evs]
    least = took = 0.0
    for dur, operands in events:
        stacks = [s for _, s in operands if len(s) == 2]
        if not stacks:
            continue
        C, N = stacks[0]
        least += costs.median_bytes(C, N) / ctx["peaks"]["hbm_bytes_per_s"]
        took += dur
    if took <= 0:
        return None
    return 100.0 * least / took
