"""Share of its roofline, in %, that the `fedavg_agg` Pallas kernel
reaches over the traced window: the least time its compulsory HBM bytes
take at the chip's peak bandwidth, (C*N + C + N) * 4 bytes per call
from the call's own (C, N) stack, summed over its events, over the
summed device time of those events."""
from chip_bench import costs


def read(ctx):
    t = ctx["trace"]
    events = (t or {}).get("kernels", {}).get("fedavg_agg", [])
    least = took = 0.0
    for dur, operands in events:
        stacks = [s for _, s in operands if len(s) == 2 and s[1] > 1]
        if not stacks:
            continue
        C, N = stacks[0]
        least += costs.fedavg_bytes(C, N) / ctx["peaks"]["hbm_bytes_per_s"]
        took += dur
    if took <= 0:
        return None
    return 100.0 * least / took
