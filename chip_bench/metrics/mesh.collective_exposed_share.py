"""Share of the device's fused-scan time, in %, spent in collective
operations while no other operation ran on that device (mean over the
chips)."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["scan_s"] <= 0 or t["collective_s"] <= 0:
        return None
    return 100.0 * t["scan_exposed_s"] / t["scan_s"]
