"""Host seconds of `FederatedSimulation.run()` outside its `fused_scan`
span, mean over the window's runs: precompute, lowering and compiling
the scan (or reading it from the compilation cache), the predict
warm-ups, classification."""


def read(ctx):
    runs = [r for r in ctx["runs"] if "fused_scan" in r["spans"]]
    if not runs:
        return None
    return sum(r["run_s"] - r["spans"]["fused_scan"] for r in runs) / len(runs)
