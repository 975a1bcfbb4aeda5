"""Host seconds of the program's `lower` span (tracing the fused scan
and lowering it to StableHLO, `jax.jit(...).lower(...)`), mean over the
window's runs."""


def read(ctx):
    runs = [r for r in ctx["runs"] if "lower" in r["spans"]]
    if not runs:
        return None
    return sum(r["spans"]["lower"] for r in runs) / len(runs)
