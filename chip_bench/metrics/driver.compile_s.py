"""Host seconds of the program's `compile` span (`.compile()` of the
lowered fused scan: an XLA compile or a read of the persistent
compilation cache), mean over the window's runs."""


def read(ctx):
    runs = [r for r in ctx["runs"] if "compile" in r["spans"]]
    if not runs:
        return None
    return sum(r["spans"]["compile"] for r in runs) / len(runs)
