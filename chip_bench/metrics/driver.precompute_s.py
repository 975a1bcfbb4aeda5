"""Host seconds of the program's `precompute` span (schedules, batch
indices and attack inputs of every round, hoisted out of the scan),
mean over the window's runs."""


def read(ctx):
    runs = [r for r in ctx["runs"] if "precompute" in r["spans"]]
    if not runs:
        return None
    return sum(r["spans"]["precompute"] for r in runs) / len(runs)
