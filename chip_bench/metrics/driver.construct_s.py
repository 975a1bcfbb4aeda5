"""Host seconds of the program's `construct` span (`FederatedSimulation
.__init__` from its telemetry's creation on: strategy, codec, fault
schedule, partition, client shards and their device copies), mean over
the window's runs."""


def read(ctx):
    runs = [r for r in ctx["runs"] if "construct" in r["spans"]]
    if not runs:
        return None
    return sum(r["spans"]["construct"] for r in runs) / len(runs)
