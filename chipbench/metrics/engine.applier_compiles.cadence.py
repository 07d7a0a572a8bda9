"""The window's delta of the engine's ``TickStats.n_applier_compiles``:
its own program-cache misses (appliers, packs and pulls)."""


def read(run):
    return run.counters.get("window", {}).get("n_applier_compiles")
