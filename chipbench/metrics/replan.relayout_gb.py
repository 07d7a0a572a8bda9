"""Mean bytes (GB, 1e9 B) the runtime relaid per replan in the window
(ShardedServiceRuntime.last_relayout_bytes)."""


def read(run):
    d = [r["relayout_bytes"] for r in run.replans]
    return sum(d) / len(d) / 1e9 if d else None
