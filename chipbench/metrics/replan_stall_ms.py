"""Mean over the window's replans of the milliseconds from the replan's
start until every resident tenant has had an update submitted after that
start applied and pulled back."""


def read(run):
    d = [r["stall_s"] for r in run.replans if r["stall_s"] is not None]
    return 1e3 * sum(d) / len(d) if d else None
