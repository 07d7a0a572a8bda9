"""Mean host milliseconds of the add_job / remove_job calls in the
window."""


def read(run):
    d = [r["host_s"] for r in run.replans]
    return 1e3 * sum(d) / len(d) if d else None
