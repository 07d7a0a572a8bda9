"""Tenant updates applied and pulled back in the window, over the window:
from its opening until the last of its updates is ready on the device."""


def read(run):
    span = run.t_close - run.t0
    return len(run.iters) / span if run.iters and span > 0 else None
