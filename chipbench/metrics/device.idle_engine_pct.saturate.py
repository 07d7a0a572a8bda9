"""Share (%) of the traced window's chip-seconds in which a chip was idle
under an open ``ps.*`` span of the engine; nothing where the trace holds
no such span."""

from chipbench.trace import engine_idle_pct


def read(run):
    if run.trace is None or not run.trace.spans:
        return None
    return engine_idle_pct(run.trace.idle_by_span, run.trace.window_s)
