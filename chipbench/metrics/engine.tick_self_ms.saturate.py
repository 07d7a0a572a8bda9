"""Mean self milliseconds of the engine's ``ps.tick`` span in the window:
its time less that of the engine spans directly inside it (compile,
snapshot, launch, fallback), the tick's pure host path."""

from chipbench.engine_trace import tick_self_ms


def read(run):
    return tick_self_ms(run.trace.spans) if run.trace is not None else None
