"""Device milliseconds of the engine's snapshot and restore copies
(``jit_state_copy``) per engine tick in the window; 0.0 where the window
ticked without one."""

from chipbench.engine_trace import COPY_PROGRAM, device_ms_per_tick


def read(run):
    return device_ms_per_tick(run, COPY_PROGRAM)
