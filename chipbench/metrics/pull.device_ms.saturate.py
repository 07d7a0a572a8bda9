"""Mean device milliseconds per execution of the pull program
(``jit_pull_gather``) in the window."""

from chipbench.engine_trace import PULL_PROGRAM, mean_device_ms


def read(run):
    return mean_device_ms(run, PULL_PROGRAM)
