"""Mean device milliseconds per execution of the push pack program
(``jit_push_pack``) in the window."""

from chipbench.engine_trace import PUSH_PROGRAM, mean_device_ms


def read(run):
    return mean_device_ms(run, PUSH_PROGRAM)
