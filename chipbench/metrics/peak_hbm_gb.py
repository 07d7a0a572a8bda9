"""peak_bytes_in_use of the device after the window, in GB (1e9 B)."""


def read(run):
    b = run.memory_peak_bytes
    return b / 1e9 if b else None
