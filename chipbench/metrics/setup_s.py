"""Seconds from the start of the process to the opening of the window:
imports, parameters and gradients made on the device, every tenant's
add_job, and the warm phase with its compiles or cache loads."""


def read(run):
    return run.setup_s
