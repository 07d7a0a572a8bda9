"""Programs lowered inside the window, compiled or loaded from the
persistent cache (jax.monitoring's backend-compile events)."""


def read(run):
    return run.compiles
