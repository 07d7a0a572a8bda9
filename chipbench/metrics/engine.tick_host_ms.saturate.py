"""Mean host milliseconds inside the engine's tick() call, per tick."""

from chipbench.readers import span_ms


def read(run):
    return span_ms(run, "engine.tick")
