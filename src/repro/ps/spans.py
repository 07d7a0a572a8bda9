"""Host spans inside the service engines, off by default.

While off, :func:`span` returns one shared no-op context manager, so the
engines' hot paths pay a call and a flag test.  While on, it returns a
``jax.profiler.TraceAnnotation``: under ``jax.profiler.trace(dir)`` the
spans land on the trace's ``/host:CPU`` plane, on the same clock as the
device lines, with ``meta`` as event stats.  Outside a profiler session an
annotation records nothing.

    from repro.ps import spans
    spans.enable(True)
    with jax.profiler.trace(trace_dir):
        ...  # submit_push / tick / pull
"""

from __future__ import annotations

import jax

_on = False


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        pass


NO_SPAN = _NoSpan()


def enable(on: bool) -> None:
    """Turn the engines' spans on or off for the whole process."""
    global _on
    _on = bool(on)


def span(name: str, **meta):
    """A context manager that records ``name`` with ``meta`` while spans
    are on (``set_metadata`` adds stats known only inside the span)."""
    if not _on:
        return NO_SPAN
    return jax.profiler.TraceAnnotation(name, **meta)
