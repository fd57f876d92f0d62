"""Program spans on the profiler's clock.

:func:`span` is ``jax.profiler.TraceAnnotation``: a named host span that
a captured trace (``jax.profiler.start_trace``) holds on the host plane,
on the same clock as the device's operations. It is never gated on the
telemetry switch, so every trace holds the program's spans; with no
profiler session a span costs well under a microsecond.

    >>> with span("service.pull", flush=3):
    ...     np.asarray(x)

Keyword metadata (``flush=3``) is encoded into the event (``name#k=v#``);
readers key on the name before any ``#``. Spans of one flush carry its
ordinal as ``flush``.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation as span

__all__ = ["span"]
