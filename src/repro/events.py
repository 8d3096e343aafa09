"""Set-up durations the program reports through ``jax.monitoring``.

Set-up runs before a profiler trace would start, so its phases are reported
as duration events, which any ``jax.monitoring`` listener receives (JAX's
own compile events travel the same way).  With no listener registered an
event costs a fraction of a microsecond.  The event names, and the spans
and scopes that go with them, are listed in ``README.md`` ("Seeing what a
Frontend does").
"""
from __future__ import annotations

import contextlib
import time

import jax


@contextlib.contextmanager
def timed(event: str, **attrs):
    """Report the wall seconds of the ``with`` body (or of each call, as a
    decorator) as the duration event ``event``.  ``with timed(...) as a``
    gives the event's attributes, to which the body may add counts.
    Nothing is reported when the body raises."""
    t0 = time.perf_counter()
    yield attrs
    jax.monitoring.record_event_duration_secs(
        event, time.perf_counter() - t0, **attrs
    )
