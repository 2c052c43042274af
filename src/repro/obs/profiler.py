"""Program spans on the profiler's clock.

`span` writes a host event into the trace of a running ``jax.profiler``
session, the same ``.xplane.pb`` that holds the device's operations, so a
reader can say what the host was doing while the device sat idle. With
no session running it records nothing and costs about a microsecond.

The name is fixed and the numbers an operator needs go in ``meta``
(event stats), so a reader matches names without parsing them.
"""
from __future__ import annotations

import jax


def span(name: str, **meta):
    """Context manager for one host span named ``name`` with ``meta`` as
    its stats. ``set_metadata(**more)`` on the entered span adds stats
    known only inside it."""
    return jax.profiler.TraceAnnotation(name, **meta)
