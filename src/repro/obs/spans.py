"""Engine spans on the profiler's clock.

:func:`span` marks one seam of the engine's host work (scheduling, dispatch,
page tables, the argmax sync, token bookkeeping) as a
``jax.profiler.TraceAnnotation`` named ``engine.<name>``. While a profile is
recording (``jax.profiler.start_trace`` or a profiling server), each span
lands on the host's timeline beside the device's programs and operations, so
a gap in which the device runs nothing can be attributed to the host work
that was open around it. With no profile recording a span writes nothing and
costs about a microsecond to enter and leave.

These spans are separate from :class:`repro.obs.trace.Tracer`: they are gated
by the profiler session, never by a tracer's level, and they add no event to
its JSON stream, which stays byte-identical whether a profile records or not.
Arguments are scalars or one short string; a span that learns an argument
only at its end adds it with ``set_metadata`` on the object ``with`` binds.
"""
from __future__ import annotations

import jax

PREFIX = "engine."


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager for the profiler span ``engine.<name>``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)
