"""Host spans of the program, written into the JAX profiler's own trace.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: while
a profiler trace records it lands there beside the device operations, on
their clock; while none records it costs about a microsecond. Counts are
keyword arguments, given when the span opens or, where they are known only
when the work ends, through :meth:`Span.count`; both arrive as the trace
event's stats. Count names carry a unit suffix (``_count``, ``_bytes``;
:data:`repro.obs.metrics.UNIT_SUFFIXES`, checked by
``tools/analysis/obs_lint.py``). A count that costs work to compute is
computed only while :func:`recording`.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

import jax

PREFIX = "repro."


class Span:
    """An open span."""

    def __init__(self, annotation: jax.profiler.TraceAnnotation):
        self._annotation = annotation

    def count(self, **counts: int) -> None:
        """Attach counts known only once the work is done."""
        self._annotation.set_metadata(**counts)


@contextlib.contextmanager
def span(name: str, **counts: int) -> Iterator[Span]:
    with jax.profiler.TraceAnnotation(PREFIX + name, **counts) as annotation:
        yield Span(annotation)


def recording() -> bool:
    """True while a profiler trace records this process's spans."""
    return jax.profiler.TraceAnnotation.is_enabled()
