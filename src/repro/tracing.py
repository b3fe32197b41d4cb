"""The program's tracing: named spans with integer counts on the profiler's clock.

:func:`span` is the tracing entry of the package, and :func:`tally` the
same for a span whose counts are known only at its end. Each span is a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` whose keyword
counts become the event's stats, so a round's host work (window fill,
host→device copy, kernel, copy back, read-back) lands on the same clock
as the device's ops in a ``jax.profiler`` trace. A span records only
while such a trace runs; otherwise it costs a few microseconds and
records nothing. A span yields nothing and returns nothing, and a tally
yields only its own counts: no caller reads a time through either, so
simulated time in the event planes still comes only from the event heap.

These spans are not the fold pool's element spans
(:meth:`repro.core.fold_pool.ParallelFoldPool.run_spans`), which are
``[lo, hi)`` ranges of a vector that one worker folds.

To trace a round::

    with jax.profiler.trace("/tmp/round-trace"):
        session.round(client_grads)

and open the trace in TensorBoard or Perfetto; the spans sit on the host
threads under their ``repro.`` names, with their counts as arguments.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

PREFIX = "repro."


@contextlib.contextmanager
def span(name: str, **counts: int) -> Iterator[None]:
    """Mark the enclosed block as span ``repro.<name>``, with ``counts``
    (integers: bytes, elements, a window's index) as the event's stats."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(PREFIX + name, **counts):
        yield


@contextlib.contextmanager
def tally(name: str, **counts: int) -> Iterator[dict]:
    """Span ``repro.<name>`` whose counts are settled inside it: yields
    ``counts`` as a dict for the block to update, and the values it holds
    when the block ends become the event's stats."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation(PREFIX + name) as annotation:
        yield counts
        annotation.set_metadata(**counts)
