"""Spans of the served path, written to two sinks.

A span site names the span, its category (one of ``PHASES``) and its
ring-buffer track, and carries the ids of the work it covers as keyword
stats: ``feed`` and ``mb`` (a micro-batch is its feed and the index of its
first frame), ``req`` (the server's request number), ``fwd`` (the
server's forward number), ``query``, and counts such as ``n`` or
``h2d_bytes``.  Both sinks use the same names.

* While a ``jax.profiler`` session records, the span is a
  ``TraceAnnotation`` in the profiler's trace, whatever ``obs`` is: it
  lies in the ``.xplane.pb`` on the clock of the device ops, with its
  stats.
* With ``obs.enabled``, the span also goes to ``obs.tracer`` (the ring
  buffer, exported as Chrome JSON) with its ``n``, and the byte stats
  ``h2d_bytes`` / ``d2h_bytes`` add to the ``link_bytes/h2d`` and
  ``link_bytes/d2h`` counters of ``obs.metrics``.
* With neither, ``span()`` returns ``NULL_SPAN`` after one check of each,
  and nothing is recorded.

One call serves both shapes of span::

    with span(obs, "harvest", "forward", "server", fwd=7):
        ...                                   # a region

    s = span(obs, "queue_wait", "queue", "feed:a", n=16, req=3)
    ...                                       # later, in another call
    s.close(fwd=7)                            # an open/close pair

A span entered at one call and closed at a later one, out of nesting order
with other spans, keeps its own start, end and stats in the profiler's
trace.
"""
from __future__ import annotations

import time
from typing import Any

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled

#: byte stats that also count into ``obs.metrics`` when obs is enabled
LINK_COUNTERS = (("h2d_bytes", "link_bytes/h2d"),
                 ("d2h_bytes", "link_bytes/d2h"))


class NullSpan:
    """What ``span()`` returns when nothing records: every method is a
    no-op, and the object is falsy, so a site can skip computing stats
    (``if s: s.set(...)``)."""

    __slots__ = ()
    t0 = 0
    t1 = 0

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, **stats: Any) -> None:
        pass

    def close(self, **stats: Any) -> None:
        pass


NULL_SPAN = NullSpan()


class Span(NullSpan):
    """An open span.  ``t0`` / ``t1`` are ``perf_counter_ns`` stamps of
    its start and end when obs is enabled, else 0."""

    __slots__ = ("_obs", "_name", "_cat", "_track", "_n", "_tm", "t0", "t1")

    def __init__(self, obs, name: str, cat: str, track: str, n: int,
                 stats: dict):
        self._obs = obs
        self._name = name
        self._cat = cat
        self._track = track
        self._n = n
        self._tm = None
        if _profiling():
            self._tm = TraceAnnotation(name, n=n, **stats)
            self._tm.__enter__()
        self.t1 = 0
        self.t0 = time.perf_counter_ns() if obs.enabled else 0
        if self.t0:
            self._count(stats)

    def __bool__(self) -> bool:
        return True

    def __exit__(self, *exc) -> None:
        self.close()

    def _count(self, stats: dict) -> None:
        for key, counter in LINK_COUNTERS:
            if stats.get(key):
                self._obs.metrics.inc(counter, stats[key])

    def set(self, **stats: Any) -> None:
        """Add stats known only once the work ran (rows out, bytes)."""
        if self._tm is not None:
            self._tm.set_metadata(**stats)
        if self.t0:
            self._count(stats)

    def close(self, **stats: Any) -> None:
        """End the span, adding ``stats``; a second close is a no-op."""
        if stats:
            self.set(**stats)
        if self._tm is not None:
            self._tm.__exit__(None, None, None)
            self._tm = None
        if self.t0 and not self.t1:
            self.t1 = time.perf_counter_ns()
            self._obs.tracer.span(self._name, self._cat, self.t0, self.t1,
                                  track=self._track, n=self._n)


def span(obs, name: str, cat: str, track: str, n: int = 0,
         **stats: Any) -> NullSpan:
    """Open a span in every sink that records; ``NULL_SPAN`` if none."""
    if obs.enabled or _profiling():
        return Span(obs, name, cat, track, n, stats)
    return NULL_SPAN
