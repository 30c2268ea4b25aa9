"""The program's own spans in a traced run, for the per-layer readers.

The served path writes its spans into the profiler's trace
(``repro.obs.spans``): ``ingest``, ``prefix:<op>``, ``queue_wait``,
``staging``, ``dispatch[<variant>]``, ``forward[<variant>]``, ``block``,
``harvest``, ``resume`` and ``tail``, each with its ids and counts as
stats.  ``events(path)`` reads them from an ``.xplane.pb``, with the
benchmark's window markers and the device's ``XLA Modules`` executions,
as plain ``Event`` tuples; ``window(evs)`` keeps what overlaps the span
from ``bench:window_start`` to ``bench:window_end``.  The readers below do
their arithmetic on that, so each can be checked on a hand-built list
without a chip.

A program that writes no such spans (one older than them) gives empty
lists, and every reader then returns None.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

WINDOW = ("bench:window_start", "bench:window_end")
SPANS = ("ingest", "queue_wait", "staging", "block", "harvest", "resume",
         "tail")
SPAN_PREFIXES = ("prefix:", "dispatch[", "forward[")
#: the extract server's forward program (``streaming/mllm.py: _extract``)
EXTRACT_MODULE = "jit__extract"


class Event(NamedTuple):
    kind: str            # "span" (host), "mark" or "module" (device)
    name: str
    start: int           # ns, the profiler's clock
    end: int
    stats: Dict[str, object]


class Window(NamedTuple):
    lo: int
    hi: int
    spans: List[Event]
    modules: List[Event]


def _ours(name: str) -> bool:
    return name in SPANS or name.startswith(SPAN_PREFIXES)


def events(path: str) -> List[Event]:
    """Host spans of the program and the window markers, with their
    stats, and the ``XLA Modules`` executions of every device plane,
    each with its ``plane``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != "XLA Modules":
                continue
            for ev in line.events:
                name = ev.name
                if device:
                    kind, stats = "module", {"plane": plane.name}
                elif name in WINDOW:
                    kind, stats = "mark", {}
                elif _ours(name):
                    kind, stats = "span", dict(ev.stats)
                else:
                    continue
                start = int(ev.start_ns)
                out.append(Event(kind, name, start,
                                 start + int(ev.duration_ns), stats))
    return out


def window(evs: Sequence[Event]) -> Window:
    """Spans and modules that overlap the window, in start order (empty
    where the trace has no window markers)."""
    marks = {e.name: e.start for e in evs if e.kind == "mark"}
    if set(marks) != set(WINDOW):
        return Window(0, 0, [], [])
    lo, hi = marks[WINDOW[0]], marks[WINDOW[1]]

    def inside(kind):
        return sorted((e for e in evs if e.kind == kind
                       and e.end > lo and e.start < hi),
                      key=lambda e: e.start)
    return Window(lo, hi, inside("span"), inside("module"))


def of_run(run) -> Window:
    """The window of the run's trace, read once per run."""
    if "program_spans" not in run:
        import harness
        import trace_reduce
        xp = trace_reduce.find_xplane(harness.TRACE_DIR)
        run["program_spans"] = window(events(xp) if xp else [])
    return run["program_spans"]


def _union(iv: Sequence[Tuple[int, int]]) -> int:
    total, end = 0, None
    for a, b in sorted(iv):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


# ---------------------------------------------------------------- readers
def queue_wait_p95_ms(w: Window) -> Optional[float]:
    """95th percentile over frames of their request's ``queue_wait``
    (submit → launch): a request counts once per frame it carries."""
    qs = [e for e in w.spans if e.name == "queue_wait"]
    if not qs:
        return None
    waits = np.repeat([e.end - e.start for e in qs],
                      [int(e.stats["n"]) for e in qs])
    return float(np.percentile(waits, 95) / 1e6)


def harvest_delays_ns(w: Window) -> Optional[List[int]]:
    """Per forward: start of its ``harvest`` minus the end of its device
    execution.  The device runs the forwards in launch order, so the
    k-th extract module in the window (on the device plane that runs
    them) is the k-th ``dispatch[*]``'s; a window where the two counts
    differ reads None."""
    launches = [e for e in w.spans if e.name.startswith("dispatch[")]
    by_plane: Dict[str, List[Event]] = {}
    for e in w.modules:
        if e.name.startswith(EXTRACT_MODULE):
            by_plane.setdefault(e.stats["plane"], []).append(e)
    runs = max(by_plane.values(), key=len, default=[])
    if not launches or len(launches) != len(runs):
        return None
    harvest = {e.stats["fwd"]: e.start for e in w.spans
               if e.name == "harvest"}
    out = [harvest[d.stats["fwd"]] - m.end
           for d, m in zip(launches, runs) if d.stats["fwd"] in harvest]
    return out or None


def harvest_delay_mean_ms(w: Window) -> Optional[float]:
    d = harvest_delays_ns(w)
    return float(np.mean(d) / 1e6) if d else None


def runtime_busy_share(w: Window) -> Optional[float]:
    """Share of the window, %, in which the runtime thread does host work:
    1 − the union of its ``ingest`` (waiting for frames) and ``block``
    (waiting for the device) spans, clipped to the window, over it."""
    waits = [(max(e.start, w.lo), min(e.end, w.hi)) for e in w.spans
             if e.name in ("ingest", "block")]
    if not any(e.name == "ingest" for e in w.spans) or w.hi <= w.lo:
        return None
    return 100.0 * (1.0 - _union(waits) / (w.hi - w.lo))


def link_bytes_per_frame(w: Window) -> Optional[float]:
    """Bytes across the host link, both ways, per frame ingested: the
    ``h2d_bytes`` and ``d2h_bytes`` stats of every span in the window
    over the frames of its ``ingest`` spans."""
    frames = sum(int(e.stats["n"]) for e in w.spans if e.name == "ingest")
    if not frames:
        return None
    moved = sum(int(e.stats.get("h2d_bytes", 0)) +
                int(e.stats.get("d2h_bytes", 0)) for e in w.spans)
    return moved / frames
