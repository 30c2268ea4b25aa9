"""From the window's logs to end-to-end numbers.

Every query answers every frame: a frame the prefix or a filter drops is
answered "no match" at the moment its micro-batch reaches the query's
sink, exactly as a record is.  So a feed's micro-batch ``k`` yields one
result per frame per query, timed from each frame's due time to that
sink call; a closed window yields one result, timed from the due time of
its last frame.

A sink call is matched to its micro-batch so: a micro-batch that reaches
the extract with no rows never suspends, so its tails run inside the pull
that handed it over and carry no ``attrs``; every other micro-batch
resumes after its forward, in pull order, and carries ``attrs``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


class MappingError(RuntimeError):
    pass


def match_calls(pulls: List[Tuple[int, int, int]], log: list
                ) -> List[int]:
    """Micro-batch index of each logged sink call."""
    t_pull = np.asarray([p[0] for p in pulls], np.int64)
    ks = []
    sync_ks = set()
    for t, has_attrs, idx, _ in log:
        if not has_attrs:
            k = int(np.searchsorted(t_pull, t, side="right")) - 1
            if k < 0 or k in sync_ks:
                raise MappingError("a tail ran before any pull")
            sync_ks.add(k)
    rest = iter([k for k in range(len(pulls)) if k not in sync_ks])
    for t, has_attrs, idx, _ in log:
        if has_attrs:
            k = next(rest, None)
            if k is None:
                raise MappingError("more resumed calls than micro-batches")
        else:
            k = int(np.searchsorted(t_pull, t, side="right")) - 1
        first, n = pulls[k][1], pulls[k][2]
        if len(idx) and (idx.min() < first or idx.max() >= first + n):
            raise MappingError(f"rows {idx.min()}..{idx.max()} outside "
                               f"micro-batch {k} [{first}, {first + n})")
        ks.append(k)
    return ks


def results(fleet, window_sizes: Dict[Tuple[str, str], int]
            ) -> Dict[str, np.ndarray]:
    """Latency (ns) of every result, ingest lag (ns) of every pull in
    time order, and the number of (micro-batch, query) pairs never
    answered."""
    lat, lag = [], []
    unanswered = 0
    for (feed, qid), sink in fleet.sinks.items():
        src = fleet.sources[feed]
        pulls = src.pulls
        ks = match_calls(pulls, sink.log)
        unanswered += len(pulls) - len(set(ks))
        w = window_sizes.get((feed, qid))
        prev_ws = 0
        for (t, _, _, ws), k in zip(sink.log, ks):
            first, n = pulls[k][1], pulls[k][2]
            lat.append(t - src.due_ns(np.arange(first, first + n)))
            if w and ws > prev_ws:
                ends = np.arange(prev_ws + w, ws + 1, w) - 1
                lat.append(t - src.due_ns(ends))
            prev_ws = ws
    for src in fleet.sources.values():
        for t, first, n in src.pulls:
            lag.append((t, t - int(src.due_ns(first + n - 1))))
    lag.sort()
    return {"latency_ns": np.concatenate(lat) if lat else np.zeros(0),
            "ingest_lag_ns": np.asarray([x for _, x in lag], np.int64),
            "ingest_t_ns": np.asarray([t for t, _ in lag], np.int64),
            "unanswered": unanswered}
