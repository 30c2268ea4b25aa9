"""From a profiler trace to device busy time and per-program device time.

``events(path)`` flattens an ``.xplane.pb`` into plain tuples
``(plane, line, name, start_ns, dur_ns)``; ``reduce(events, ...)`` does
the arithmetic on those, so it can be checked on a small recorded trace
without a chip.

* busy: the union of the intervals of the device's ``XLA Ops`` events
  inside the window, averaged over the chips used;
* kernels: device time and calls of each Pallas kernel (an ``XLA Ops``
  event whose text is ``%<kernel>.<n> = <out shape> custom-call(...)``
  with target ``tpu_custom_call``), per output shape;
* idle gaps: the longest stretches with no device op, each named by the
  benchmark's host span that covers most of it (``bench:await_frames``:
  the runtime waits for a camera frame) or else ``host:runtime``.

The window is bounded by the host markers ``bench:window_start`` and
``bench:window_end`` where the trace has them.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, int, int]


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def events(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if not device and not ev.name.startswith("bench:"):
                    continue
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def _union(iv: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def _kernel(name: str):
    """``(kernel, output shape)`` of a Pallas kernel op, else None."""
    if "tpu_custom_call" not in name or " = " not in name:
        return None
    lhs, rhs = name.split(" = ", 1)
    return lhs.lstrip("%").rsplit(".", 1)[0], rhs.split("{")[0].split(" ")[0]


def reduce(evs: Sequence[Event], top: int = 10) -> Dict[str, object]:
    host = [e for e in evs if not e[0].startswith("/device:")]
    marks = {e[2]: e[3] for e in host if e[2] in ("bench:window_start",
                                                 "bench:window_end")}
    dev = [e for e in evs if e[0].startswith("/device:")]
    if not dev:
        return {"busy_s": 0.0, "window_s": 0.0, "kernels": {},
                "device_ops": [], "idle_gaps": []}
    lo = marks.get("bench:window_start",
                   min(e[3] for e in dev))
    hi = marks.get("bench:window_end",
                   max(e[3] + e[4] for e in dev))
    planes = sorted({e[0] for e in dev})
    busy = 0
    gaps: List[Tuple[int, int]] = []
    ops: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, list]] = {}
    for plane in planes:
        iv = []
        for p, line, name, s, d in dev:
            if p != plane or s + d <= lo or s >= hi:
                continue
            a, b = max(s, lo), min(s + d, hi)
            iv.append((a, b))
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e9
            k = _kernel(name)
            if k is not None:
                slot = kernels.setdefault(k[0], {}).setdefault(k[1], [0, 0.0])
                slot[0] += 1
                slot[1] += (b - a) / 1e9
        u = _union(iv)
        busy += sum(b - a for a, b in u)
        edges = [lo] + [x for ab in u for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    await_iv = [(e[3], e[3] + e[4]) for e in host
                if e[2] == "bench:await_frames"]

    def label(a: int, b: int) -> str:
        cover = sum(max(0, min(b, y) - max(a, x)) for x, y in await_iv)
        return "bench:await_frames" if cover * 2 > (b - a) \
            else "host:runtime"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy / 1e9 / len(planes),
        "window_s": (hi - lo) / 1e9,
        "kernels": kernels,
        "device_ops": [[n, t] for n, t in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }
