"""Runtime: median latency of every result (each (frame, query) answer
and closed window, from its due time), ms: the micro-batch's fill time
and what waits behind it.  On one feed the latencies come in steps of a
frame period and the median sits on a step's edge, so it is a reading,
not a bound."""
import numpy as np


def read(run):
    lat = run["window"]["latency_ns"]
    return float(np.percentile(lat, 50) / 1e6) if len(lat) else None
