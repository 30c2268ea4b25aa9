"""Runtime, above the knee: 95th percentile of every result's latency
(each (frame, query) answer and closed window, from its due time), ms.
The queue grows through such a run, so the tail reads the queue's
growth; the cell's end-to-end metric is frames/s."""
import numpy as np


def read(run):
    lat = run["window"]["latency_ns"]
    return float(np.percentile(lat, 95) / 1e6) if len(lat) else None
