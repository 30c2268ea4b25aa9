"""Runtime: how late the runtime took each micro-batch from its feed.

For every pull, hand-over time minus the due time of the micro-batch's
last frame (the earliest the batch could be taken), 95th percentile, ms.
"""
import numpy as np


def read(run):
    lag = run["window"]["ingest_lag_ns"]
    return float(np.percentile(lag, 95) / 1e6) if len(lag) else None
