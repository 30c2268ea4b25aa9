"""``ingest_lag_p95_ms`` in a cell above the knee, which reports frames/s."""
import layout


def read(run):
    return layout.metric_reader("ingest_lag_p95_ms").read(run)
