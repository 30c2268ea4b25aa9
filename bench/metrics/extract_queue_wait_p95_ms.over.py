"""``extract_queue_wait_p95_ms`` in a cell above the knee, which reports
frames/s."""
import layout


def read(run):
    return layout.metric_reader("extract_queue_wait_p95_ms").read(run)
