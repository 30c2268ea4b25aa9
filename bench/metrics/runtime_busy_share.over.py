"""``runtime_busy_share`` in a cell above the knee, which reports
frames/s."""
import layout


def read(run):
    return layout.metric_reader("runtime_busy_share").read(run)
