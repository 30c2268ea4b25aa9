"""``device_idle_share`` in a cell above the knee, which reports frames/s."""
import layout


def read(run):
    return layout.metric_reader("device_idle_share").read(run)
