"""``frames_per_forward`` in a cell above the knee, which reports frames/s."""
import layout


def read(run):
    return layout.metric_reader("frames_per_forward").read(run)
