"""``extract_mfu`` in a cell above the knee, which reports frames/s."""
import layout


def read(run):
    return layout.metric_reader("extract_mfu").read(run)
