"""Runtime: share of the window in which the runtime thread does host
work, not waiting for frames (``ingest``) or for the device (``block``),
% (device trace)."""
import spans


def read(run):
    return spans.runtime_busy_share(spans.of_run(run))
