"""Extract server: padding rows over all rows of the forwards, %."""


def read(run):
    s = run["stats"]
    rows = s["frames"] + s["padded_frames"]
    return 100.0 * s["padded_frames"] / rows if rows else None
