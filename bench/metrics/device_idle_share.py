"""Device: share of the traced window with no op on the chip, %."""


def read(run):
    tr = run["trace"]
    if tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
