"""Extract server: real frames per coalesced forward."""


def read(run):
    s = run["stats"]
    return s["frames"] / s["forwards"] if s["forwards"] else None
