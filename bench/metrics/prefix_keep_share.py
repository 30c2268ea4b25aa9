"""Prefix ops: share of ingested frames the prefix hands to the extract
(the server's ``frames`` counter over frames ingested), %."""


def read(run):
    n = run["frames_ingested"]
    return 100.0 * run["stats"]["frames"] / n if n else None
