"""Prefix ops and extract server: bytes across the host link, both ways,
per frame ingested (the ``h2d_bytes`` / ``d2h_bytes`` the program counts
on its spans; program counter)."""
import spans


def read(run):
    return spans.link_bytes_per_frame(spans.of_run(run))
