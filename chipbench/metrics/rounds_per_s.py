"""Rounds completed in the window over the window's whole time (host
clock, up to the end of the last chunk's metrics pull)."""


def read(rec):
    return rec["rounds"] / rec["window_s"]
