"""The device's ``peak_bytes_in_use`` right after the window, in GB."""


def read(rec):
    if rec["peak_bytes"] is None:
        return None
    return rec["peak_bytes"] / 1e9
