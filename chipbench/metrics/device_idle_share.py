"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals over the window), in %."""


def read(rec):
    t = rec["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
