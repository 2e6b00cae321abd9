"""XLA backend compiles inside the window (``jax.monitoring``)."""


def read(rec):
    return float(rec["compiles_in_window"])
