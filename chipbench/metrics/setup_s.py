"""From the start of the benchmark's process to the start of the window:
weights, data, program, compilation or compile-cache loads, and the two
set-up chunks."""


def read(rec):
    return rec["setup_s"]
