"""Drawn local steps over executed local steps, in %: the masked local
phase runs every client to ``steps_cap`` steps and keeps the drawn ones."""


def read(rec):
    executed = rec["rounds"] * rec["cohort"] * rec["steps_cap"]
    if not executed:
        return None
    return 100.0 * rec["client_steps"] / executed
