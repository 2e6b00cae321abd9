"""Device time of the round's uplink encode over the cohort, jitted alone
(``probes/encode.py``), in ms."""

PROBES = ("encode",)


def read(rec):
    probe = rec["probes"].get("encode")
    if not probe:
        return None
    return 1e3 * probe["seconds"]
