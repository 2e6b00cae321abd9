"""The encode's share of its roofline: the least time the chip could take,
(dense trees read once + packed slots written once) over the peaks table's
HBM bytes/s, over the measured device time, in %.  The encode does no
matmul work, so bandwidth bounds it."""

PROBES = ("encode",)


def read(rec):
    probe = rec["probes"].get("encode")
    if not probe or probe["seconds"] <= 0:
        return None
    floor_s = probe["bytes"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / probe["seconds"]
