"""Share of the device's busy time, in the ``round_spans`` probe's traced
chunks, that no operation under a ``round.*`` span covers (the rounds'
scan and key bookkeeping, and anything the spans miss), in %."""

from chipbench import scopes

PROBES = ("round_spans",)


def read(rec):
    return scopes.unscoped_share(rec, "unscoped_share")
