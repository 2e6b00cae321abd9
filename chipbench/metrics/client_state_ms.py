"""Device time under the program's ``round.state_gather`` and
``round.state_update`` spans (the cohort's rows of per-client state taken
from the store, updated and written back) per round, in ms, from the
``round_spans`` probe's traced chunks."""

from chipbench import scopes

PROBES = ("round_spans",)


def read(rec):
    return scopes.span_ms(rec, "client_state_ms",
                          ("round.state_gather", "round.state_update"))
