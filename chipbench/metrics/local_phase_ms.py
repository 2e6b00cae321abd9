"""Device time under the program's ``round.local_phase`` span (the masked
local-step scan over the cohort) per round, in ms, from the
``round_spans`` probe's traced chunks."""

from chipbench import scopes

PROBES = ("round_spans",)


def read(rec):
    return scopes.span_ms(rec, "local_phase_ms", ("round.local_phase",))
