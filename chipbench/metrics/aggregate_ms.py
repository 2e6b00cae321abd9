"""Device time under the program's ``round.aggregate`` span (the policy,
the server-side decode of the gathered payloads and the mean) per round,
in ms, from the ``round_spans`` probe's traced chunks."""

from chipbench import scopes

PROBES = ("round_spans",)


def read(rec):
    return scopes.span_ms(rec, "aggregate_ms", ("round.aggregate",))
