"""Device time under the program's ``round.encode`` span (the uplink's
compression inside the fused round) per round, in ms, from the
``round_spans`` probe's traced chunks; ``encode_ms`` times the same encode
jitted alone."""

from chipbench import scopes

PROBES = ("round_spans",)


def read(rec):
    return scopes.span_ms(rec, "round_encode_ms", ("round.encode",))
