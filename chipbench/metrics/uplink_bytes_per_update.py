"""Packed uplink payload bytes shipped in the window over the client
updates that shipped them.  The program reports the bytes per round as
float32, so the sum is exact to float32's spacing at each round's value
(16 B at qwen2-0.5b's 148 MB), read on the host in float64."""


def read(rec):
    if not rec["updates"]:
        return None
    return rec["payload_bytes"] / rec["updates"]
