"""The whole round's share of the chip's peak: FLOPs that the drawn local
steps' forward and backward passes require (``models/<config>.py``'s
``step_flops``, no recomputation), over the traced window's host time,
over the peaks table's bf16 FLOP/s, in %."""


def read(rec):
    if rec["trace"] is None or not rec["client_steps"]:
        return None
    flops = rec["client_steps"] * rec["step_flops"]
    return 100.0 * flops / rec["window_s"] / rec["peaks"]["bf16_flops_per_s"]
