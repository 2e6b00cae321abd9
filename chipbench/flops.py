"""Operations and bytes the measured work requires, from its shapes.

A multiply-add is 2 FLOPs.  Backward is counted as what it requires: the
gradient with respect to every weight (one more pass of each matmul) and
with respect to every activation that a weight gradient needs (another
pass of each matmul but the first one over the input data).  Recomputed
work (remat) is not counted.
"""

from __future__ import annotations

INDEX_BYTES = 4      # a packed TopK slot's uint32 index


def decoder_step(*, d: int, f: int, layers: int, heads: int, kv_heads: int,
                 head_dim: int, vocab: int, batch: int, seq: int) -> int:
    """One local step of a decoder LM with a tied, gated-MLP stack:
    forward and backward over ``batch`` sequences of ``seq`` tokens, with
    logits at every position but the last.  The embedding lookup costs no
    FLOPs; its gradient flows through every layer, so every matmul's input
    gradient is needed."""
    per_layer = d * (heads + 2 * kv_heads) * head_dim + heads * head_dim * d \
        + 3 * d * f
    tokens = batch * seq
    fwd = 2 * per_layer * layers * tokens
    fwd += 2 * d * vocab * batch * (seq - 1)
    # causal attention: QK^T and PV over the seq*(seq+1)/2 visible pairs
    fwd += layers * batch * 2 * 2 * heads * head_dim * seq * (seq + 1) // 2
    return 3 * fwd


def topk_slots(size: int, density: float) -> int:
    """Slots of one leaf's packed TopK payload (``TopK._k``'s rule)."""
    return max(1, min(size, int(round(density * size))))


def encode_bytes(leaf_sizes, itemsize: int, density: float) -> tuple:
    """``(read, written)`` bytes of one client's TopK encode: the dense tree
    read once, the packed slots (index + value) written once."""
    read = sum(leaf_sizes) * itemsize
    slots = sum(topk_slots(n, density) for n in leaf_sizes)
    return read, slots * (INDEX_BYTES + itemsize)
