"""The FLOP and byte functions against hand counts."""

from chipbench import flops
from chipbench.bench import Bench


def test_decoder_step_qwen2_0_5b():
    # per layer: q 896x896, k and v 896x128, o 896x896, MLP 3 x 896x4864
    per_layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    layers = 24 * per_layer                       # 357,826,560 weights
    unembed = 151_936 * 896                       # tied, 136,134,656
    tokens, logits = 2 * 128, 2 * 127             # batch 2 x 128 tokens
    attn = 24 * 2 * (2 * 2 * 14 * 64) * (128 * 129 // 2)
    fwd = 2 * layers * tokens + 2 * unembed * logits + attn
    assert fwd == 253_783_900_160
    got = flops.decoder_step(d=896, f=4864, layers=24, heads=14, kv_heads=2,
                             head_dim=64, vocab=151_936, batch=2, seq=128)
    assert got == 3 * fwd == 761_351_700_480


def qwen_leaf_sizes():
    per_layer = [896 * 896, 896, 896 * 128, 128, 896 * 128, 128, 896 * 896,
                 896, 896, 896 * 4864, 4864 * 896, 896 * 4864]
    return [151_936 * 896, 896] + 24 * per_layer


def test_encode_bytes_qwen2_0_5b():
    sizes = qwen_leaf_sizes()
    assert sum(sizes) == 494_032_768
    # k = round(0.05 n): 6,806,733 for the embedding, 45 for the final
    # norm, and per layer 40,141 + 45 + 5,734 + 6 + 5,734 + 6 + 40,141 + 45
    # + 45 + 3 x 217,907 = 745,618
    slots = 6_806_733 + 45 + 24 * 745_618
    assert slots == 24_701_610
    read, written = flops.encode_bytes(sizes, 2, 0.05)
    assert read == 988_065_536
    assert written == slots * (4 + 2) == 148_209_660


def test_the_qwen2_cell_counts_as_the_hand_count():
    bench = Bench()
    cell = bench.cell("qwen2-0.5b.topk-uplink")
    model = bench.model(cell["config"])
    assert model.step_flops(bench.config(cell["config"]), bench.traffic(
        cell["traffic"])) == 761_351_700_480
