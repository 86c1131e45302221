"""The yardstick's FLOP/byte functions and peaks table, against hand-worked
shapes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import costs, peaks  # noqa: E402

MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 2,
           "vocab_size": 32000, "num_attention_heads": 32, "num_key_value_heads": 8}


def test_peaks_table_v5e_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "source" in p
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")


@pytest.mark.parametrize("n,start,pairs", [(1, 0, 1), (4, 0, 10), (4, 10, 50), (256, 512, 163968)])
def test_causal_pairs(n, start, pairs):
    assert costs.causal_pairs(n, start) == pairs


def test_flash_fwd_by_hand():
    # b=1, s=4, hq=2, hkv=1, hd=8: 10 causal pairs x 4*2*8 FLOPs
    f, by = costs.flash_fwd(1, 4, 2, 1, 8)
    assert f == 640.0
    # q + out (2 heads) and k + v (1 head), 4 x 8 bf16 each, + fp32 lse 4 x 2
    assert by == 2 * 4 * 8 * (2 * 2 + 2 * 1) + 4 * 4 * 2
    full, _ = costs.flash_fwd(1, 4, 2, 1, 8, causal=False)
    assert full == 4.0 * 2 * 8 * 16


def test_flash_bwd_is_five_matmuls_to_the_forwards_two():
    f, _ = costs.flash_fwd(1, 4096, 32, 8, 128)
    b, _ = costs.flash_bwd(1, 4096, 32, 8, 128)
    assert b == pytest.approx(2.5 * f)
    # seq 4096 at Mistral widths: 4*32*128 FLOPs x 4096*4097/2 pairs
    assert f == pytest.approx(16384 * 8390656)


def test_paged_decode_by_hand():
    # two sequences of 100 and 300 cached tokens, hq=32, hkv=8, hd=128, bf16
    f, by = costs.paged_decode([100, 300], 32, 8, 128)
    assert f == 4.0 * 32 * 128 * 400
    assert by == 2 * (2 * 8 * 128 * 400) + 2 * 2 * (2 * 32 * 128)
    # memory bound on a v5e: bytes / 819e9 exceeds flops / 197e12
    p = peaks.peaks_for("TPU v5 lite")
    assert costs.roofline_min_s(f, by, p) == by / 819e9


def test_packed_ctx_by_hand():
    # one entry: 2 new tokens after 3 cached; hq=2, hkv=1, hd=4
    f, by = costs.packed_ctx([(3, 5)], 2, 1, 4)
    assert f == 4.0 * 2 * 4 * (2 * 3 + 3)  # queries see 4 and 5 keys
    kv = 2 * (2 * 1 * 4 * 5)               # K and V rows of 5 tokens, bf16
    q = 2 * (2 * 4 * 2)                    # q of 2 tokens, bf16
    out = 4 * (2 * 4 * 2 + 2 * 2 * 2)      # fp32 acc + (m, l)
    assert by == kv + q + out
    # at Mistral widths a cold 256-token pack is bound by its bytes (the fp32
    # accumulator out), the same pack behind 2048 cached tokens by its FLOPs
    p = peaks.peaks_for("TPU v5 lite")
    f, by = costs.packed_ctx([(0, 256)], 32, 8, 128)
    assert costs.roofline_min_s(f, by, p) == by / 819e9
    f, by = costs.packed_ctx([(2048, 2304)], 32, 8, 128)
    assert f == 4.0 * 32 * 128 * (256 * 2048 + 256 * 257 // 2)
    assert costs.roofline_min_s(f, by, p) == f / 197e12


def test_train_flops_per_token_by_hand():
    d, f, v, hq, hkv, hd = 4096, 14336, 32000, 32, 8, 128
    layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f
    assert costs.matmul_params(MISTRAL) == 2 * layer + d * v
    per_tok = costs.train_flops_per_token(MISTRAL, 4096)
    attn = 2 * 3 * 4.0 * hq * hd * 4097 / 2
    assert per_tok == pytest.approx(6.0 * (2 * layer + d * v) + attn)
    # the attention term is a visible share at seq 4096, not a rounding error
    assert 0.05 < attn / per_tok < 0.2
