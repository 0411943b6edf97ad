"""The FLOP and byte counters against hand counts at both configurations'
widths, and the peak table."""
import pytest

from chipbench import counters, peaks, spec


def _sizes(name):
    cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json",
                         "configuration")
    return counters.Sizes.from_arch(cfg["arch"])


def test_gpt2_xl_counts():
    s = _sizes("gpt2-1.5b")
    # per layer: q,k,v,o 4 x 1600^2 + MLP 2 x 1600 x 6400; 48 layers
    assert counters.matmul_flops_per_token(s) == 2 * 48 * (
        4 * 1600 * 1600 + 2 * 1600 * 6400) == 2_949_120_000
    assert counters.attention_flops(s, 1) == 4 * 48 * 25 * 64 == 307_200
    assert counters.head_flops(s) == 2 * 1600 * 50257 == 160_822_400
    assert counters.decode_flops(s, 300) == \
        2_949_120_000 + 300 * 307_200 + 160_822_400
    # a 64-token chunk at 128..191: keys 129..192
    keys = sum(range(129, 193))
    assert counters.chunk_flops(s, 128, 64, False) == \
        64 * 2_949_120_000 + keys * 307_200
    # fused decode pass of 8: each stage packs 24 layers x 8 seqs x 8 tokens
    # x 25 heads x 64 x 2 bytes, read and written
    assert counters.kv_pack_ragged_bytes(s, 24, 8, 8) == \
        2 * 24 * 8 * 8 * 25 * 64 * 2 == 9_830_400


def test_opt_66b_stage_counts():
    s = _sizes("opt-66b-s4")
    per_layer = 4 * 9216 * 9216 + 2 * 9216 * 36864
    assert per_layer == 1_019_215_872
    assert counters.matmul_flops_per_token(s) == 2 * 4 * per_layer
    assert counters.attention_flops(s, 1) == 4 * 4 * 72 * 128
    assert counters.head_flops(s) == 2 * 9216 * 50272
    assert counters.chunk_flops(s, 0, 10, True) == \
        10 * 8 * per_layer + 55 * 4 * 4 * 72 * 128 + 2 * 9216 * 50272
    assert counters.kv_pack_ragged_bytes(s, 2, 4, 8) == \
        2 * 2 * 4 * 8 * 72 * 128 * 2


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
