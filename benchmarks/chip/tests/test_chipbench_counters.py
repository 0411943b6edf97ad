"""The counts a configuration's reference module gives (`counters`' hooks)
against hand counts at both configurations' widths, the stage decode
program's FLOPs and bytes, the `stage_decode_roofline` reader on a
synthetic trace, and the peak table."""
from types import SimpleNamespace as NS

import pytest

from chipbench import counters, peaks, spec
from chipbench.harness import Run
from chipbench.tracefile import TraceSummary
from chipbench.window import Pass


def _config(name):
    cfg = spec.load_json(spec.HERE / "configs" / f"{name}.json",
                         "configuration")
    return spec.reference(cfg), cfg["arch"]


def test_gpt2_xl_counts():
    ref, arch = _config("gpt2-1.5b")
    s = counters.Sizes.from_reference(ref, arch)
    # per layer: q,k,v,o 4 x 1600^2 + MLP 2 x 1600 x 6400; 48 layers
    assert ref.matmul_flops_per_token(arch) == s.matmul_flops_per_token \
        == 2 * 48 * (4 * 1600 * 1600 + 2 * 1600 * 6400) == 2_949_120_000
    assert ref.attention_flops_per_key(arch) == 4 * 48 * 25 * 64 == 307_200
    assert ref.head_flops(arch) == 2 * 1600 * 50257 == 160_822_400
    assert counters.decode_flops(s, 300) == \
        2_949_120_000 + 300 * 307_200 + 160_822_400
    # a 64-token chunk at 128..191: keys 129..192
    keys = sum(range(129, 193))
    assert counters.chunk_flops(s, 128, 64, False) == \
        64 * 2_949_120_000 + keys * 307_200


def test_opt_66b_stage_counts():
    ref, arch = _config("opt-66b-s4")
    s = counters.Sizes.from_reference(ref, arch)
    per_layer = 4 * 9216 * 9216 + 2 * 9216 * 36864
    assert per_layer == 1_019_215_872
    assert s.matmul_flops_per_token == 2 * 4 * per_layer
    assert s.attention_flops_per_key == 4 * 4 * 72 * 128
    assert s.head_flops == 2 * 9216 * 50272
    assert counters.chunk_flops(s, 0, 10, True) == \
        10 * 8 * per_layer + 55 * 4 * 4 * 72 * 128 + 2 * 9216 * 50272


def test_gpt2_stage_decode_cost():
    """Two stages of 24 layers, a batch whose queries see 300 and 40 keys."""
    ref, arch = _config("gpt2-1.5b")
    keys = [300, 40]
    layer = 4 * 1600 * 1600 + 2 * 1600 * 6400 + 4 * 1600   # + ln1, ln2
    kv = 2 * 24 * 1600 * 340                # K and V, 24 layers, 340 tokens
    f0, b0 = ref.stage_decode_cost(arch, 0, 24, keys, True, False)
    # token and position rows of 2 sequences
    assert b0 == 2 * (24 * layer + kv + 2 * 2 * 1600) == 1_527_104_000
    f1, b1 = ref.stage_decode_cost(arch, 24, 48, keys, False, True)
    # the tied head [50257, 1600] and the final norm
    assert b1 == 2 * (24 * layer + kv + 50257 * 1600 + 2 * 1600) \
        == 1_687_920_000
    assert f0 == 2 * 24 * 30_720_000 * 2 + 4 * 24 * 1600 * 340
    # the stages' FLOPs add up to the recorder's per-token count
    s = counters.Sizes.from_reference(ref, arch)
    assert f0 + f1 == sum(counters.decode_flops(s, k) for k in keys)


def test_opt_66b_stage_decode_cost():
    """docqa's 2 stages of 2 layers, 4 sequences; the tied embedding is
    read whole on the last stage only."""
    ref, arch = _config("opt-66b-s4")
    keys = [1000, 1200, 900, 1100]
    layer = 1_019_215_872 + 4 * 9216
    kv = 2 * 2 * 9216 * 4200
    f0, b0 = ref.stage_decode_cost(arch, 0, 2, keys, True, False)
    assert b0 == 2 * (2 * layer + kv + 2 * 4 * 9216)
    f1, b1 = ref.stage_decode_cost(arch, 2, 4, keys, False, True)
    assert b1 == 2 * (2 * layer + kv + 50272 * 9216 + 2 * 9216)
    # ~9.1 GB of weights and ~0.6 GB of KV per pass: 11.9 ms at 819 GB/s
    assert b0 + b1 == pytest.approx(9.70e9, rel=0.01)
    s = counters.Sizes.from_reference(ref, arch)
    assert f0 + f1 == sum(counters.decode_flops(s, k) for k in keys)


def _roofline_run(modules):
    """Two decode passes and a prefill pass of the gpt2 configuration over
    2 stages, on a trace whose clock is the host's minus 100 s."""
    ref, arch = _config("gpt2-1.5b")
    passes = [Pass("decode", 100.10, 100.20, rids=[0, 1], keys=[300, 40]),
              Pass("prefill", 100.20, 100.30, rids=[2]),
              Pass("decode", 100.30, 100.40, rids=[0, 1], keys=[301, 41])]
    tr = TraceSummary(window=(0.0, 1.0), busy_s=0.5, devices=1,
                      modules=modules)
    return Run(cell=None, ref=ref, arch=arch, rec=NS(passes=passes),
               window_s=1.0, t_open=100.0, t_close=101.0, setup_s=1.0,
               clients=2, stages=[(0, 24), (24, 48)],
               peaks=peaks.peaks_for("TPU v5 lite"), trace=tr,
               trace_t0=100.0, trace_t1=101.0)


def test_stage_decode_roofline_reader():
    read = spec.reader("stage_decode_roofline")
    name = "jit_stage_decode_batch_paged"
    run = _roofline_run({name: [
        (0.11, 0.010), (0.13, 0.012),          # pass 1: stage 0, stage 1
        (0.21, 0.010),                         # a prefill pass: left out
        (0.31, 0.010), (0.32, 0.010), (0.33, 0.010)]})   # 3: left out
    ref, arch, bw = run.ref, run.arch, 819e9
    least = (ref.stage_decode_cost(arch, 0, 24, [300, 40], True, False)[1]
             + ref.stage_decode_cost(arch, 24, 48, [300, 40], False,
                                     True)[1]) / bw
    assert read(run) == pytest.approx(100.0 * least / 0.022)
    assert 0 < read(run) < 100


@pytest.mark.parametrize("modules", [
    {}, {"jit_stage_decode_batch": [(0.11, 0.01), (0.13, 0.01)]},
    {"jit_stage_decode_batch_paged": [(0.21, 0.01), (0.25, 0.01)]}])
def test_stage_decode_roofline_reads_none_without_a_match(modules):
    read = spec.reader("stage_decode_roofline")
    assert read(_roofline_run(modules)) is None
    run = _roofline_run(modules)
    run.trace = None
    assert read(run) is None


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")
