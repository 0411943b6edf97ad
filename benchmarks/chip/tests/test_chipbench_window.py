"""Window arithmetic from synthetic token timestamps: closed-loop send
times, tokens/s, inter-token gaps at the window's edges, regenerated
tokens, and the recorder's pass bookkeeping."""
import math

import numpy as np
import pytest

from chipbench import counters, window
from chipbench.window import Recorder, TimedTokens

SIZES = counters.Sizes(layers=2, d_model=64, heads=4, kv_heads=4,
                       head_dim=16, d_ff=128, vocab=256, gated_mlp=False,
                       itemsize=2)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_tokens_and_gaps_respect_window_edges():
    first = {0: [0.5, 1.5, 2.0, 4.5], 1: [3.0, 3.25, 5.5]}
    assert window.tokens_in_window(first, 1.0, 4.0) == 4
    # gaps with both tokens in [1, 4]: 1.5->2.0 and 3.0->3.25 (0.5->1.5
    # starts before the window, 2.0->4.5 and 3.25->5.5 end after it)
    assert sorted(window.gaps_in_window(first, 1.0, 4.0)) == [0.25, 0.5]
    assert window.gaps_in_window({}, 0, 1) == []
    assert math.isnan(window.percentile([], 95))
    assert window.percentile([1.0] * 19 + [21.0], 95) == pytest.approx(2.0)


def test_tokens_per_s_and_itl_p95_readers():
    from chipbench.spec import reader

    class R:
        window_s = 4.0
        rec = type("rec", (), {"first": {0: [0.0, 1.0, 2.0, 3.0, 4.0],
                                         1: [1.0, 3.0, 5.0]}})()
        t_open, t_close = 0.0, 4.0

        def tokens_out(self):
            return window.tokens_in_window(self.rec.first, 0.0, 4.0)

        def gaps(self):
            return window.gaps_in_window(self.rec.first, 0.0, 4.0)

    assert reader("tokens_per_s")(R()) == pytest.approx(7 / 4)
    gaps = [1.0] * 4 + [2.0]
    assert reader("engine.itl_p95_ms")(R()) == pytest.approx(
        np.percentile(gaps, 95) * 1e3)


def test_closed_loop_send_times_and_attempted():
    # 2 clients; completions at 3 and 5: request 2 is sent at 3, 3 at 5
    sends = window.send_times([5.0, 3.0], clients=2, n=5, t_open=1.0)
    assert sends == [1.0, 1.0, 3.0, 5.0, None]
    assert window.attempted(sends, 4.0) == 3
    # a request that fails after it was sent still counts as attempted
    assert window.attempted(sends, 10.0) == 4



def test_regenerated_tokens_are_not_new_output():
    clk = Clock()
    rec = Recorder(SIZES, {0: 4}, {0: 3}, chunk=16, clock=clk)
    toks = TimedTokens(0, rec)
    for t, tok in ((1.0, 7), (2.0, 8)):
        clk.t = t
        toks.append(tok)
    clk.t = 3.0
    del toks[1:]                       # rollback to one token
    assert rec.ntok[0] == 1
    clk.t = 4.0
    toks.append(8)                     # regenerated, same value
    assert rec.first[0] == [1.0, 2.0] and rec.regen_changed == 0
    clk.t = 5.0
    toks[1] = 9                        # regenerated differently
    assert rec.regen_changed == 1
    clk.t = 6.0
    toks.append(3)
    assert rec.first[0] == [1.0, 2.0, 6.0] and rec.completions == [6.0]
    assert list(toks) == [7, 9, 3]


def test_recorder_groups_passes_and_counts_flops():
    clk = Clock()
    rec = Recorder(SIZES, {0: 20, 1: 10}, {0: 4, 1: 4}, chunk=16, clock=clk)
    toks = {r: TimedTokens(r, rec) for r in (0, 1)}
    clk.t = 1.0
    rec.on_step("r0")                  # admission: chunk 0..15 of rid 0
    clk.t = 1.5
    rec.on_step("r1")                  # admission: whole prompt of rid 1
    clk.t = 1.6
    rec.on_sample(True)
    rec.on_sample(False)
    toks[1].append(5)
    clk.t = 2.0
    rec.on_step("prefill-r0")          # chunk 16..19 completes rid 0
    clk.t = 2.5
    rec.on_step("decode-r1")           # new kind: new pass
    clk.t = 2.6
    rec.on_sample(True)
    clk.t = 2.7
    rec.on_sample(False)
    p = rec.passes
    assert [(x.kind, x.batch) for x in p] == [("prefill", 1), ("prefill", 1),
                                              ("prefill", 1), ("decode", 1)]
    assert (p[0].t0, p[0].t1) == (1.0, 1.5)        # ended by the next fire
    assert (p[1].t0, p[1].t1) == (1.5, 1.6)        # ended by the sampler
    assert (p[3].t0, p[3].t1) == (2.5, 2.7)        # first sampler return
    assert [t for t, _ in rec.admitted] == [1.0, 1.5]
    assert p[0].flops == counters.chunk_flops(SIZES, 0, 16, False)
    assert p[1].flops == counters.chunk_flops(SIZES, 0, 10, True)
    assert p[2].flops == counters.chunk_flops(SIZES, 16, 4, True)
    assert p[3].flops == counters.decode_flops(SIZES, 10 + 1)


def test_fill_decides_when_the_window_may_open():
    rec = Recorder(SIZES, {0: 4, 1: 4, 2: 4}, {0: 3, 1: 3, 2: 3}, chunk=16,
                   clients=3, fill=2, clock=Clock())
    TimedTokens(0, rec).append(1)
    assert not rec.filled()
    TimedTokens(2, rec).append(1)
    assert rec.filled()
    assert Recorder(SIZES, {0: 4}, {0: 3}, chunk=16, clients=3).fill == 3


def test_window_closes_at_a_pass_boundary():
    clk = Clock()
    rec = Recorder(SIZES, {0: 4}, {0: 9}, chunk=16, clock=clk)
    rec.close = lambda now: now >= 10.0
    clk.t = 9.0
    rec.on_step("decode-r0")
    clk.t = 10.5
    with pytest.raises(window.WindowClosed):
        rec.on_step("decode-r0")
    assert not issubclass(window.WindowClosed, RuntimeError)
    assert rec.passes[0].t1 == 10.5



def test_window_profile_lists_passes_and_collector_pauses():
    from chipbench.harness import Run, window_profile
    clk = Clock()
    rec = Recorder(SIZES, {0: 4, 1: 4}, {0: 9, 1: 9}, chunk=16, clock=clk)
    for t0, t1 in ((1.0, 2.5), (2.5, 4.0), (4.0, 9.0)):
        clk.t = t0
        rec.on_step("decode-r0")
        rec.on_step("decode-r1")
        clk.t = t1
        rec.on_sample(False)
    run = Run(cell=None, sizes=SIZES, rec=rec, window_s=8.0, t_open=1.0,
              t_close=9.0, setup_s=1.0, clients=2, stage_layers=[1, 1],
              kv_pack_width=8,
              gc_pauses=[(3.0, 3.25, 2), (5.0, 5.001, 0), (6.0, 6.002, 0)])
    line = window_profile(run)
    assert "d2:1500 d2:1500 d2:5000;" in line
    assert "gen0 2 x, 3.0 ms, longest 2.0 ms" in line
    assert "gen2 1 x, 250.0 ms, longest 250.0 ms" in line
    run.gc_pauses = []
    assert window_profile(run).endswith("collector: none")
