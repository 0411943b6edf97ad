"""The traffic generator: the same seed gives the same stream, every seed
the same sizes, and every length stays inside the configuration's
context."""
import numpy as np
import pytest

from chipbench import spec, traffic

MIXES = ["chat", "docqa"]


def _mix(name):
    return spec.load_json(spec.HERE / "traffic" / f"{name}.json", "traffic")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    mix = _mix(name)
    a = traffic.make_stream(mix, 50257, 2**31 + 11)
    b = traffic.make_stream(mix, 50257, 2**31 + 11)
    assert len(a) == len(b) == mix["requests"]
    for (pa, oa), (pb, ob) in zip(a, b):
        assert oa == ob and np.array_equal(pa, pb) and pa.dtype == np.int32


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_same_sizes_other_tokens(name):
    mix = _mix(name)
    a = traffic.make_stream(mix, 50257, 1)
    b = traffic.make_stream(mix, 50257, 2**33 + 1)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert any(not np.array_equal(pa, pb) for (pa, _), (pb, _) in zip(a, b))


@pytest.mark.parametrize("name,config", [
    ("chat", "gpt2-1.5b"), ("docqa", "opt-66b-s4")])
def test_lengths_inside_the_context(name, config):
    mix = _mix(name)
    arch = spec.load_json(spec.HERE / "configs" / f"{config}.json",
                          "configuration")["arch"]
    assert mix["max_total"] <= arch["max_seq_len"]
    for p, o in traffic.make_stream(mix, arch["vocab_size"], 7):
        assert mix["prompt"]["min"] <= len(p) <= mix["prompt"]["max"]
        assert 1 <= o <= mix["output"]["max"]
        assert len(p) + o <= mix["max_total"]
        assert p.min() >= 0 and p.max() < arch["vocab_size"]


def test_cells_name_their_mix(bench):
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"


def test_lognormal_median_and_clip():
    rng = np.random.default_rng(0)
    x = traffic.draw_lengths({"dist": "lognormal", "median": 256,
                              "sigma": 0.8, "min": 32, "max": 768},
                             20000, rng)
    assert 32 <= x.min() and x.max() <= 768
    assert abs(np.median(x) - 256) < 10
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 3, rng)

