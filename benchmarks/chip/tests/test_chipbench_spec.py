"""The harness finds every configuration, traffic mix and metric that
BENCHMARK.json names, by name; an unknown name is an error; configuration
files state their published widths; importing the harness touches no
accelerator; and BENCHMARK.json keeps to the benchmark's schema."""
import copy
import json
import os
import re
import subprocess
import sys

import pytest

from conftest import CHIP, ROOT

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
          "vocab_size")


def test_every_named_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], bench)
        assert cell.chips == w["chips"]
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cell.readers[m])


@pytest.mark.parametrize("field,value", [
    ("config", "no-such-config"), ("traffic", "no-such-mix")])
def test_unknown_config_or_traffic_is_an_error(bench, field, value):
    b = copy.deepcopy(bench)
    b["workloads"][0][field] = value
    with pytest.raises(spec.SpecError):
        spec.load_cell(b["workloads"][0]["name"], b)


def test_unknown_metric_or_workload_is_an_error(bench):
    b = copy.deepcopy(bench)
    b["per_layer"].append(dict(b["per_layer"][0], name="no.such_metric"))
    with pytest.raises(spec.SpecError):
        spec.load_cell(b["workloads"][0]["name"], b)
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", bench)


@pytest.mark.parametrize("name", ["gpt2-1.5b", "opt-66b-s4"])
def test_configuration_states_its_published_widths(bench, name):
    entry = next(c for c in bench["configs"] if c["name"] == name)
    cfg = spec.load_json(spec.ROOT / entry["file"], "configuration")
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == list(cfg["changed_from"])
    published = {k: v for k, v in cfg.items() if not isinstance(v, (dict,
                                                                    list))}
    published.update(cfg["changed_from"])
    for key, expr in cfg["sizes"].items():
        as_run = eval(expr, {}, {k: v for k, v in cfg.items()
                                 if isinstance(v, (int, float))})
        assert cfg["arch"][key] == as_run, key
        src = eval(expr, {}, {k: v for k, v in published.items()
                              if isinstance(v, (int, float))})
        if key in WIDTHS:                # a width is never cut
            assert as_run == src, key
    for key in cfg["reduced"]:
        assert cfg[key] != cfg["changed_from"][key]
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"


def test_gpt2_and_opt_widths_are_the_published_ones(bench):
    gpt2 = spec.load_cell("gpt2-1.5b.chat", bench).config["arch"]
    assert (gpt2["num_layers"], gpt2["d_model"], gpt2["num_heads"],
            gpt2["head_dim"], gpt2["d_ff"], gpt2["vocab_size"],
            gpt2["max_seq_len"]) == (48, 1600, 25, 64, 6400, 50257, 1024)
    opt = spec.load_json(spec.ROOT / "benchmarks/chip/configs/opt-66b-s4.json",
                         "configuration")["arch"]
    assert (opt["d_model"], opt["num_heads"], opt["head_dim"], opt["d_ff"],
            opt["vocab_size"], opt["max_seq_len"], opt["num_layers"]) == \
        (9216, 72, 128, 36864, 50272, 2048, 4)


def test_benchmark_json_schema(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][1].startswith("benchmarks/chip/")
    assert 1 <= bench["run_seconds"] <= 51
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/chip/")
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and "\n" not in m["layer"]
        # every cell the metric lists reports the metric it moves
        moved = next(e for e in bench["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", [x["name"] for x in bench["workloads"]]):
            assert "workloads" not in moved or w in moved["workloads"], \
                (m["name"], w)
    assert len(json.dumps(bench)) < 64 * 1024


def test_importing_the_harness_touches_no_backend():
    code = ("import sys; sys.path[:0] = [%r, %r]\n"
            "import chipbench.harness, chipbench.tracefile, chipbench.correct\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            % (CHIP, os.path.join(ROOT, "src")))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "gpt2-1.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
