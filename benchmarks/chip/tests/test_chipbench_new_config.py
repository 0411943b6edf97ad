"""A configuration whose program is not the pre-LN dense decoder comes in by
adding files only.  In a copy of the benchmark, a configuration file and a
reference module for the registry's reduced ``moonshot-v1-16b-a3b`` (MoE
with gated experts and a float32 router, RMSNorm, RoPE, an untied head) go
beside the files already there, and `BENCHMARK.json` gains entries; every
file the benchmark had stays as it was.  The cell resolves, its weights
take the program's own tree and dtypes, the configuration file reaches the
reference with the source's keys under their own names, and the counts
come from its module."""
import dataclasses
import filecmp
import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from conftest import CHIP

from chipbench import counters, harness, spec, weights

CONFIG = "moonshot-v1-16b-a3b-tiny"

# the reference module of the new architecture: its counts, and a stand-in
# for the forward of `correct` that reads the source's own epsilon
REFERENCE = '''
"""Counts of a decoder with RMSNorm, RoPE and a routed MoE MLP: every token
meets `experts_per_token` gated experts of width `d_ff` and the router."""


def _widths(arch):
    hd = arch["head_dim"]
    return arch["d_model"], arch["num_heads"] * hd, arch["num_kv_heads"] * hd


def _layer_params_per_token(arch):
    d, qd, kvd = _widths(arch)
    experts = arch["experts_per_token"] * 3 * d * arch["d_ff"]
    return d * qd + 2 * d * kvd + qd * d + d * arch["num_experts"] + experts


def matmul_flops_per_token(arch):
    return 2 * arch["num_layers"] * _layer_params_per_token(arch)


def attention_flops_per_key(arch):
    return 4 * arch["num_layers"] * _widths(arch)[1]


def head_flops(arch):
    return 2 * arch["d_model"] * arch["vocab_size"]


def stage_decode_cost(arch, lo, hi, keys, first, last):
    d, qd, kvd = _widths(arch)
    n, batch = hi - lo, len(keys)
    met = min(arch["num_experts"], batch * arch["experts_per_token"])
    weights = 2 * (d * qd + 2 * d * kvd + qd * d + 3 * met * d * arch["d_ff"]
                   + 2 * d) + 4 * d * arch["num_experts"]
    nbytes = n * weights + 2 * n * 2 * kvd * sum(keys)
    flops = 2 * n * _layer_params_per_token(arch) * batch \\
        + 4 * n * qd * sum(keys)
    if first:
        nbytes += 2 * batch * d
    if last:
        nbytes += 2 * (arch["vocab_size"] * d + d)
        flops += head_flops(arch) * batch
    return flops, nbytes


def forward_stats(params, arch, config, seqs, targets, *, pad_to,
                  control=False, device=None):
    return [{"eps": float(config["rms_norm_eps"])} for _ in seqs]
'''


def _arch() -> dict:
    from repro.configs.registry import get_arch
    cfg = get_arch("moonshot-v1-16b-a3b").reduced()
    arch = {k: v for k, v in dataclasses.asdict(cfg).items()
            if k not in ("name", "source")}
    arch["full_attn_layers"] = list(arch["full_attn_layers"])
    return arch


def _config_file(arch: dict) -> dict:
    return {
        "source": "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/"
                  "main/config.json",
        "model_type": "deepseek_v3", "rms_norm_eps": 1e-6,
        "reduced": [], "changed_from": {}, "sizes": {},
        "arch": arch, "serving": {"n_workers": 2},
        "reference": "moe_rms_decoder",
        "init": {"embed": 0.02, "lm_head": 0.02, "wq": 0.02, "wk": 0.02,
                 "wv": 0.02, "wo": 0.01, "router": 0.02, "w_gate": 0.02,
                 "w_up": 0.02, "w_down": 0.01, "scale": 0.1},
        "limits": {"widest_gap": 0.1, "logit_error": 0.1},
    }


@pytest.fixture
def checkout(tmp_path, monkeypatch, bench):
    """A copy of the benchmark with the new configuration's files added,
    which `spec` reads in place of the repository's."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    arch = _arch()
    (chip / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(_config_file(arch)))
    (chip / "configs" / "moe_rms_decoder.py").write_text(REFERENCE)
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": CONFIG, "source": "x",
                           "file": f"benchmarks/chip/configs/{CONFIG}.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": f"{CONFIG}.chat", "config": CONFIG,
                             "traffic": "chat", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    monkeypatch.setattr(spec, "HERE", chip)
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "BENCHMARK_JSON", root / "BENCHMARK.json")
    return root, arch


def test_files_that_were_there_stay_as_they_were(checkout, bench):
    root, _ = checkout
    chip = root / "benchmarks" / "chip"
    added = {f"configs/{CONFIG}.json", "configs/moe_rms_decoder.py"}
    for path in chip.rglob("*"):
        rel = path.relative_to(chip).as_posix()
        if path.is_file() and rel not in added:
            assert filecmp.cmp(path, CHIP + "/" + rel, shallow=False), rel
    # BENCHMARK.json gains one configuration and one workload, at the end
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert new[key].pop()["name"].startswith(CONFIG)
    assert new == bench


def test_a_moe_rmsnorm_rope_configuration_comes_in_by_files_alone(checkout):
    from repro.configs.base import ArchConfig
    from repro.models import build_model
    _, arch = checkout
    cell = spec.load_cell(f"{CONFIG}.chat")
    assert cell.config["arch"] == arch and cell.chips == 1
    assert set(cell.end_to_end) == {"tokens_per_s", "setup_s"}

    # the reference reads the source's own keys under their own names
    ref = spec.reference(cell.config)
    assert "layer_norm_epsilon" not in cell.config
    assert ref.forward_stats(None, arch, cell.config, [[1]], [[[1]]],
                             pad_to=1) == [{"eps": 1e-6}]

    # the weights take the program's tree, each leaf in its own dtype
    model = build_model(ArchConfig(name=CONFIG, **arch))
    want = weights.declared(model)
    assert want["layers/moe/router"][1] == jnp.float32
    assert want["layers/moe/w_gate"][1] == jnp.bfloat16
    assert "pos_table" not in want and "lm_head" in want
    assert not any(p.endswith("/bias") for p in want)
    params = weights.make_params(model, cell.config["init"], 7)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {"/".join(k.key for k in path): (leaf.shape, leaf.dtype)
           for path, leaf in flat}
    assert got == want

    # the counts come from the configuration's module: 2 layers, 64 wide,
    # 4 heads and 2 KV heads of 16, 2 of 4 experts of 32, vocab 256
    s = counters.Sizes.from_reference(ref, arch)
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 64 * 4 + 2 * 3 * 64 * 32
    assert s.matmul_flops_per_token == 2 * 2 * per_layer == 99_328
    assert s.attention_flops_per_key == 4 * 2 * 4 * 16
    assert s.head_flops == 2 * 64 * 256
    assert counters.decode_flops(s, 10) == 99_328 + 10 * 512 + 32_768
    f0, _ = ref.stage_decode_cost(arch, 0, 1, [10, 20], True, False)
    f1, b1 = ref.stage_decode_cost(arch, 1, 2, [10, 20], False, True)
    assert f0 + f1 == counters.decode_flops(s, 10) + \
        counters.decode_flops(s, 20)
    assert b1 > 2 * 256 * 64
    # and the harness builds the cell's model, stream and counts from them
    served = harness.Served(cell, 7, jax.devices()[:1])
    assert served.sizes == s
