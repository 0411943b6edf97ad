import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(workload: str = "gpt2-1.5b.chat", **traffic):
    """A cell of BENCHMARK.json with its configuration cut to a CPU-sized
    model and a small, fixed-size closed loop (the same harness code)."""
    from chipbench import spec
    cell = spec.load_cell(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config["arch"].update(num_layers=2, d_model=64, num_heads=4,
                               num_kv_heads=4, head_dim=16, d_ff=128,
                               vocab_size=256, max_seq_len=64,
                               prefill_chunk_tokens=16)
    cell.config["init"].update(wo=0.01, w_down=0.01)
    mix = {"clients": 2, "requests": 400, "max_total": 64, "size_seed": 0,
           "prompt": {"dist": "uniform", "min": 20, "max": 20},
           "output": {"dist": "uniform", "min": 6, "max": 6}}
    mix.update(traffic)
    cell.traffic = mix
    return cell


@pytest.fixture
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
