#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip, in
one process:

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3,... [--control-seeds 1,2,3]

For every seed, one whole run of the cell (warm-up, window, comparison),
printing the program's compared numbers; for each control seed also the
float8 control read on the same prompts and served tokens (see
chipbench/correct.py), put through the same checks and limits as the
program, with its verdict.  The benchmark's own runs never run the control.
One JSON line per seed on standard output.  Exits 1 if the control of any
seed comes out correct: then the limits do not separate the two.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    from chipbench import correct, harness, spec

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.load_cell(args.workload)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    control_passed = False
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t_process=t0)
        out.pop("_run")
        c, items = out.pop("_compared")
        line = {"seed": seed, "correct": out["correct"],
                "checks": out["checks"], "metrics": out["metrics"]}
        if seed in controls:
            arch = dict(c.arch, layer_norm_epsilon=c.cell.config[
                "layer_norm_epsilon"])
            ctl = correct.control(
                spec.reference(c.cell.config), c.params, arch, items,
                int(c.arch["max_seq_len"]), c.devices[0])
            ctl["checks"] = correct.control_checks(ctl, cell.config["limits"])
            ctl["correct"] = correct.passed(ctl["checks"])
            control_passed |= ctl["correct"]
            line["control"] = ctl
        del c, items
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    if control_passed:
        print("the float8 control came out correct under the limits",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
