#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark (cells in the checkout's
BENCHMARK.json):

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with a TPU.  One process, one
run: weights from the seed, a warm-up that replays the run's own request
stream, then the measured window of ``--seconds``, then the comparison with
the plain reference.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and ``checks`` last); the last lines of
standard error are the numbers compared, each beside its limit.

Without a TPU, or with fewer chips than the cell needs, it exits 1 and
prints no result.  See benchmarks/chip/README.md.
"""
import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def process_start() -> float:
    """The process's start on the `time.perf_counter` clock (Linux: from
    /proc), so set-up counts interpreter start-up too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


def main(argv=None) -> int:
    t_process = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch.compile_cache import use_compile_cache
        from chipbench import harness, spec

        cell = spec.load_cell(args.workload)
        use_compile_cache()
        # cache every program, the ones that compile in under a second too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_process=t_process)
    except Exception:
        traceback.print_exc()
        sys.stderr.flush()
        return 1
    out.pop("_run")
    out.pop("_compared")
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
