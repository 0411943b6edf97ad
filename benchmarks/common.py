"""Shared benchmark helpers: CSV emission (+ JSON artifact capture) and
timing.

Every `emit`/`emit_metric` row is also recorded in memory; when the
``BENCH_JSON_DIR`` environment variable is set, the rows are written (at
interpreter exit, or per-module via `flush_json`) to
``$BENCH_JSON_DIR/<script-stem>.json`` so CI can upload the per-PR perf
trajectory as a workflow artifact without re-running anything.

The JSON artifact is the ``repro.bench/v1`` schema::

    {"schema": "repro.bench/v1",
     "rows": [{"name": ..., "us_per_call": ..., "derived": ...},
              {"name": ..., "value": <float>, "note": ...}, ...],
     "telemetry": <repro.telemetry/v1 snapshot or null>}

`emit_metric` rows carry a NUMERIC ``value`` — these are what
``tools/check_bench_trend.py`` compares against the committed baseline
(``benchmarks/baselines/BENCH_baseline.json``).  When ``BENCH_JSON_DIR``
is set, an ambient telemetry registry is installed at import so every
`ServingEngine` run in the module aggregates into one snapshot, embedded
in the artifact at flush time.
"""
from __future__ import annotations

import atexit
import json
import os
import sys
import time

from repro.core import telemetry
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()

_ROWS: list = []
_FLUSHED: set = set()   # stems written this process (double-flush guard)

if os.environ.get("BENCH_JSON_DIR") and telemetry.current() is None:
    telemetry.install(telemetry.Telemetry())


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")
    _ROWS.append({"name": name, "us_per_call": us_per_call, "derived": derived})


def emit_metric(name: str, value: float, note: str = "") -> None:
    """A numeric headline metric (trend-gated by check_bench_trend.py)."""
    print(f"{name},{float(value):.6g},{note}")
    _ROWS.append({"name": name, "value": float(value), "note": note})


def timeit(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(*args)
    return (time.perf_counter() - t0) / iters * 1e6   # µs


def flush_json(name: str) -> None:
    """Write (and clear) the rows emitted so far to ``$BENCH_JSON_DIR/
    <name>.json``, embedding the ambient telemetry snapshot (a fresh
    registry is installed afterwards so modules don't bleed into each
    other).  The `benchmarks.run` harness calls this after each module so
    the full-suite job still produces per-module artifacts; a
    directly-invoked module relies on the atexit hook below instead."""
    out_dir = os.environ.get("BENCH_JSON_DIR")
    if not out_dir:
        _ROWS.clear()
        return
    if not _ROWS:
        return
    if name in _FLUSHED:
        # a second flush would silently overwrite the artifact (rows and
        # telemetry already cleared), corrupting the CI trend input —
        # error out rather than lose the first flush's numbers
        raise RuntimeError(
            f"flush_json({name!r}): artifact already written this process; "
            "a module must flush each stem at most once")
    _FLUSHED.add(name)
    tele = telemetry.current()
    doc = {
        "schema": "repro.bench/v1",
        "rows": list(_ROWS),
        "telemetry": tele.snapshot() if tele is not None else None,
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{name}.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    _ROWS.clear()
    if tele is not None:
        telemetry.install(telemetry.Telemetry())


def _write_json_rows() -> None:
    stem = os.path.splitext(os.path.basename(sys.argv[0]))[0] or "bench"
    flush_json(stem)


atexit.register(_write_json_rows)
