"""On-chip serving benchmark: harness, traffic generator, window arithmetic,
counters, trace reduction and the correctness comparison.

Nothing here touches an accelerator at import time; `harness.run_cell` is
the only code that reaches the device, and `run.py` refuses to call it
without a TPU.
"""
