import os
import sys

# Tests run on the single real CPU device (the 512-device flag is dry-run-only).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ... and never read or write a persistent compilation cache, whatever the
# environment or an imported entry point (benchmarks/common.py) places.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
