"""The benchmark's own tests (``python3 -m pytest bench/tests``), on the
CPU: four host devices stand in for a four-chip host, and nothing here
loads the TPU runtime."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
