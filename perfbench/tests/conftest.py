"""The benchmark's own tests run on the CPU: four forced host devices
stand in for the four-chip mesh, at sizes a test run can hold.

    python -m pytest perfbench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("REPRO_PLAN_CACHE", os.path.join(ROOT, ".plan_cache"))
src = os.path.join(ROOT, "src")
if src not in sys.path:
    sys.path.insert(0, src)
