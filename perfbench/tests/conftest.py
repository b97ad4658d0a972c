"""These tests belong to the benchmark and stay out of tier-1's count: run
them with ``pytest perfbench/tests``. They run on the CPU at tiny sizes."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
        " --xla_cpu_collective_call_terminate_timeout_seconds=600")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "0")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
