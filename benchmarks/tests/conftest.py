"""The benchmark's own tests run on the CPU, on four virtual devices, and
outside tier-1: ``python -m pytest benchmarks/tests``."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))  # benchmarks/
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # the checkout
