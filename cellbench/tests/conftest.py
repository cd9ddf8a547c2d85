"""The benchmark's own tests: run by hand with ``pytest cellbench/tests``
(tier-1 collects ``tests/`` only). Everything here runs on the CPU backend
at the tiny rehearsal size."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
