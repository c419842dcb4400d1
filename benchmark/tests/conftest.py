"""The benchmark's own tests run on the CPU, with four virtual devices for
the data-parallel rehearsal (set before jax is imported)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
