"""The benchmark's own tests: run with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are outside tier-1 (``tests/``), hold JAX to the CPU with four host
devices, and never describe or touch a TPU."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=4").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
