"""Custom ops: Pallas kernels and their reference implementations.

The measured keep-or-kill policy (BASELINE.md "Pallas decision"): kernels
live here when profiling on the real chip justifies them; each ships with a
pure-JAX reference that doubles as spec and recompute-backward.
"""
