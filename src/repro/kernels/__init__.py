"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel package ships three files (the kernels/EXAMPLE.md contract):
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (auto interpret=True off-TPU)
  ref.py    — pure-jnp oracle used by the allclose test sweeps
"""
import jax


def interpret() -> bool:
    """Interpret mode for every Pallas kernel when the backend is not a
    TPU: the CPU test path.  The chip path never relies on it — it refuses
    to start off-TPU (``chip_smoke.py``)."""
    return jax.default_backend() != "tpu"
