"""What every architecture's random weights share: a key from ``--seed``.

The benchmark makes the weights itself, on the device, in one jitted call
per architecture (``archs/<model_type>.py`` ``make_weights``), in the
dtype they are served in.  The program under test is handed them, and the
plain reference reads the same arrays, so the reference takes nothing the
program has made.  ``run.py`` checks the tree's layout against the
program's before it hands the weights over.

Scales: embedding N(0, 0.02) (the configuration's ``initializer_range``),
matmul weights N(0, 1/fan_in), norm weights 1.
"""
from __future__ import annotations

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all of its bits kept (a
    plain ``jax.random.key`` keeps only the low 32 without x64)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
