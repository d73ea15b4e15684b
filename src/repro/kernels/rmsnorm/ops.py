"""Public jit'd wrapper for the fused RMSNorm kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret as _interpret
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas


@partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, weight, residual=None, *, eps: float = 1e-6, block_rows: int = 256):
    """Fused (residual-add) RMSNorm over rows. x: (M, d)."""
    return rmsnorm_pallas(
        x, weight, residual, eps=eps, block_rows=block_rows, interpret=_interpret()
    )
