"""Public jit'd wrapper for the Mamba-2 SSD kernel (differentiable via the
chunked-oracle VJP)."""
from __future__ import annotations

from functools import lru_cache

import jax

from repro.kernels import interpret as _interpret
from repro.kernels.ssd_scan.kernel import ssd_pallas


@lru_cache(maxsize=None)
def _make(chunk: int):
    from repro.models.mamba2 import ssd_chunked

    def ref(x, dt, A, Bm, Cm, state0):
        return ssd_chunked(x, dt, A, Bm, Cm, state0, chunk=chunk)

    @jax.custom_vjp
    def f(x, dt, A, Bm, Cm, state0):
        return ssd_pallas(x, dt, A, Bm, Cm, state0, chunk=chunk,
                          interpret=_interpret())

    def fwd(*args):
        return f(*args), args

    def bwd(res, g):
        _, vjp = jax.vjp(ref, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def ssd(x, dt, A, Bm, Cm, state0, *, chunk: int = 128):
    """Chunked Mamba-2 SSD scan. Returns (y, final_state)."""
    return _make(min(chunk, x.shape[1]))(x, dt, A, Bm, Cm, state0)
