"""Public jit'd wrapper for the chunked WKV6 kernel (differentiable via the
chunked-oracle VJP)."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.kernels import interpret as _interpret
from repro.kernels.rwkv6_scan.kernel import wkv6_pallas


@lru_cache(maxsize=None)
def _make(chunk: int):
    from repro.models.rwkv6 import wkv_chunked

    def ref(r, k, v, logw, u, state0):
        y, st = wkv_chunked(r, k, v, logw, u, state0, chunk=chunk)
        return y.astype(jnp.float32), st

    @jax.custom_vjp
    def f(r, k, v, logw, u, state0):
        return wkv6_pallas(r, k, v, logw, u, state0, chunk=chunk,
                           interpret=_interpret())

    def fwd(*args):
        return f(*args), args

    def bwd(res, g):
        _, vjp = jax.vjp(ref, *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return jax.jit(f)


def wkv6(r, k, v, logw, u, state0, *, chunk: int = 64):
    """Chunked RWKV-6 scan. r/k/v/logw: (B,S,H,N); returns (y, final_state)."""
    return _make(min(chunk, r.shape[1]))(r, k, v, logw, u, state0)
