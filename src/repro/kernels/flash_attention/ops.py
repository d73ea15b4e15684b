"""Public jit'd wrapper for the flash-attention kernel.

Accepts GQA-form inputs directly: q at Hq heads, k/v at Hkv heads with
Hkv | Hq.  The forward kernel maps query groups onto shared KV tiles so the
expansion never materializes; only the *backward* recompute (which reuses
the pure-lax chunked oracle's VJP — flash-style recomputation, no S×S
residuals stored) widens KV, and jax.vjp folds the group gradients back to
Hkv width automatically.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.kernels import interpret as _interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@lru_cache(maxsize=None)
def _make(causal: bool, window: int, block_q: int, block_k: int):
    from repro.models import layers

    def ref(q, k, v):
        G = q.shape[2] // k.shape[2]
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        return layers.chunked_attention(
            q, k, v, causal=causal, window=window,
            q_chunk=block_q, k_chunk=block_k,
        )

    @jax.custom_vjp
    def fa(q, k, v):
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_k=block_k, interpret=_interpret(),
        )

    def fwd(q, k, v):
        return fa(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)

    fa.defvjp(fwd, bwd)
    return jax.jit(fa)


def flash_attention(q, k, v, *, causal=True, window=0, block_q=512, block_k=512):
    """GQA/MHA flash attention. q: (B,S,Hq,D); k/v: (B,S,Hkv,D), Hkv | Hq."""
    S = q.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, k.shape[1])
    return _make(causal, window, block_q, block_k)(q, k, v)
