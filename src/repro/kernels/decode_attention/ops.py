"""Public jit'd wrapper for the flash-decoding kernels.

``decode_attention`` dispatches between the single-stage kernel (short
caches: grid is already wide enough at B·Hkv) and the two-stage split-K
kernel (long caches: B·Hkv·K grid cells walk KV chunks concurrently).
``k_splits=0`` picks the split automatically from the cache length.

With ``page_table`` the KV operands are a shared page pool
(P, page_size, Hkv, D) read through (B, n_blocks) block tables, and the
same short/long split applies over the *logical* cache length
n_blocks·page_size — the paged split-K kernel keeps the flash-decoding
grid parallelism while gathering pages inside the grid.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret as _interpret
from repro.kernels.decode_attention.kernel import (
    decode_attention_paged,
    decode_attention_paged_splitk,
    decode_attention_pallas,
    decode_attention_splitk,
    mixed_attention_paged,
    mixed_attention_pallas,
)

# caches at/above this length get the split-K treatment by default
SPLITK_MIN_S = 2048
SPLITK_MAX = 8
# each split chunk should stream at least this many tokens: thinner chunks
# spend their grid cells on softmax-state bookkeeping instead of KV reads
# (the paged 4k bench regressed to 0.88x vs contiguous before this floor)
SPLITK_MIN_CHUNK = 256


def auto_k_splits(S: int, block_k: int = 512) -> int:
    """Largest split ≤ SPLITK_MAX whose chunk is a whole number of blocks."""
    if S < SPLITK_MIN_S:
        return 1
    for k in range(min(SPLITK_MAX, S // block_k), 1, -1):
        if S % k == 0 and (S // k) % min(block_k, S // k) == 0:
            return k
    return 1


def auto_paged_k_splits(n_blocks: int, page_size: int) -> int:
    """Largest split ≤ SPLITK_MAX that divides the block table evenly,
    covers ≥ SPLITK_MIN_S logical tokens, and keeps every chunk streaming
    ≥ SPLITK_MIN_CHUNK tokens (page-block sizing: a chunk is a whole
    number of pages, so small pages need more of them per chunk)."""
    if n_blocks * page_size < SPLITK_MIN_S:
        return 1
    for k in range(min(SPLITK_MAX, n_blocks), 1, -1):
        if n_blocks % k == 0 and (n_blocks // k) * page_size >= SPLITK_MIN_CHUNK:
            return k
    return 1


@partial(jax.jit, static_argnames=("block_k", "k_splits"))
def decode_attention(q, k_cache, v_cache, lengths, *, page_table=None,
                     block_k=512, k_splits=0):
    """One-token GQA attention with per-seq lengths.

    Contiguous: ``k_cache`` is (B, S, Hkv, D).  Paged (``page_table`` is a
    (B, n_blocks) int32 array): ``k_cache`` is the (P, page_size, Hkv, D)
    pool and tiles are gathered through the table inside the kernel grid.
    """
    if page_table is not None:
        nb = page_table.shape[1]
        ps = k_cache.shape[1]
        if k_splits == 0:
            k_splits = auto_paged_k_splits(nb, ps)
        if k_splits > 1:
            return decode_attention_paged_splitk(
                q, k_cache, v_cache, page_table, lengths,
                k_splits=k_splits, interpret=_interpret(),
            )
        return decode_attention_paged(
            q, k_cache, v_cache, page_table, lengths, interpret=_interpret()
        )
    S = k_cache.shape[1]
    if k_splits == 0:
        k_splits = auto_k_splits(S, block_k)
    if k_splits > 1:
        return decode_attention_splitk(
            q, k_cache, v_cache, lengths,
            k_splits=k_splits, block_k=block_k, interpret=_interpret(),
        )
    return decode_attention_pallas(
        q, k_cache, v_cache, lengths, block_k=block_k, interpret=_interpret()
    )


@partial(jax.jit, static_argnames=("block_k",))
def mixed_attention(q, k_cache, v_cache, cache_lens, *, page_table=None,
                    block_k=512):
    """Q-chunk GQA attention for the mixed (prefill+decode) engine step.

    ``q`` is (B, Q, Hq, D): query i of sequence b sits at absolute position
    ``cache_lens[b] + i`` and attends keys at or before it — the chunk's
    own KV must already be scattered into the cache/pool.  Contiguous:
    ``k_cache`` is (B, S, Hkv, D); paged (``page_table`` a (B, n_blocks)
    int32 array): ``k_cache`` is the (P, page_size, Hkv, D) pool and tiles
    gather through the table inside the kernel grid.  Q = 1 is exactly
    flash decoding with ``lengths = cache_lens + 1``.
    """
    if page_table is not None:
        return mixed_attention_paged(
            q, k_cache, v_cache, page_table, cache_lens,
            interpret=_interpret(),
        )
    return mixed_attention_pallas(
        q, k_cache, v_cache, cache_lens, block_k=block_k,
        interpret=_interpret(),
    )
