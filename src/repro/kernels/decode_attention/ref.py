"""Pure-jnp oracles for the decode-attention kernels (GQA, length-masked).

``decode_attention_ref`` is the single-pass softmax; ``decode_attention_
splitk_ref`` expresses the same math in the two-stage split-K decomposition
(per-chunk partial (m, l, acc) + log-sum-exp combine) so the Pallas split-K
kernel has a shape-faithful oracle and the benchmark can measure what the
decomposition itself buys on a given backend.

``decode_attention_paged_ref`` is the block-table oracle: KV lives in a
shared page pool (P, page_size, Hkv, D) and each sequence reads its pages
through a (B, n_blocks) table.  ``gather_pages`` is the layout adapter —
after the gather the math is exactly the contiguous reference, which is
what makes paged decoding token-exact with the striped cache.
``decode_attention_paged_splitk_ref`` composes the gather with the split-K
decomposition — the host-path expression of what ``ops.decode_attention``
dispatches for long paged caches (the ``kernels/decode_paged_4k`` bench
row times this at the ops-auto split).

``mixed_attention_ref`` is the chunked-prefill oracle: each sequence
carries Q new tokens at absolute positions ``cache_len + i`` and query i
attends causally to every cache position ``<= cache_len + i`` — the
q-chunk generalization of ``decode_attention_ref`` (Q=1 reduces to it
exactly).  Rows past a sequence's real suffix produce garbage the engine
discards; the kernel contract masks *keys* per query, never queries.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_attention_ref(
    q: jax.Array,          # (B, Hq, D) — one new token per sequence
    k_cache: jax.Array,    # (B, S, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,    # (B,) int32 valid prefix
    *,
    softmax_scale=None,
) -> jax.Array:
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, D).astype(q.dtype)


def decode_attention_splitk_ref(
    q: jax.Array,          # (B, Hq, D)
    k_cache: jax.Array,    # (B, S, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,    # (B,) int32
    *,
    k_splits: int = 4,
    softmax_scale=None,
) -> jax.Array:
    """Two-stage split-K softmax in pure lax: the KV axis is cut into
    ``k_splits`` chunks, each producing an unnormalized partial state, then
    merged with the standard max-rescaled combine."""
    B, S, Hkv, D = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    assert S % k_splits == 0
    ck = S // k_splits

    qg = q.reshape(B, Hkv, G, D)
    kb = k_cache.reshape(B, k_splits, ck, Hkv, D)
    vb = v_cache.reshape(B, k_splits, ck, Hkv, D)
    s = jnp.einsum("bhgd,bckhd->bchgk", qg, kb,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k_splits)[:, None] * ck + jnp.arange(ck)[None, :]
    valid = pos[None] < lengths[:, None, None]                    # (B, C, ck)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                       # (B, C, H, G)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bchgk,bckhd->bchgd", p.astype(v_cache.dtype), vb,
                     preferred_element_type=jnp.float32)
    m_star = jnp.max(m, axis=1)                                   # (B, H, G)
    alpha = jnp.exp(m - m_star[:, None])
    l_star = jnp.sum(l * alpha, axis=1)
    out = jnp.sum(acc * alpha[..., None], axis=1)
    out = out / jnp.maximum(l_star, 1e-30)[..., None]
    return out.reshape(B, Hq, D).astype(q.dtype)


def mixed_attention_ref(
    q: jax.Array,          # (B, Q, Hq, D) — Q new tokens per sequence
    k_cache: jax.Array,    # (B, S, Hkv, D)
    v_cache: jax.Array,
    cache_lens: jax.Array, # (B,) int32 tokens already cached BEFORE this chunk
    *,
    window=0,              # keys in (pos - window, pos] (0: all; may be traced)
    softmax_scale=None,
) -> jax.Array:
    """Chunked-prefill attention: query i of sequence b sits at absolute
    position ``cache_lens[b] + i`` and attends keys at positions
    ``<= cache_lens[b] + i`` (cached prefix + the chunk's earlier writes,
    which the caller has already scattered into the cache), and with a
    ``window`` only those after ``cache_lens[b] + i - window``."""
    B, S, Hkv, D = k_cache.shape
    Q, Hq = q.shape[1], q.shape[2]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    qg = q.reshape(B, Q, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    keypos = jnp.arange(S)
    qpos = cache_lens[:, None] + jnp.arange(Q)[None, :]          # (B, Q)
    valid = keypos[None, None, :] <= qpos[:, :, None]            # (B, Q, S)
    if not isinstance(window, int) or window > 0:
        valid = valid & (keypos[None, None, :] > qpos[:, :, None] - window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Q, Hq, D).astype(q.dtype)


def gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(P, ps, Hkv, D) pool + (B, nb) tables -> contiguous (B, nb*ps, Hkv, D).

    Logical position ``t`` of sequence ``b`` lives at row ``t % ps`` of page
    ``block_tables[b, t // ps]``; the gather restores logical order, so any
    contiguous-cache attention applies unchanged afterwards.
    """
    B, nb = block_tables.shape
    _, ps, Hkv, D = pages.shape
    return pages[block_tables].reshape(B, nb * ps, Hkv, D)


def decode_attention_paged_ref(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (P, page_size, Hkv, D) shared pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_blocks) int32 page ids
    lengths: jax.Array,        # (B,) int32 valid prefix
    *,
    softmax_scale=None,
) -> jax.Array:
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    return decode_attention_ref(q, k, v, lengths, softmax_scale=softmax_scale)


def decode_attention_paged_splitk_ref(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (P, page_size, Hkv, D) shared pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_blocks) int32 page ids
    lengths: jax.Array,        # (B,) int32 valid prefix
    *,
    k_splits: int = 4,
    softmax_scale=None,
) -> jax.Array:
    """Paged split-K oracle: the table gather composed with the two-stage
    split-K softmax — the host expression of the paged dispatch path at a
    given split (``ops.auto_paged_k_splits`` picks it from the table)."""
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    return decode_attention_splitk_ref(q, k, v, lengths, k_splits=k_splits,
                                       softmax_scale=softmax_scale)


def mixed_attention_paged_ref(
    q: jax.Array,              # (B, Q, Hq, D)
    k_pages: jax.Array,        # (P, page_size, Hkv, D) shared pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_blocks) int32 page ids
    cache_lens: jax.Array,     # (B,) int32 cached tokens before the chunk
    *,
    softmax_scale=None,
) -> jax.Array:
    k = gather_pages(k_pages, block_tables)
    v = gather_pages(v_pages, block_tables)
    return mixed_attention_ref(q, k, v, cache_lens, softmax_scale=softmax_scale)
