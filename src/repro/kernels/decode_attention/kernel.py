"""Flash-decoding Pallas TPU kernels: a few query tokens vs a long KV cache.

Decode attention is HBM-bandwidth-bound (the whole cache is read once per
token), so the kernel's job is to stream KV through VMEM in large tiles
while keeping the online-softmax state in VMEM scratch.  Tiles past a
sequence's length are skipped entirely with @pl.when — for a 32k-token
budget cache holding 2k tokens that is a 16× read saving over the masked
dense einsum (the lax baseline).

One kernel body serves every entry point.  Its q operand is a q-chunk
(B, Q, Hq, D) regrouped to (B, Hkv, Q·G, D): row r of KV head h is query
``r // G`` of the chunk, group member ``r % G``, at absolute position
``cache_lens[b] + r // G``, allowed keys ``<= cache_lens[b] + r // G``.
Decode is the Q = 1 case with ``cache_lens = lengths - 1``; the mixed-batch
engine step (``mixed_attention_*``) fuses decode rows and prefill chunks
into the same call.

Grid: (B, K, tiles per split).  Each step DMAs one (bk, Hkv, D) KV tile —
all KV heads of bk positions, the cache's own (B, S, Hkv, D) layout, so the
block's last two dims cover the whole (Hkv, D) extent as Mosaic requires —
and walks the Hkv heads in-kernel with a (Q·G, D)×(D, bk) MXU matmul each.
The per-sequence lengths (and the paged block tables) ride in as
*scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``) so they live
in SMEM, and the paged index_map resolves ``tables[b, j]`` before the tile
DMA issues — the KV gather happens inside the grid, not as a materialized
(B, S, Hkv, D) copy in HBM.

* single-stage (K = 1): each sequence walks its KV tiles sequentially and
  normalizes in the last step.
* split-K (K > 1, flash-decoding, Dao et al.): the cache is cut into K
  chunks, each chunk's grid cells emit an *unnormalized* partial state
  (m, l, acc), and ``_combine`` merges the K partials with the standard
  log-sum-exp rescaling — long caches at small B then spread over B·K
  grid cells.

Paged variants read KV from a shared page pool (P, page_size, Hkv, D)
through a (B, n_blocks) block table; one grid step streams one page.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_kernel(*refs, n_prefetch: int, bk: int, nkc: int, Q: int, G: int,
                   scale: float, split: bool):
    """Online softmax over one (bk, Hkv, D) KV tile for every KV head.

    refs: scalar prefetch ([tables,] cache_lens), q (Hkv, QG, D), k and v
    (bk, Hkv, D) tiles, outputs (normalized (Hkv, QG, D), or the split-K
    partials m, l (Hkv, QG, 1) and acc (Hkv, QG, D)), then scratch m, l,
    acc of the same shapes as the partials.
    """
    len_ref = refs[n_prefetch - 1]
    q_ref, k_ref, v_ref = refs[n_prefetch:n_prefetch + 3]
    outs = refs[n_prefetch + 3:-3]
    m_ref, l_ref, acc_ref = refs[-3:]
    b, kc, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    clen = len_ref[b]
    start = (kc * nkc + kj) * bk

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the widest row sees clen + Q keys; tiles wholly past that are skipped
    @pl.when(start < clen + Q)
    def _compute():
        pos = start - clen + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (Q * G, 1), 0)
        # key at offset pos from the cache frontier is visible to row r iff
        # pos <= r // G, i.e. G·pos <= r (no vector integer division)
        visible = G * pos <= row                            # (QG, bk)
        for h in range(q_ref.shape[0]):
            q = q_ref[h].astype(jnp.float32)                # (QG, D)
            k = k_ref[:, h, :].astype(jnp.float32)          # (bk, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                       # (QG, bk)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[h]                               # (QG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[h] = m_new
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[:, h, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                               # (QG, D)
            acc_ref[h] = acc_ref[h] * corr + pv

    @pl.when(kj == nkc - 1)
    def _finalize():
        if split:
            # chunks entirely past the frontier emit the identity state
            # (m=-inf, l=0, acc=0) — the combine's rescale zeroes them.
            m_out, l_out, acc_out = outs
            m_out[...] = m_ref[...]
            l_out[...] = l_ref[...]
            acc_out[...] = acc_ref[...]
        else:
            (o_ref,) = outs
            o_ref[...] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            ).astype(o_ref.dtype)


def _combine(m, l, acc, dtype):
    """Merge K split-K partial states (axis 1) by log-sum-exp rescaling."""
    alpha = jnp.exp(m - jnp.max(m, axis=1, keepdims=True))  # (B, K, Hkv, QG, 1)
    out = jnp.sum(acc * alpha, axis=1)
    return (out / jnp.maximum(jnp.sum(l * alpha, axis=1), 1e-30)).astype(dtype)


def _regroup_q_chunk(q: jax.Array, Hkv: int) -> jax.Array:
    """(B, Q, Hq, D) -> (B, Hkv, Q·G, D): KV-head-major rows so one grid
    cell serves the whole head-group of every chunk query.  Row r of a
    (b, h) cell is query ``r // G``, group member ``r % G``."""
    B, Q, Hq, D = q.shape
    G = Hq // Hkv
    return (q.reshape(B, Q, Hkv, G, D)
             .transpose(0, 2, 1, 3, 4)
             .reshape(B, Hkv, Q * G, D))


def _ungroup_q_chunk(out: jax.Array, Q: int, Hq: int) -> jax.Array:
    B, Hkv, QG, D = out.shape
    G = QG // Q
    return (out.reshape(B, Hkv, Q, G, D)
               .transpose(0, 2, 1, 3, 4)
               .reshape(B, Q, Hq, D))


def _attend(q, k, v, cache_lens, tables, *, bk: int, k_splits: int,
            softmax_scale, interpret: bool) -> jax.Array:
    """q (B, Q, Hq, D) against contiguous k/v (B, S, Hkv, D) (``tables`` is
    None) or a page pool (P, ps, Hkv, D) read through ``tables`` (B, nb)."""
    B, Q, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    QG = Q * G
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    n_tiles = tables.shape[1] if tables is not None else k.shape[1] // bk
    assert n_tiles % k_splits == 0, (n_tiles, k_splits)
    nkc = n_tiles // k_splits                   # tiles per split chunk

    if tables is not None:
        prefetch = (tables.astype(jnp.int32), cache_lens.astype(jnp.int32))

        def kv_map(b, kc, kj, tbl, lens):
            return (tbl[b, kc * nkc + kj], 0, 0, 0)
    else:
        prefetch = (cache_lens.astype(jnp.int32),)

        def kv_map(b, kc, kj, lens):
            return (b, kc * nkc + kj, 0, 0)

    def q_map(b, kc, kj, *_):
        return (b, 0, 0, 0)

    def part_map(b, kc, kj, *_):
        return (b, kc, 0, 0, 0)

    split = k_splits > 1
    if split:
        out_specs = [pl.BlockSpec((None, None, Hkv, QG, 1), part_map)] * 2 + [
            pl.BlockSpec((None, None, Hkv, QG, D), part_map)]
        out_shape = [jax.ShapeDtypeStruct((B, k_splits, Hkv, QG, 1), jnp.float32)] * 2 + [
            jax.ShapeDtypeStruct((B, k_splits, Hkv, QG, D), jnp.float32)]
    else:
        out_specs = pl.BlockSpec((None, Hkv, QG, D), q_map)
        out_shape = jax.ShapeDtypeStruct((B, Hkv, QG, D), q.dtype)
    kv_spec = pl.BlockSpec((None, bk, Hkv, D), kv_map)
    kernel = functools.partial(
        _attend_kernel, n_prefetch=len(prefetch), bk=bk, nkc=nkc, Q=Q, G=G,
        scale=scale, split=split,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, k_splits, nkc),
            in_specs=[pl.BlockSpec((None, Hkv, QG, D), q_map), kv_spec, kv_spec],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((Hkv, QG, 1), jnp.float32),
                pltpu.VMEM((Hkv, QG, 1), jnp.float32),
                pltpu.VMEM((Hkv, QG, D), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, _regroup_q_chunk(q, Hkv), k, v)
    if split:
        out = _combine(*out, q.dtype)
    return _ungroup_q_chunk(out, Q, Hq)


def decode_attention_pallas(
    q: jax.Array,          # (B, Hq, D)
    k_cache: jax.Array,    # (B, S, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,    # (B,) int32
    *,
    block_k: int = 512,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    """Single-stage flash decoding: each sequence walks its KV tiles."""
    return decode_attention_splitk(
        q, k_cache, v_cache, lengths, k_splits=1, block_k=block_k,
        softmax_scale=softmax_scale, interpret=interpret,
    )


def decode_attention_splitk(
    q: jax.Array,          # (B, Hq, D)
    k_cache: jax.Array,    # (B, S, Hkv, D)
    v_cache: jax.Array,
    lengths: jax.Array,    # (B,) int32
    *,
    k_splits: int = 4,
    block_k: int = 512,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    """Two-stage split-K flash decoding over a contiguous cache."""
    S = k_cache.shape[1]
    assert S % k_splits == 0, (S, k_splits)
    # the largest tile up to block_k that divides a split chunk
    bk = math.gcd(block_k, S // k_splits)
    out = _attend(q[:, None], k_cache, v_cache, lengths - 1, None, bk=bk,
                  k_splits=k_splits, softmax_scale=softmax_scale,
                  interpret=interpret)
    return out[:, 0]


def decode_attention_paged(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (P, page_size, Hkv, D) shared pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_blocks) int32
    lengths: jax.Array,        # (B,) int32
    *,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    """Single-stage paged flash decoding: one grid step per page."""
    return decode_attention_paged_splitk(
        q, k_pages, v_pages, block_tables, lengths, k_splits=1,
        softmax_scale=softmax_scale, interpret=interpret,
    )


def decode_attention_paged_splitk(
    q: jax.Array,              # (B, Hq, D)
    k_pages: jax.Array,        # (P, page_size, Hkv, D)
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_blocks) int32
    lengths: jax.Array,        # (B,) int32
    *,
    k_splits: int = 4,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    """Two-stage paged flash decoding: the block-table axis is cut into
    ``k_splits`` chunks (grid-parallel partial states), then merged with
    the same combine as the contiguous split-K path."""
    out = _attend(q[:, None], k_pages, v_pages, lengths - 1, block_tables,
                  bk=k_pages.shape[1], k_splits=k_splits,
                  softmax_scale=softmax_scale, interpret=interpret)
    return out[:, 0]


def mixed_attention_pallas(
    q: jax.Array,          # (B, Q, Hq, D) — Q new tokens per sequence
    k_cache: jax.Array,    # (B, S, Hkv, D), chunk KV already written
    v_cache: jax.Array,
    cache_lens: jax.Array, # (B,) int32 tokens cached BEFORE the chunk
    *,
    block_k: int = 512,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    bk = math.gcd(block_k, k_cache.shape[1])
    return _attend(q, k_cache, v_cache, cache_lens, None, bk=bk, k_splits=1,
                   softmax_scale=softmax_scale, interpret=interpret)


def mixed_attention_paged(
    q: jax.Array,              # (B, Q, Hq, D)
    k_pages: jax.Array,        # (P, page_size, Hkv, D) shared pool
    v_pages: jax.Array,
    block_tables: jax.Array,   # (B, n_blocks) int32
    cache_lens: jax.Array,     # (B,) int32 tokens cached BEFORE the chunk
    *,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    return _attend(q, k_pages, v_pages, cache_lens, block_tables,
                   bk=k_pages.shape[1], k_splits=1,
                   softmax_scale=softmax_scale, interpret=interpret)
