"""Full attention block: projections, GQA, qk-norm, RoPE, KV cache.

Cache layouts
-------------
* full attention: ``k/v`` of shape (B, S_max, Hkv, Dh); ``cache_len`` scalar.
* sliding-window (mixtral): ring buffer of shape (B, W, Hkv, Dh) — bounds
  long_500k cache memory to the window (keys stored with absolute RoPE, so
  relative phases stay correct as the ring wraps).
* per-layer kinds (mellum): every layer keeps a full-length cache (either
  layout); a ``kind`` — the layer's window and RoPE tables, scanned beside
  its weights (``transformer.layer_kinds``) — masks keys outside the window
  (query at p sees keys in (p - W, p]) and picks the layer's RoPE.
* paged: a shared (P, page_size, Hkv, Dh) page pool read/written through a
  (B, n_blocks) ``page_table`` — logical position ``t`` of slot ``b`` lives
  at row ``t % page_size`` of page ``page_table[b, t // page_size]``.
  Requests sharing a prompt prefix point at the SAME physical pages
  (serving.paged_kv owns the refcount/copy-on-write bookkeeping); the
  decode step only ever writes position ``cache_len[b]``, which the
  allocator guarantees is an exclusively owned page.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers


class KVCache(NamedTuple):
    """Keys and values.  ``attention_block`` returns one layer's
    (B, S_cache, Hkv, Dh).  The decode, mixed and paged-prefill steps take
    the stacked cache of every layer, (L, B, S_cache, Hkv, Dh) or the page
    pool (L, P, ps, Hkv, Dh), with a layer index: they write that layer's
    new rows into it in place, attend over that layer, and return the whole
    stacked cache.  ``cache_len`` lives at the model level (shared across
    layers)."""
    k: jax.Array
    v: jax.Array


def _layer(a: jax.Array, layer, rows: Optional[int] = None) -> jax.Array:
    """Layer ``layer`` of a stacked (L, B, S, ...) cache, cut to its first
    ``rows`` positions when given."""
    if rows is None:
        return lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
    start = (layer,) + (0,) * (a.ndim - 1)
    return lax.dynamic_slice(a, start, (1, a.shape[1], rows, *a.shape[3:]))[0]


def _layer_pages(pool: jax.Array, layer, table: jax.Array) -> jax.Array:
    """The pages ``table`` (B, nb) names in layer ``layer`` of a stacked
    (L, P, ps, Hkv, Dh) pool, in logical order: (B, nb·ps, Hkv, Dh) — the
    layout ``ref.gather_pages`` gives for one layer's pool, in one gather."""
    B, nb = table.shape
    return pool[layer, table].reshape(B, nb * pool.shape[2], *pool.shape[3:])


def init_attn_params(key: jax.Array, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, qd), dtype),
        "wk": layers.dense_init(ks[1], (d, kvd), dtype),
        "wv": layers.dense_init(ks[2], (d, kvd), dtype),
        "wo": layers.dense_init(ks[3], (qd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def fuse_qkv_weights(p) -> jax.Array:
    """Concatenate wq/wk/wv into one (d, qd+2·kvd) matrix.  Called ONCE per
    decode dispatch on the stacked (L, ...) layer weights — outside the
    layer scan — so the concat is loop-invariant w.r.t. the token scan and
    costs nothing per step (see transformer.fused_decode_weights)."""
    return jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=-1)


def _rope(x, positions, cfg: ModelConfig, kind: Optional[Dict]):
    if kind is None:
        return layers.apply_rope(x, positions, cfg.rope_theta)
    return layers.apply_rope(x, positions, cfg.rope_theta,
                             inv_freq=kind["inv_freq"], scale=kind["rope_scale"])


def _window(kind: Optional[Dict]):
    """The layer's window for the masks (0: none)."""
    return 0 if kind is None else kind["window"]


def _no_kernel_kinds(cfg: ModelConfig, kind: Optional[Dict]) -> None:
    if kind is not None and cfg.use_pallas:
        raise ValueError(f"{cfg.name}: per-layer windows and RoPE kinds run on "
                         "the XLA attention path (use_pallas=False)")


def _project_qkv(p, x, cfg: ModelConfig, positions, *, fused: bool = False,
                 wqkv: Optional[jax.Array] = None, kind: Optional[Dict] = None):
    """QKV projection.  ``fused=True`` (decode hot path) runs the three
    projections as ONE matmul — bitwise identical per output column, but a
    third of the matmul dispatches.  Pass a precomputed ``wqkv``
    (``fuse_qkv_weights``) when calling from inside a scanned layer loop;
    otherwise the concat happens here (fine when ``p`` is loop-invariant,
    e.g. zamba2's single shared attention block).  ``kind`` gives the
    layer's own RoPE tables (``transformer.layer_kinds``)."""
    B = x.shape[0]
    S = x.shape[1]
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    if fused:
        w = wqkv if wqkv is not None else fuse_qkv_weights(p)
        qkv = jnp.einsum("bsd,dk->bsk", x, w)
        q, k, v = jnp.split(qkv, [cfg.q_dim, cfg.q_dim + cfg.kv_dim], axis=-1)
        q = q.reshape(B, S, Hq, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        # one norm+rope pass over the concatenated (Hq+Hkv) head axis —
        # rms_norm reduces over hd (per head, unaffected by the concat) and
        # rope depends only on positions; assembling the (H, hd) norm
        # weight costs two broadcasts + a concat of a tiny tensor.
        qk = jnp.concatenate([q, k], axis=2)
        if cfg.qk_norm:
            wqk = jnp.concatenate([
                jnp.broadcast_to(p["q_norm"], (Hq, hd)),
                jnp.broadcast_to(p["k_norm"], (Hkv, hd)),
            ])
            qk = layers.rms_norm(qk, wqk, cfg.norm_eps)
        qk = _rope(qk, positions, cfg, kind)
        q, k = qk[:, :, :Hq], qk[:, :, Hq:]
        return q, k, v
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"]).reshape(B, S, Hq, hd)
    k = jnp.einsum("bsd,dk->bsk", x, p["wk"]).reshape(B, S, Hkv, hd)
    v = jnp.einsum("bsd,dk->bsk", x, p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = _rope(q, positions, cfg, kind)
    k = _rope(k, positions, cfg, kind)
    return q, k, v


def _head_shard_constraint(t: jax.Array, mesh) -> jax.Array:
    """Pin (B, S, H, Dh) to batch-over-(pod,data) × heads-over-model."""
    if mesh is None or "model" not in mesh.axis_names:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, _, H, _ = t.shape
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    bspec = ba if (B % nb == 0 and B >= nb) else None
    hspec = "model" if H % mesh.shape["model"] == 0 else None
    return jax.lax.with_sharding_constraint(
        t, NamedSharding(mesh, P(bspec, None, hspec, None))
    )


def _tp_degree(mesh) -> int:
    return mesh.shape["model"] if (mesh is not None and "model" in mesh.axis_names) else 1


def _expand_and_pad_heads(q, k, v, cfg: ModelConfig, mesh):
    """GQA→MHA expansion + zero-pad heads to a multiple of the TP degree.

    Head-sharding only partitions when H % tp == 0; arctic's 56 heads pad
    to 64 (14% waste, vs full replication of the score matmuls otherwise).
    Padded q rows are zero ⇒ uniform softmax over garbage v, sliced off
    before the output projection — exactness is unaffected.

    This is the *fallback* layout: the Pallas flash kernel is GQA-native
    (``_gqa_native_ok``) and keeps KV at Hkv width, so expansion only runs
    for the pure-lax path and for TP degrees that force q-head padding.
    """
    B, S, Hq, Dh = q.shape
    G = Hq // cfg.n_kv_heads
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    tp = _tp_degree(mesh)
    Hp = ((Hq + tp - 1) // tp) * tp
    if Hp != Hq:
        pad = [(0, 0), (0, 0), (0, Hp - Hq), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    q = _head_shard_constraint(q, mesh)
    k = _head_shard_constraint(k, mesh)
    v = _head_shard_constraint(v, mesh)
    return q, k, v, Hq


def _gqa_native_ok(cfg: ModelConfig, mesh) -> bool:
    """The Pallas kernel can take KV at Hkv width whenever the q heads shard
    cleanly over TP (KV shards too when Hkv % tp == 0, else it replicates —
    still Hkv-wide per device, never G× expanded).  Only a TP degree that
    does not divide Hq (arctic's 56 heads on tp=16) needs the padded
    MHA-form fallback."""
    return cfg.n_heads % _tp_degree(mesh) == 0


def attention_block(
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, S, d)
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    return_cache: bool = False,
    mesh=None,
    kind: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Prefill / training attention (chunked flash path).  ``kind``: the
    layer's window and RoPE (``transformer.layer_kinds``)."""
    _no_kernel_kinds(cfg, kind)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = _project_qkv(p, x, cfg, positions, kind=kind)
    cache = None
    if return_cache:
        kc, vc = k, v
        if cfg.sliding_window > 0 and S >= cfg.sliding_window:
            # keep last W entries; ring-aligned when S % W == 0
            kc = kc[:, -cfg.sliding_window:]
            vc = vc[:, -cfg.sliding_window:]
        cache = KVCache(k=kc, v=vc)
    gqa_native = cfg.use_pallas and _gqa_native_ok(cfg, mesh)
    if gqa_native:
        # GQA-native kernel: KV stays at Hkv width end to end — no
        # jnp.repeat, so KV HBM traffic/VMEM never multiply by the group
        # size (8× for llama3-405b).
        qe = _head_shard_constraint(q, mesh)
        ke = _head_shard_constraint(k, mesh)
        ve = _head_shard_constraint(v, mesh)
        Hq = qe.shape[2]
    else:
        qe, ke, ve, Hq = _expand_and_pad_heads(q, k, v, cfg, mesh)
    if cfg.use_pallas:
        from repro.kernels.flash_attention.ops import flash_attention

        out = flash_attention(
            qe, ke, ve,
            causal=cfg.causal,
            window=cfg.sliding_window,
            block_q=min(512, S),
            block_k=min(512, S),
        )
    else:
        out = layers.chunked_attention(
            qe, ke, ve,
            causal=cfg.causal,
            window=cfg.sliding_window if kind is None else kind["window"],
            q_chunk=min(1024, S),
            k_chunk=min(1024, S),
        )
    out = out[:, :, :Hq, :]
    out = jnp.einsum("bsq,qd->bsd", out.reshape(B, S, cfg.q_dim), p["wo"])
    return out, cache


def attention_decode(
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, 1, d) — one new token
    cache: KVCache,                     # stacked (L, B, S_cache, Hkv, Dh)
    layer: jax.Array,                   # scalar int32: the layer to run
    cache_len: jax.Array,               # scalar int32 OR (B,) per-slot lengths
    cfg: ModelConfig,
    wqkv: Optional[jax.Array] = None,   # precomputed fuse_qkv_weights(p)
    page_table: Optional[jax.Array] = None,   # (B, n_blocks) int32 page ids
    kind: Optional[Dict] = None,        # the layer's window and RoPE
) -> Tuple[jax.Array, KVCache]:
    """One decode step of layer ``layer``: write the token's KV into the
    stacked cache (ring for SWA), attend over that layer, project.

    ``cache_len`` may be a scalar (fixed-batch generation: every sequence is
    at the same position) or a (B,) vector (continuous batching: each slot
    has its own length; writes go to per-slot positions, one row scatter).
    With ``cfg.use_pallas`` the attention runs the flash-decoding kernel
    (length-skipped tiles, split-K for long caches) instead of the dense
    einsum over the full ``max_len`` cache.

    With ``page_table`` the cache is the stacked shared page pool (L, P,
    ps, Hkv, Dh): the new token's KV scatters to its table-resolved (page,
    row) and attention reads through the table — the Pallas paged kernel
    gathers pages inside its grid; the lax fallback gathers then reuses the
    dense reference.  Paged mode requires ragged (B,) ``cache_len`` and no
    ring window; per-layer windows (``kind``) mask either layout.
    """
    _no_kernel_kinds(cfg, kind)
    if page_table is not None:
        return _attention_decode_paged(p, x, cache, layer, cache_len, cfg,
                                       wqkv=wqkv, page_table=page_table,
                                       kind=kind)
    B = x.shape[0]
    cache_len = jnp.asarray(cache_len, jnp.int32)
    ragged = cache_len.ndim == 1
    positions = (
        cache_len[:, None] if ragged
        else jnp.broadcast_to(cache_len, (B, 1)).astype(jnp.int32)
    )
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, fused=True, wqkv=wqkv,
                                   kind=kind)

    W = cache.k.shape[2]
    if cfg.sliding_window > 0:
        write_at = cache_len % W
        eff_len = jnp.minimum(cache_len + 1, W)
    else:
        write_at = cache_len
        eff_len = cache_len + 1
    with jax.named_scope("kv_write"):
        if ragged:
            # clipped like a dynamic_update_slice's start
            slot = jnp.arange(B)
            k_c = cache.k.at[layer, slot, write_at].set(k_new[:, 0], mode="clip")
            v_c = cache.v.at[layer, slot, write_at].set(v_new[:, 0], mode="clip")
        else:
            at = (layer, 0, write_at, 0, 0)
            k_c = lax.dynamic_update_slice(cache.k, k_new[None], at)
            v_c = lax.dynamic_update_slice(cache.v, v_new[None], at)
    k_l, v_l = _layer(k_c, layer), _layer(v_c, layer)

    # ring buffer already bounds the SWA window, so only length masking
    # remains — which is exactly the flash-decoding kernel's contract.
    if cfg.use_pallas:
        from repro.kernels.decode_attention.ops import decode_attention as kdecode

        lengths = eff_len if ragged else jnp.broadcast_to(eff_len, (B,))
        out = kdecode(q[:, 0], k_l, v_l, lengths, block_k=math.gcd(W, 512))
    else:
        out = layers.decode_attention(q[:, 0], k_l, v_l, eff_len,
                                      window=_window(kind))
    out = jnp.einsum("bq,qd->bd", out.reshape(B, cfg.q_dim), p["wo"])[:, None, :]
    return out, KVCache(k=k_c, v=v_c)


def _attention_decode_paged(
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, 1, d)
    cache: KVCache,                     # stacked pool: (L, P, ps, Hkv, Dh)
    layer: jax.Array,
    cache_len: jax.Array,               # (B,) per-slot lengths
    cfg: ModelConfig,
    *,
    wqkv: Optional[jax.Array],
    page_table: jax.Array,              # (B, n_blocks) int32
    kind: Optional[Dict] = None,
) -> Tuple[jax.Array, KVCache]:
    if cfg.sliding_window > 0:
        raise ValueError("paged KV does not support sliding-window attention")
    B = x.shape[0]
    ps = cache.k.shape[2]
    cache_len = jnp.asarray(cache_len, jnp.int32)
    if cache_len.ndim != 1:
        raise ValueError("paged decode requires (B,) per-slot cache_len")
    positions = cache_len[:, None]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, fused=True, wqkv=wqkv,
                                   kind=kind)

    # scatter the new token's KV to its (page, row).  Idle/finished slots
    # resolve to the trash page; colliding trash writes are harmless.
    page = jnp.take_along_axis(
        page_table, (cache_len // ps)[:, None], axis=1
    )[:, 0]
    row = cache_len % ps
    with jax.named_scope("kv_write"):
        k_c = cache.k.at[layer, page, row].set(k_new[:, 0])
        v_c = cache.v.at[layer, page, row].set(v_new[:, 0])
    eff_len = cache_len + 1

    if cfg.use_pallas:
        from repro.kernels.decode_attention.ops import decode_attention as kdecode

        out = kdecode(q[:, 0], _layer(k_c, layer), _layer(v_c, layer), eff_len,
                      page_table=page_table)
    else:
        out = layers.decode_attention(
            q[:, 0], _layer_pages(k_c, layer, page_table),
            _layer_pages(v_c, layer, page_table), eff_len, window=_window(kind),
        )
    out = jnp.einsum("bq,qd->bd", out.reshape(B, cfg.q_dim), p["wo"])[:, None, :]
    return out, KVCache(k=k_c, v=v_c)


def attention_mixed(
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (B, Q, d) — Q new tokens per slot
    cache: KVCache,                     # stacked (L, B, S, Hkv, Dh) or pool (L, P, ps, Hkv, Dh)
    layer: jax.Array,                   # scalar int32: the layer to run
    cache_lens: jax.Array,              # (B,) tokens already cached per slot
    new_lens: jax.Array,                # (B,) REAL new tokens (<= Q) per slot
    cfg: ModelConfig,
    *,
    wqkv: Optional[jax.Array] = None,   # precomputed fuse_qkv_weights(p)
    page_table: Optional[jax.Array] = None,   # (B, n_blocks) => paged pool
    attn_window: Optional[int] = None,  # static: keys [0, attn_window) suffice
    kind: Optional[Dict] = None,        # the layer's window and RoPE
) -> Tuple[jax.Array, KVCache]:
    """One mixed-batch step of layer ``layer``: every slot advances by its
    own ragged suffix.

    The engine's fused prefill+decode dispatch: slot b carries
    ``(cache_lens[b], new_lens[b])`` — a decode slot has new_len 1, a
    prefill chunk has new_len up to Q, an idle/waiting slot 0.  All Q
    positions project/attend (padding rows compute discarded garbage, which
    is what lets ONE trace per pow-of-2 Q bucket serve every chunk shape);
    only rows ``i < new_lens[b]`` write KV — padding writes are suppressed
    (contiguous: scattered past the cache and dropped; paged: redirected
    to the trash page), so garbage never lands
    where real KV will live before it is overwritten.  Query i attends
    causally to every position ``<= cache_lens[b] + i`` (cached prefix +
    the chunk's earlier tokens, written into the stacked cache first).

    ``attn_window`` is the engine's static bound on ``max(cache_lens +
    new_lens)`` this step: attention reads only the first ``attn_window``
    cache positions (the lax path's stand-in for the Pallas kernels'
    length-based tile skipping — without it every chunk pays O(S_max)
    score work on backends running the reference path).  Correctness does
    not depend on it: the causal mask already excludes everything past the
    content frontier.

    A layer with a window of its own (``kind``) also masks keys at or
    before ``position - window``; its cache still holds every position.

    Requires ragged (B,) lengths and no ring window (``cfg.sliding_window``)
    — the same contract as the paged decode path.
    """
    _no_kernel_kinds(cfg, kind)
    if cfg.sliding_window > 0:
        raise ValueError("mixed-batch steps do not support sliding-window attention")
    B, Q, _ = x.shape
    cache_lens = jnp.asarray(cache_lens, jnp.int32)
    new_lens = jnp.asarray(new_lens, jnp.int32)
    if cache_lens.ndim != 1:
        raise ValueError("mixed-batch steps require (B,) per-slot cache_lens")
    positions = cache_lens[:, None] + jnp.arange(Q, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, x, cfg, positions, fused=True, wqkv=wqkv,
                                   kind=kind)
    valid = jnp.arange(Q, dtype=jnp.int32)[None, :] < new_lens[:, None]

    if page_table is not None:
        ps = cache.k.shape[2]
        nb = page_table.shape[1]
        block = jnp.clip(positions // ps, 0, nb - 1)
        page = jnp.take_along_axis(page_table, block, axis=1)
        page = jnp.where(valid, page, 0)                 # padding -> trash page
        row = positions % ps
        with jax.named_scope("kv_write"):
            k_c = cache.k.at[layer, page, row].set(k_new.astype(cache.k.dtype))
            v_c = cache.v.at[layer, page, row].set(v_new.astype(cache.v.dtype))
        read_table = (page_table if attn_window is None
                      else page_table[:, : -(-attn_window // ps)])
    else:
        # one (Hkv, Dh) row scatter per chunk row; padding rows aim past
        # the cache and are dropped.  (A positional select over every cache
        # position, cheaper on CPU, lowers on TPU to a per-element gather
        # of the whole cache in every layer.)
        S = cache.k.shape[2]
        pos = jnp.where(valid, positions, S)
        slot = jnp.arange(B)[:, None]
        with jax.named_scope("kv_write"):
            k_c = cache.k.at[layer, slot, pos].set(k_new.astype(cache.k.dtype), mode="drop")
            v_c = cache.v.at[layer, slot, pos].set(v_new.astype(cache.v.dtype), mode="drop")

    if page_table is None:
        read_table = None
        k_r, v_r = _layer(k_c, layer, attn_window), _layer(v_c, layer, attn_window)
    elif cfg.use_pallas:                                 # the kernel reads the table
        k_r, v_r = _layer(k_c, layer), _layer(v_c, layer)
    else:
        k_r = _layer_pages(k_c, layer, read_table)
        v_r = _layer_pages(v_c, layer, read_table)
    if cfg.use_pallas:
        from repro.kernels.decode_attention.ops import mixed_attention

        out = mixed_attention(q, k_r, v_r, cache_lens, page_table=read_table)
    else:
        from repro.kernels.decode_attention.ref import mixed_attention_ref

        out = mixed_attention_ref(q, k_r, v_r, cache_lens, window=_window(kind))
    out = jnp.einsum("bqk,kd->bqd", out.reshape(B, Q, cfg.q_dim), p["wo"])
    return out, KVCache(k=k_c, v=v_c)


def attention_prefill_paged(
    p: Dict[str, jax.Array],
    x: jax.Array,                       # (1, T, d) — the prompt suffix
    pool: KVCache,                      # stacked shared page pool (L, P, ps, Hkv, Dh)
    layer: jax.Array,                   # scalar int32: the layer to run
    page_row: jax.Array,                # (nb,) int32: ONE slot's block table
    start: jax.Array,                   # scalar int32: tokens already cached
    cfg: ModelConfig,
    kind: Optional[Dict] = None,        # the layer's window and RoPE
) -> Tuple[jax.Array, KVCache]:
    """Continuation prefill of layer ``layer``: extend a paged cache by T
    tokens in ONE step.

    The prefix-hit admission path: positions [0, start) are already in the
    pool (reused pages), so only the suffix runs through the model — its KV
    scatters into the slot's pages and each suffix query attends causally
    to everything at or before it (cached prefix + earlier suffix).  This
    is prefill-shaped compute (one dispatch, (T, S) attention), not T
    decode steps.
    """
    if cfg.sliding_window > 0:
        raise ValueError("paged KV does not support sliding-window attention")
    B, T, _ = x.shape
    assert B == 1, "continuation prefill is per-slot (B=1)"
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    G = Hq // Hkv
    ps = pool.k.shape[2]
    pos = start + jnp.arange(T, dtype=jnp.int32)        # (T,) absolute
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[None, :], kind=kind)
    pages = page_row[pos // ps]
    rows = pos % ps
    with jax.named_scope("kv_write"):
        k_c = pool.k.at[layer, pages, rows].set(k_new[0].astype(pool.k.dtype))
        v_c = pool.v.at[layer, pages, rows].set(v_new[0].astype(pool.v.dtype))

    kg = _layer_pages(k_c, layer, page_row[None])[0]    # (S_max, Hkv, Dh)
    vg = _layer_pages(v_c, layer, page_row[None])[0]
    qg = q[0].reshape(T, Hkv, G, hd)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("thgd,shd->hgts", qg, kg,
                   preferred_element_type=jnp.float32) * scale
    keypos = jnp.arange(kg.shape[0])
    mask = keypos[None, :] <= pos[:, None]              # causal continuation
    if kind is not None:
        mask = mask & (keypos[None, :] > pos[:, None] - kind["window"])
    s = jnp.where(mask[None, None], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hgts,shd->thgd", pr.astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = jnp.einsum("tq,qd->td", out.reshape(T, cfg.q_dim), p["wo"])[None]
    return out, KVCache(k=k_c, v=v_c)


def empty_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    S_cache = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    shape = (batch, S_cache, cfg.n_kv_heads, cfg.resolved_head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def empty_page_pool(cfg: ModelConfig, num_pages: int, page_size: int,
                    dtype) -> KVCache:
    """The shared paged-KV pool for one layer: (P, page_size, Hkv, Dh)."""
    shape = (num_pages, page_size, cfg.n_kv_heads, cfg.resolved_head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))
