"""Core neural layers, pure JAX (jnp/lax), memory-safe at 32k-500k contexts.

Conventions
-----------
* Activations are (B, S, d); attention tensors are (B, S, H, Dh).
* All matmuls run in the config dtype (bf16 on TPU); softmax/norm/rope/state
  math in float32.
* Attention is a *chunked online-softmax* implementation (lax.scan over KV
  blocks inside a scan over Q blocks) so HLO never materializes S×S scores —
  the pure-JAX analogue of flash attention, and the oracle the Pallas kernel
  in ``repro.kernels.flash_attention`` is checked against.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32, cast back to input dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def group_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               num_groups: int, eps: float = 1e-5) -> jax.Array:
    """GroupNorm over the channel dim (RWKV head-wise ln_x)."""
    b_shape = x.shape[:-1]
    c = x.shape[-1]
    xf = x.astype(jnp.float32).reshape(*b_shape, num_groups, c // num_groups)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mean) * lax.rsqrt(var + eps)
    xf = xf.reshape(*b_shape, c)
    return (xf * weight + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max: float, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies (Hugging Face ``_compute_yarn_parameters``,
    truncated correction range): theta^(-2i/d) kept where a dimension turns
    more than ``beta_fast`` times over ``original_max`` positions, divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, and blended by
    a linear ramp between the two correction dimensions."""
    def corr_dim(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    base = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    return (base * (1.0 - ramp) + base / factor * ramp).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[jax.Array] = None,
               scale: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., S, H, Dh); positions: (..., S) int32.  ``inv_freq`` (Dh/2,)
    replaces theta's frequencies and ``scale`` multiplies cos and sin (a
    layer's own RoPE kind, e.g. YaRN's attention factor)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, Dh/2)
    sin = jnp.sin(angles)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — pure lax, O(S·C) memory
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def has_window(window) -> bool:
    """A window to mask by: a positive int, or a traced per-layer value."""
    return not isinstance(window, int) or window > 0


def _block_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool, window) -> jax.Array:
    """(Cq, Ck) bool mask: True = attend."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m = m & (dk <= dq)
    if has_window(window):
        m = m & (dk > dq - window)
    return m


def chunked_attention(
    q: jax.Array,                 # (B, Sq, H, Dh)
    k: jax.Array,                 # (B, Sk, H, Dh) — same head count (MHA form)
    v: jax.Array,                 # (B, Sk, H, Dh)
    *,
    causal: bool = True,
    window=0,                     # sliding window (0 = unbounded; may be traced)
    q_offset: int = 0,            # absolute position of q[0] (prefill w/ cache)
    q_chunk: int = 1024,
    k_chunk: int = 1024,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention over KV chunks; never builds (Sq, Sk) scores.

    Expects MHA-shaped inputs (GQA expansion + head padding to the TP degree
    happen in attention.py) so the head dim shards cleanly over 'model' —
    the grouped (B,Cq,Hkv,G,Dh) layout defeats the SPMD partitioner when
    Hkv < TP degree and silently replicates the score matmuls (the single
    largest FLOP term); see EXPERIMENTS.md §Perf iteration 0.

    Each Q-chunk's inner KV scan is wrapped in ``jax.checkpoint`` so training
    backward recomputes scores instead of storing every chunk product.
    """
    B, Sq, H, Dh = q.shape
    _, Sk, Hk, _ = k.shape
    assert H == Hk, (H, Hk)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(Dh)

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    nq, nk = Sq // q_chunk, Sk // k_chunk
    assert Sq % q_chunk == 0 and Sk % k_chunk == 0, (Sq, q_chunk, Sk, k_chunk)

    # (B, Sk, H, Dh) -> (nk, B, Ck, H, Dh)
    kb = k.reshape(B, nk, k_chunk, H, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nk, k_chunk, H, Dh).transpose(1, 0, 2, 3, 4)
    qb = q.reshape(B, nq, q_chunk, H, Dh).transpose(1, 0, 2, 3, 4)

    k_positions = jnp.arange(Sk, dtype=jnp.int32).reshape(nk, k_chunk)

    def q_block(args):
        q_i, q_pos = args                      # (B, Cq, H, Dh), (Cq,)

        def kv_step(carry, inputs):
            m_prev, l_prev, acc = carry
            k_j, v_j, k_pos = inputs           # (B, Ck, H, Dh), (Ck,)
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q_i, k_j,
                preferred_element_type=jnp.float32,
            ) * scale
            mask = _block_mask(q_pos, k_pos, causal, window)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32,
            )
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, H, q_chunk, Dh), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (kb, vb, k_positions))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (B, H, Cq, Dh) -> (B, Cq, H, Dh)
        return out.transpose(0, 2, 1, 3)

    q_positions = (q_offset + jnp.arange(Sq, dtype=jnp.int32)).reshape(nq, q_chunk)
    out_blocks = lax.map(jax.checkpoint(q_block), (qb, q_positions))
    out = out_blocks.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, Dh)
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                 # (B, Hq, Dh) — one new token per sequence
    k_cache: jax.Array,           # (B, S, Hkv, Dh)
    v_cache: jax.Array,           # (B, S, Hkv, Dh)
    cache_len: jax.Array,         # scalar or (B,) — valid prefix length
    *,
    window=0,                     # keys in (len - 1 - window, len - 1]; may be traced
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention against a (possibly sharded) KV cache.

    Dense einsum over S — memory is O(B·Hq·S) scores, which is small for
    Sq=1 and lets XLA partition the softmax reduction over a sequence-sharded
    cache (sequence parallelism for long_500k).
    """
    B, S, Hkv, Dh = k_cache.shape
    Hq = q.shape[1]
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(Dh)

    qg = q.reshape(B, Hkv, G, Dh)
    s = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(S, dtype=jnp.int32)
    if jnp.ndim(cache_len) == 0:
        valid = pos[None, :] < cache_len
    else:
        valid = pos[None, :] < cache_len[:, None]
    if has_window(window):
        lo = cache_len - window
        valid = valid & (pos[None, :] >= jnp.asarray(lo).reshape(-1, 1))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhgk,bkhd->bhgd", (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype),
        v_cache, preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Hq, Dh).astype(q.dtype)


def shard_batch_heads(t: jax.Array, mesh, head_axis: int = 2) -> jax.Array:
    """Constrain a (B, S, H, ...) tensor to batch-over-(pod,data) ×
    heads-over-model.  The SSM/RWKV scan inputs come out of reshape/concat
    chains the SPMD partitioner fails to propagate through (it replicates the
    whole scan — see EXPERIMENTS.md §Perf zamba2 iteration); this pins them.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P

    B = t.shape[0]
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    bspec = ba if (B % nb == 0 and B >= nb) else None
    spec = [bspec] + [None] * (t.ndim - 1)
    if head_axis < t.ndim and t.shape[head_axis] % mesh.shape["model"] == 0:
        spec[head_axis] = "model"
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, P(*spec)))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def fuse_gate_up_weights(w_gate: jax.Array, w_up: jax.Array) -> jax.Array:
    """Concatenate the swiglu gate/up matrices into one (d, 2f) matrix.
    Do this ONCE per decode dispatch on stacked (L, ...) weights, outside
    the layer scan, so it is loop-invariant w.r.t. the token scan."""
    return jnp.concatenate([w_gate, w_up], axis=-1)


def swiglu_fused(x: jax.Array, w_gu: jax.Array, w_down: jax.Array) -> jax.Array:
    """``swiglu`` with the gate/up pair as ONE matmul against a precomputed
    ``fuse_gate_up_weights`` matrix.  Bitwise identical to ``swiglu``
    (output columns of a matmul are independent), but half the
    up-projection dispatches — the scanned decode hot path."""
    gu = jnp.einsum("...d,df->...f", x, w_gu)
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, w_down)


def gelu_mlp(x: jax.Array, w_up: jax.Array, b_up: jax.Array,
             w_down: jax.Array, b_down: jax.Array) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, w_up) + b_up
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, w_down) + b_down


# ---------------------------------------------------------------------------
# Parameter init helpers
# ---------------------------------------------------------------------------


def dense_init(key: jax.Array, shape, dtype, scale: Optional[float] = None) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def embed_init(key: jax.Array, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
