"""Flash attention (prefill/training fwd) as a Pallas TPU kernel — GQA-native.

TPU adaptation (DESIGN.md hardware-adaptation notes): the CUDA flash
algorithm maps warps to score tiles; on TPU the analogue is MXU-shaped
(128-multiple) VMEM tiles walked by a sequential grid, with the online
softmax state (m, l, acc) living in VMEM scratch that persists across the
innermost (KV) grid dimension.

GQA is handled *inside* the kernel: q arrives at full Hq = G·Hkv width but
k/v stay at Hkv width.  The grid walks (B·Hkv, Sq/bq, Sk/bk) and each step
loads one (G, bq, D) query group against a single shared (bk, D) KV tile —
the (G·bq, D)×(D, bk) matmul feeds the MXU one KV read per *group* instead
of per query head, so KV HBM traffic and VMEM footprint never multiply by
G (8× for llama3-405b).  G == 1 recovers the plain MHA kernel.

Causal/sliding-window masking is positional (iota over the tile); the causal
upper triangle of KV blocks is skipped entirely via @pl.when (no MXU work),
unlike the baseline lax implementation which masks but still multiplies.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,       # VMEM tiles
    m_ref, l_ref, acc_ref,            # scratch (persist across kv grid dim)
    *,
    bq: int, bk: int, nk: int, g: int,
    causal: bool, window: int, scale: float, sk_minus_sq: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this tile (shared by all G heads of the group)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + sk_minus_sq
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    run = True
    if causal:
        # skip tiles entirely above the diagonal
        run = (kj * bk) <= (qi * bq + bq - 1 + sk_minus_sq)
    if window > 0:
        run = jnp.logical_and(run, (kj + 1) * bk - 1 > qi * bq + sk_minus_sq - window) if causal else run

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32).reshape(g * bq, -1)   # (G·bq, d)
        k = k_ref[0].astype(jnp.float32)                       # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                              # (G·bq, bk)
        s = s.reshape(g, bq, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask[None], s, NEG_INF)

        m_prev = m_ref[...]                                    # (G, bq)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
        m_ref[...] = m_new
        pv = jax.lax.dot_general(
            p.reshape(g * bq, bk).astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(g, bq, -1)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]).astype(
            o_ref.dtype
        )


def flash_attention_pallas(
    q: jax.Array,            # (B, Sq, Hq, D)
    k: jax.Array,            # (B, Sk, Hkv, D); Hkv divides Hq (GQA-native)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    softmax_scale=None,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, f"GQA head mismatch: Hq={Hq} Hkv={Hkv}"
    G = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk

    # q: (B, Sq, Hkv·G, D) -> (B·Hkv, G, Sq, D); query head h serves kv head
    # h // G (the same grouping convention as the decode kernel/ref).
    qf = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 3, 1, 4).reshape(
        B * Hkv, G, Sq, D
    )
    # k/v: (B, Sk, Hkv, D) -> (B·Hkv, Sk, D) — never widened to Hq.
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)

    grid = (B * Hkv, nq, nk)
    kernel = functools.partial(
        _flash_kernel,
        bq=bq, bk=bk, nk=nk, g=G,
        causal=causal, window=window, scale=scale, sk_minus_sq=Sk - Sq,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, bq, D), lambda bh, qi, kj: (bh, 0, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, kj: (bh, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D), lambda bh, qi, kj: (bh, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq), jnp.float32),
            pltpu.VMEM((G, bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    # (B·Hkv, G, Sq, D) -> (B, Sq, Hq, D)
    return out.reshape(B, Hkv, G, Sq, D).transpose(0, 3, 1, 2, 4).reshape(
        B, Sq, Hq, D
    )
