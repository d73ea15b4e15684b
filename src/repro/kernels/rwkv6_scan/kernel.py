"""Chunked WKV6 recurrence as a Pallas TPU kernel.

GPU RWKV kernels assign one thread per channel and serialize over time; the
TPU adaptation processes a whole (C, N) chunk per grid step so the intra-chunk
work is MXU matmuls (C×N · N×N and C×C · C×N), with the cross-chunk carried
state S (N×N fp32) in VMEM scratch — the sequential TPU grid plays the role
of the GPU's time loop but at chunk, not token, granularity.

All pairwise decays are exp(non-positive) (log-space cumulative sums), so
the kernel is overflow-free for any data-dependent decay.

Grid: (B·H, S/C).  Inputs are pre-transposed to (B·H, S, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,   # (1,C,N)×4, (1,N), (1,N,N)
    y_ref, sout_ref,                             # (1,C,N), (1,N,N)
    state_ref,                                   # scratch (N,N) f32
    *,
    chunk: int, nc: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    rb = r_ref[0].astype(jnp.float32)        # (C, N)
    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)
    wb = w_ref[0].astype(jnp.float32)        # log-decay <= 0
    u = u_ref[0].astype(jnp.float32)         # (N,)

    cum = jnp.cumsum(wb, axis=0)             # (C, N) inclusive
    a = cum - wb                             # decay chunk-start -> t (exclusive)
    S_prev = state_ref[...]

    y_inter = jax.lax.dot_general(
        rb * jnp.exp(a), S_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # pairwise decays D[t,s,n] = exp(a[t,n] - cum[s,n]), s < t   (all <= 1)
    D = jnp.exp(a[:, None, :] - cum[None, :, :])
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_), k=-1)
    D = jnp.where(tri[:, :, None], D, 0.0)
    att = jnp.einsum("tn,tsn,sn->ts", rb, D, kb)
    y_intra = jax.lax.dot_general(
        att, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_bonus = jnp.sum(rb * u[None, :] * kb, axis=1, keepdims=True) * vb

    y_ref[0] = (y_inter + y_intra + y_bonus).astype(y_ref.dtype)

    dec_end = jnp.exp(cum[-1:, :] - cum)     # (C, N)
    state_ref[...] = jnp.exp(cum[-1])[:, None] * S_prev + jax.lax.dot_general(
        (kb * dec_end), vb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ci == nc - 1)
    def _final():
        sout_ref[0] = state_ref[...]


def wkv6_pallas(
    r, k, v, logw,          # (B, S, H, N)
    u,                      # (H, N)
    state0,                 # (B, H, N, N) fp32
    *,
    chunk: int = 64,
    interpret: bool = False,
):
    """Returns (y (B,S,H,N) fp32, final_state (B,H,N,N) fp32)."""
    B, S, H, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, N)

    rf, kf, vf, wf = map(flat, (r, k, v, logw))
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
    s0 = state0.reshape(B * H, N, N)

    grid = (B * H, nc)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk, nc=nc)
    y, sout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, N), lambda bh, ci: (bh, 0)),
            pl.BlockSpec((1, N, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, N, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)
    return (
        y.reshape(B, H, S, N).transpose(0, 2, 1, 3),
        sout.reshape(B, H, N, N),
    )
