"""Mamba-2 SSD chunk kernel (Pallas TPU).

Same TPU adaptation as the WKV6 kernel: the sequential grid walks chunks,
the (P, N) state lives in VMEM scratch, and intra-chunk work is MXU matmuls.
Mamba-2's decay is a *scalar per head per step*, so the pairwise decay matrix
is only (C, C) — the kernel is effectively masked attention with decays plus
a rank-N state passthrough.

Grid: (B·H, S/C).  B/C projections are shared across heads (index_map drops
the head coordinate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref,  # (1,C,P),(1,C),(1,),(1,C,N),(1,C,N),(1,P,N)
    y_ref, sout_ref,                             # (1,C,P), (1,P,N)
    state_ref,                                   # scratch (P,N) f32
    *,
    chunk: int, nc: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    xb = x_ref[0].astype(jnp.float32)      # (C, P)
    dtb = dt_ref[0].astype(jnp.float32)    # (C,)
    A = a_ref[0].astype(jnp.float32)       # scalar
    Bb = b_ref[0].astype(jnp.float32)      # (C, N)
    Cb = c_ref[0].astype(jnp.float32)      # (C, N)

    da = dtb * A                           # (C,) log-decay <= 0
    cum = jnp.cumsum(da)                   # inclusive
    S_prev = state_ref[...]

    # inter-chunk: y_t += exp(cum[t]) · S_prev C_t
    y_inter = jax.lax.dot_general(
        Cb, S_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * jnp.exp(cum)[:, None]              # (C, P)

    # intra-chunk: att[t,s] = (C_t·B_s)·exp(cum[t]-cum[s])·Δ_s, s <= t
    G = jnp.exp(cum[:, None] - cum[None, :])
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    cb = jax.lax.dot_general(
        Cb, Bb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    att = cb * jnp.where(tri, G, 0.0) * dtb[None, :]
    y_intra = jax.lax.dot_general(
        att, xb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y_ref[0] = (y_inter + y_intra).astype(y_ref.dtype)

    dec_end = jnp.exp(cum[-1] - cum)       # (C,)
    upd = jax.lax.dot_general(
        xb * (dtb * dec_end)[:, None], Bb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                      # (P, N)
    state_ref[...] = jnp.exp(cum[-1]) * S_prev + upd

    @pl.when(ci == nc - 1)
    def _final():
        sout_ref[0] = state_ref[...]


def ssd_pallas(
    x,          # (B, S, H, P)
    dt,         # (B, S, H)
    A,          # (H,)
    Bm,         # (B, S, N)
    Cm,         # (B, S, N)
    state0,     # (B, H, P, N) fp32
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y (B,S,H,P) fp32, final_state (B,H,P,N) fp32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S)
    af = jnp.broadcast_to(A[None], (B, H)).reshape(B * H)
    s0 = state0.reshape(B * H, P, N)

    grid = (B * H, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc)
    y, sout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk), lambda bh, ci: (bh, ci)),
            pl.BlockSpec((1,), lambda bh, ci: (bh,)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci, H=H: (bh // H, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci, H=H: (bh // H, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, af, Bm, Cm, s0)
    return (
        y.reshape(B, H, S, P).transpose(0, 2, 1, 3),
        sout.reshape(B, H, P, N),
    )
