"""What every architecture's plain reference shares: float32 building
blocks, the float8 control's rounding, and the gap of served tokens.

An architecture's forward (``archs/<model_type>.py``) is written from its
published description and imports nothing of the program.  Matmuls run at
``precision=HIGHEST``: float32 on the TPU, not three bf16 passes.

The control (``mode="fp8"`` of a forward) is the same forward with every
matmul weight stored as float8_e4m3 (one scale per output column) and
computed in bfloat16, the step below the bfloat16 the configuration
states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0      # largest finite float8_e4m3fn
ROWS = 512           # logit rows computed per request (its served tokens)


def fp8(w: jax.Array) -> jax.Array:
    """Round a (..., in, out) weight to float8_e4m3 with one scale per
    output column, returned dequantized in bfloat16."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (wf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


def rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """Rotate-half RoPE on (T, H, D) at positions 0..T-1."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def served_gaps(logits, prompt: np.ndarray, served: np.ndarray, pad_to: int,
                control: bool = False):
    """For one request: the gap by which each served token's logit lies
    below the reference's best at its position, and (``control``) the
    same gap for the token the fp8 control puts first there.

    ``logits(tokens, rows, mode=)`` is an architecture's forward: the
    logits of the sequence ``tokens`` at positions ``rows``, ``mode``
    ``"f32"`` or ``"fp8"``.  The sequence is the prompt plus the served
    tokens but the last, padded at the end to ``pad_to`` (causal, so
    padding changes nothing before it).  Returns (served gaps, control
    gaps or None) as float32 arrays of len(served)."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n, T = len(served), len(seq)
    if T > pad_to:
        raise ValueError(f"sequence of {T} tokens above pad_to {pad_to}")
    tokens = jnp.asarray(np.pad(seq, (0, pad_to - T)))
    rows_np = len(prompt) - 1 + np.arange(n)
    n_rows = ROWS if n <= ROWS else pad_to          # one program per size
    rows = jnp.asarray(np.pad(rows_np, (0, n_rows - n), mode="edge"))
    ref = logits(tokens, rows, mode="f32")[:n]
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(best - got)
    cgaps = None
    if control:
        low = logits(tokens, rows, mode="fp8")[:n]
        pick = jnp.argmax(low, axis=-1)
        cgaps = np.asarray(best - jnp.take_along_axis(ref, pick[:, None],
                                                      axis=-1)[:, 0])
    return gaps, cgaps
