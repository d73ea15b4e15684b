"""The plain reference: a Qwen3 decoder's whole-sequence forward in
float32, written from the published architecture and importing nothing
of the program.

  x = embed[tokens]
  per layer:  h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv
              q, k = rmsnorm per head (q_norm, k_norm), then RoPE
              (rotate-half, theta from the configuration)
              a = softmax(q k^T / sqrt(head_dim), causal) v   (GQA)
              x = x + a Wo
              h = rmsnorm(x) ; x = x + (silu(h Wg) * h Wu) Wd
  logits = rmsnorm(x) embed^T            (tied embedding)

It runs layer by layer inside one scan, casting each layer's weights up
as it goes, so the float32 copy of the whole model never exists.  Matmuls
run at ``precision=HIGHEST``: float32 on the TPU, not three bf16 passes.

``mode="fp8"`` is the control: the same forward with every matmul weight
stored as float8_e4m3 (one scale per output column) and computed in
bfloat16, the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.work import Shapes

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0      # largest finite float8_e4m3fn
ROWS = 512           # logit rows computed per request (its served tokens)


def _fp8(w: jax.Array) -> jax.Array:
    """Round a (..., in, out) weight to float8_e4m3 with one scale per
    output column, returned dequantized in bfloat16."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (wf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * scale).astype(jnp.bfloat16)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """Rotate-half RoPE on (T, H, D) at positions 0..T-1."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


@partial(jax.jit, static_argnames=("s", "eps", "theta", "mode"))
def _logits(params, tokens, rows, s: Shapes, eps: float, theta: float,
            mode: str):
    """Logits (len(rows), vocab) of one sequence ``tokens`` (T,) at
    positions ``rows``."""
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    wcast = (lambda w: w.astype(jnp.float32)) if mode == "f32" else _fp8
    mm = partial(jnp.einsum, precision=HI,
                 preferred_element_type=jnp.float32)
    T, hd = tokens.shape[0], s.head_dim
    G = s.heads // s.kv_heads
    embed = wcast(params["embed"].T).T                    # scale per row
    x = jnp.take(embed, tokens, axis=0).astype(dt)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = _rms(x, lp["ln1"], eps)
        q = mm("td,dk->tk", h, wcast(a["wq"])).astype(dt).reshape(T, s.heads, hd)
        k = mm("td,dk->tk", h, wcast(a["wk"])).astype(dt).reshape(T, s.kv_heads, hd)
        v = mm("td,dk->tk", h, wcast(a["wv"])).astype(dt).reshape(T, s.kv_heads, hd)
        q = _rope(_rms(q, a["q_norm"], eps), theta)
        k = _rope(_rms(k, a["k_norm"], eps), theta)
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        sc = mm("qhd,khd->hqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = mm("hqk,khd->qhd", p.astype(dt), v).astype(dt).reshape(T, -1)
        x = x + mm("tq,qd->td", o, wcast(a["wo"])).astype(dt)
        h = _rms(x, lp["ln2"], eps)
        g = mm("td,df->tf", h, wcast(m["w_gate"]))
        u = mm("td,df->tf", h, wcast(m["w_up"]))
        hh = (jax.nn.silu(g) * u).astype(dt)
        return x + mm("tf,fd->td", hh, wcast(m["w_down"])).astype(dt), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    xr = _rms(jnp.take(x, rows, axis=0), params["final_norm"], eps)
    return mm("rd,vd->rv", xr, embed)


def served_gaps(params, s: Shapes, eps: float, theta: float,
                prompt: np.ndarray, served: np.ndarray, pad_to: int,
                control: bool = False):
    """For one request: the gap by which each served token's logit lies
    below the reference's best at its position, and (``control``) the
    same gap for the token the fp8 control puts first there.

    The sequence is the prompt plus the served tokens but the last,
    padded at the end to ``pad_to`` (causal, so padding changes nothing
    before it).  Returns (served gaps, control gaps or None) as float32
    arrays of len(served)."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n, T = len(served), len(seq)
    if T > pad_to:
        raise ValueError(f"sequence of {T} tokens above pad_to {pad_to}")
    tokens = jnp.asarray(np.pad(seq, (0, pad_to - T)))
    rows_np = len(prompt) - 1 + np.arange(n)
    n_rows = ROWS if n <= ROWS else pad_to          # one program per size
    rows = jnp.asarray(np.pad(rows_np, (0, n_rows - n), mode="edge"))
    ref = _logits(params, tokens, rows, s, eps, theta, "f32")[:n]
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], axis=-1)[:, 0]
    gaps = np.asarray(best - got)
    cgaps = None
    if control:
        low = _logits(params, tokens, rows, s, eps, theta, "fp8")[:n]
        pick = jnp.argmax(low, axis=-1)
        cgaps = np.asarray(best - jnp.take_along_axis(ref, pick[:, None],
                                                      axis=-1)[:, 0])
    return gaps, cgaps
