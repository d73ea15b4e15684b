"""Gradient compression for slow cross-pod links (DCN at 1000+ nodes).

Two compressors, both with error feedback (the residual of this step's
quantization is added to next step's gradient, preserving convergence —
Karimireddy et al. 2019):

* ``int8``: per-block symmetric quantization (block = last axis), 4×
  byte reduction over fp32 (2× over bf16);
* ``topk``: magnitude top-k sparsification (k as a fraction), for extreme
  ratios.

``qdq_with_error_feedback`` is the grad_transform hook used by
``train_step`` — it models exactly what the wire sees.  The explicit
cross-pod collective lives in ``compressed_psum`` (shard_map over 'pod'),
exercised by the multi-device tests.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# int8 block quantization
# ---------------------------------------------------------------------------


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-row int8. Returns (q int8, scale f32 with last dim 1)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def qdq_int8(x: jax.Array) -> jax.Array:
    q, s = quantize_int8(x)
    return dequantize_int8(q, s).astype(x.dtype)


# ---------------------------------------------------------------------------
# top-k sparsification
# ---------------------------------------------------------------------------


def qdq_topk(x: jax.Array, fraction: float = 0.1) -> jax.Array:
    """Keep the top `fraction` entries by magnitude (per leaf), zero the rest."""
    xf = x.astype(jnp.float32)
    flat = xf.reshape(-1)
    k = max(1, int(flat.shape[0] * fraction))
    thresh = lax.top_k(jnp.abs(flat), k)[0][-1]
    kept = jnp.where(jnp.abs(flat) >= thresh, flat, 0.0)
    return kept.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# error feedback wrapper (the grad_transform hook)
# ---------------------------------------------------------------------------


class ErrorFeedbackState(NamedTuple):
    residual: Any   # tree like grads


def make_ef_transform(
    method: str = "int8", topk_fraction: float = 0.1
) -> Tuple[Callable, Callable]:
    """Returns (init_fn(grads_like) -> state, transform(grads, state) ->
    (compressed_grads, new_state))."""

    def compress(leaf):
        if method == "int8":
            return qdq_int8(leaf)
        if method == "topk":
            return qdq_topk(leaf, topk_fraction)
        raise ValueError(method)

    def init_fn(grads_like):
        return ErrorFeedbackState(
            residual=jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)
        )

    def transform(grads, state: ErrorFeedbackState):
        with_res = jax.tree.map(
            lambda g, r: g.astype(jnp.float32) + r, grads, state.residual
        )
        compressed = jax.tree.map(compress, with_res)
        new_res = jax.tree.map(lambda w, c: w - c.astype(jnp.float32), with_res, compressed)
        out = jax.tree.map(lambda g, c: c.astype(g.dtype), grads, compressed)
        return out, ErrorFeedbackState(residual=new_res)

    return init_fn, transform


# ---------------------------------------------------------------------------
# explicit compressed cross-pod all-reduce (shard_map over 'pod')
# ---------------------------------------------------------------------------


def compressed_psum(tree, mesh, axis: str = "pod"):
    """int8-compress each pod's contribution, psum int32, dequantize.

    Wire bytes over the pod axis: 1 byte/element + 4/row scale, vs 4
    bytes/element for fp32 all-reduce — the §Perf collective-term lever.
    """

    def body(*leaves):
        out = []
        for leaf in leaves:
            q, s = quantize_int8(leaf)
            qsum = lax.psum(q.astype(jnp.int32), axis)
            ssum = lax.pmax(s, axis)           # conservative shared scale
            n = lax.psum(jnp.ones((), jnp.float32), axis)
            out.append((qsum.astype(jnp.float32) * ssum / n).astype(leaf.dtype))
        return tuple(out)

    leaves, treedef = jax.tree.flatten(tree)
    specs = tuple(P(*(None,) * leaf.ndim) for leaf in leaves)
    out = jax.shard_map(
        body, mesh=mesh, in_specs=specs, out_specs=specs, check_vma=False
    )(*leaves)
    return treedef.unflatten(list(out))
