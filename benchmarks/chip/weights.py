"""Random weights of a Qwen3-style decoder, made from ``--seed`` on the
device in one jitted call, in the dtype they are served in.

The benchmark makes the weights itself: the program under test is handed
them, and the plain reference (``reference.py``) reads the same arrays,
so the reference takes nothing the program has made.  The tree is laid
out as the program's ``Model`` takes its parameters (layer weights stacked
on a leading layer axis); ``run.py`` checks that layout against the
program before it hands the weights over.

Scales: embedding N(0, 0.02) (the configuration's ``initializer_range``),
matmul weights N(0, 1/fan_in), norm weights 1.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks.chip.work import Shapes


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all of its bits kept (a
    plain ``jax.random.key`` keeps only the low 32 without x64)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("s", "dtype"))
def _make(key, s: Shapes, dtype):
    L, d, f = s.layers, s.d_model, s.d_ff
    q, kv, hd = s.heads * s.head_dim, s.kv_heads * s.head_dim, s.head_dim
    ks = iter(jax.random.split(key, 8))

    def dense(shape):
        return (jax.random.normal(next(ks), shape, dtype)
                * jnp.asarray(shape[-2] ** -0.5, dtype))

    ones = lambda *shape: jnp.ones(shape, dtype)
    return {
        "embed": jax.random.normal(next(ks), (s.vocab, d), dtype)
        * jnp.asarray(0.02, dtype),
        "final_norm": ones(d),
        "layers": {
            "ln1": ones(L, d), "ln2": ones(L, d),
            "attn": {"wq": dense((L, d, q)), "wk": dense((L, d, kv)),
                     "wv": dense((L, d, kv)), "wo": dense((L, q, d)),
                     "q_norm": ones(L, hd), "k_norm": ones(L, hd)},
            "mlp": {"w_gate": dense((L, d, f)), "w_up": dense((L, d, f)),
                    "w_down": dense((L, f, d))},
        },
    }


def make_weights(s: Shapes, seed: int, dtype="bfloat16"):
    return _make(seed_key(seed), s, jnp.dtype(dtype).name)
