"""Qwen3 (``model_type: qwen3``): a dense decoder with GQA attention,
per-head q/k RMSNorm, rotate-half RoPE, a SwiGLU MLP in every layer and a
tied embedding.  Everything the harness needs of the architecture, behind
the interface ``work.py`` states:

* ``shapes(config)``  -- the work model (``Shapes``);
* ``overrides(config)`` -- the program's ``ModelConfig`` fields;
* ``make_weights(config, seed)`` -- random weights on the device;
* ``served_gaps(...)`` -- the float32 reference and its float8 control;
* ``tiny(config)`` -- the CPU rehearsal's shrink.

The reference forward, from the published architecture, importing
nothing of the program:

  x = embed[tokens]
  per layer:  h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv
              q, k = rmsnorm per head (q_norm, k_norm), then RoPE
              (rotate-half, theta from the configuration)
              a = softmax(q k^T / sqrt(head_dim), causal) v   (GQA)
              x = x + a Wo
              h = rmsnorm(x) ; x = x + (silu(h Wg) * h Wu) Wd
  logits = rmsnorm(x) embed^T            (tied embedding)

It runs layer by layer inside one scan, casting each layer's weights up
as it goes, so the float32 copy of the whole model never exists.
"""
from __future__ import annotations

from functools import partial
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, weights, work


# -- the work model ----------------------------------------------------------
class Shapes(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2

    @property
    def matmul_params(self) -> int:
        """Parameters that take part in a matmul per token (LM head
        included): every layer's attention projections and dense MLP."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        per_layer = self.d_model * q + 2 * self.d_model * kv + q * self.d_model \
            + 3 * self.d_model * self.d_ff
        return self.layers * per_layer + self.vocab * self.d_model

    @property
    def weight_bytes(self) -> int:
        """Bytes of every weight read once (tied embedding counted once)."""
        norms = self.layers * (2 * self.d_model + 2 * self.head_dim) + self.d_model
        return (self.matmul_params + norms) * self.dtype_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    def tokens_flops(self, contexts: Iterable[int]) -> float:
        return work.tokens_flops(self.matmul_params, self.layers * self.heads
                                 * self.head_dim, contexts)

    def decode_steps_bytes(self, steps: int, contexts: Iterable[int]) -> float:
        """Every weight once a step (a dense model's step reads them all)."""
        return work.decode_steps_bytes(self.weight_bytes, self.kv_bytes_per_token,
                                       steps, contexts)


def shapes(config: dict) -> Shapes:
    """The shapes of a configuration file (Hugging Face key names)."""
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError(f"{config['num_attention_heads']} query heads do not "
                         f"share {config['num_key_value_heads']} kv heads evenly")
    return Shapes(
        layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        dtype_bytes=work.DTYPE_BYTES[config["torch_dtype"]])


# -- the program's configuration ---------------------------------------------
def overrides(config: dict) -> dict:
    """The program's ``ModelConfig`` fields, as the configuration file
    states them (the file is the configuration as run)."""
    return dict(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        qk_norm=True, rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"], use_pallas=False,
    )


def tiny(config: dict) -> dict:
    """The configuration at the CPU rehearsal's size: every width, the
    depth and the vocabulary shrunk, the rest as the file states it."""
    return dict(config, num_hidden_layers=2, hidden_size=128,
                intermediate_size=256, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, vocab_size=512)


# -- weights -----------------------------------------------------------------
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


def make_weights(config: dict, seed: int):
    """The parameter tree as the program's ``Model`` takes it (layer
    weights stacked on a leading layer axis), in the served dtype."""
    return _make(weights.seed_key(seed), shapes(config),
                 jnp.dtype(config["torch_dtype"]).name)


# -- the reference -----------------------------------------------------------
@partial(jax.jit, static_argnames=("s", "eps", "theta", "mode"))
def _logits(params, tokens, rows, s: Shapes, eps: float, theta: float,
            mode: str):
    """Logits (len(rows), vocab) of one sequence ``tokens`` (T,) at
    positions ``rows``."""
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    wcast = (lambda w: w.astype(jnp.float32)) if mode == "f32" else reference.fp8
    mm = partial(jnp.einsum, precision=reference.HI,
                 preferred_element_type=jnp.float32)
    rms, rope = reference.rms, reference.rope
    T, hd = tokens.shape[0], s.head_dim
    G = s.heads // s.kv_heads
    embed = wcast(params["embed"].T).T                    # scale per row
    x = jnp.take(embed, tokens, axis=0).astype(dt)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, lp):
        a, m = lp["attn"], lp["mlp"]
        h = rms(x, lp["ln1"], eps)
        q = mm("td,dk->tk", h, wcast(a["wq"])).astype(dt).reshape(T, s.heads, hd)
        k = mm("td,dk->tk", h, wcast(a["wk"])).astype(dt).reshape(T, s.kv_heads, hd)
        v = mm("td,dk->tk", h, wcast(a["wv"])).astype(dt).reshape(T, s.kv_heads, hd)
        q = rope(rms(q, a["q_norm"], eps), theta)
        k = rope(rms(k, a["k_norm"], eps), theta)
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        sc = mm("qhd,khd->hqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = mm("hqk,khd->qhd", p.astype(dt), v).astype(dt).reshape(T, -1)
        x = x + mm("tq,qd->td", o, wcast(a["wo"])).astype(dt)
        h = rms(x, lp["ln2"], eps)
        g = mm("td,df->tf", h, wcast(m["w_gate"]))
        u = mm("td,df->tf", h, wcast(m["w_up"]))
        hh = (jax.nn.silu(g) * u).astype(dt)
        return x + mm("tf,fd->td", hh, wcast(m["w_down"])).astype(dt), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    xr = rms(jnp.take(x, rows, axis=0), params["final_norm"], eps)
    return mm("rd,vd->rv", xr, embed)


def served_gaps(params, config: dict, prompt: np.ndarray, served: np.ndarray,
                max_len: int, control: bool = False):
    """For one request: the gap by which each served token's logit lies
    below the float32 reference's best at its position, and (``control``)
    the gap of the token the float8 control puts first there
    (``reference.served_gaps``)."""
    logits = partial(_logits, params, s=shapes(config),
                     eps=float(config["rms_norm_eps"]),
                     theta=float(config["rope_theta"]))
    return reference.served_gaps(logits, prompt, served, max_len, control)
