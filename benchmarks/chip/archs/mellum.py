"""Mellum 2 (``model_type: mellum``): a decoder whose every layer is GQA
attention, windowed or full, and a sparse MLP of routed experts; untied
embedding.  Everything the harness needs of the architecture, behind the
interface ``work.py`` states (``shapes``, ``overrides``, ``make_weights``,
``served_gaps``, ``tiny``).

The configuration holds one chip's share of an expert-parallel
deployment: ``num_experts`` counts the experts held here (ids
``[held_experts[0], held_experts[1])`` of ``expert_share``), the router
keeps its published ``expert_share.router_outputs``.

The reference forward, from the published configuration, importing
nothing of the program:

  x = embed[tokens]
  per layer (``layer_types``):
      h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv          (no q/k norm)
      RoPE, rotate-half: "sliding_attention" layers default RoPE at
        theta; "full_attention" layers YaRN (``rope_parameters``): inverse
        frequencies theta^(-2i/d) blended with the same over ``factor`` by
        a linear ramp between the correction dimensions of ``beta_fast``
        and ``beta_slow`` at ``original_max_position_embeddings`` (floor
        and ceiling taken), cos and sin both times ``attention_factor``
      a = softmax(q k^T / sqrt(head_dim), mask) v  (GQA); mask causal, and
        for sliding layers keys after position - ``sliding_window`` only
      x = x + a Wo
      h = rmsnorm(x) ; p = softmax(h Wr) over every router output, in
        float32 ; the top ``num_experts_per_tok`` picks, gates p / sum p
      x = x + sum over picks on held experts of gate * (silu(h Wg_e) *
        h Wu_e) Wd_e                        (picks on other chips add 0)
  logits = rmsnorm(x) lm_head

Every held expert runs on every token, weighted by its gate (0 where not
picked), which is the routed sum written plainly.  It runs layer by layer
inside one scan, casting each layer's weights up as it goes.

Work model.  Per token the matmuls are the attention projections, the
router and, of the experts, the expected picks that land here:
``num_experts_per_tok x num_experts / router_outputs`` (8 x 16/64 = 2) of
``3 x hidden x moe_intermediate`` each, plus the LM head.  A decode step
reads every weight outside the experts, and of the held experts those its
tokens can touch: with n active slots and uniform routing,
``held x (1 - (1 - k/E)^n)`` a layer (E router outputs, k picks; 14.1 of
16 at n = 16), n the step's mean active slots.  Attention of a token at
context c reads c positions in a full layer and min(c, window) in a
windowed one, in FLOPs and in cache bytes alike.
"""
from __future__ import annotations

import json
import math
from functools import partial
from typing import Iterable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, weights, work

ROUTER_BYTES = 4          # the router is kept and run in float32


# -- the work model ----------------------------------------------------------
class Shapes(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    expert_ff: int
    experts_held: int
    router_outputs: int
    top_k: int
    vocab: int
    window: int
    windowed_layers: int
    dtype_bytes: int = 2

    @property
    def _attn_params(self) -> int:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return 2 * self.d_model * q + 2 * self.d_model * kv

    @property
    def _expert_params(self) -> int:
        return 3 * self.d_model * self.expert_ff

    @property
    def picks_here(self) -> float:
        """Picks of a token that land on held experts, on average."""
        return self.top_k * self.experts_held / self.router_outputs

    @property
    def matmul_params(self) -> int:
        """Parameters in a matmul per token: attention, router, the picks
        that land here, and the LM head."""
        per_layer = (self._attn_params + self.d_model * self.router_outputs
                     + round(self.picks_here * self._expert_params))
        return self.layers * per_layer + self.vocab * self.d_model

    def _step_bytes(self, experts_touched: float) -> float:
        """Weight bytes a step reads with ``experts_touched`` held experts a
        layer: all but the experts (the embedding's rows aside), and those."""
        per_layer = (self._attn_params * self.dtype_bytes
                     + self.d_model * self.router_outputs * ROUTER_BYTES
                     + 2 * self.d_model * self.dtype_bytes
                     + experts_touched * self._expert_params * self.dtype_bytes)
        head = (self.vocab * self.d_model + self.d_model) * self.dtype_bytes
        return self.layers * per_layer + head

    @property
    def weight_bytes(self) -> int:
        """Bytes of every weight (both embeddings, every held expert)."""
        return int(self._step_bytes(self.experts_held)
                   + self.vocab * self.d_model * self.dtype_bytes)

    @property
    def kv_bytes_per_token(self) -> int:
        """Every layer keeps every position."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    def _context_sum(self, contexts: Iterable[int]) -> int:
        """Positions attended, summed over layers and tokens."""
        full = self.layers - self.windowed_layers
        return sum(full * c + self.windowed_layers * min(c, self.window)
                   for c in contexts)

    def experts_touched(self, tokens: float) -> float:
        """Held experts ``tokens`` tokens reach in a layer, on average, under
        uniform routing."""
        miss = 1.0 - self.top_k / self.router_outputs
        return self.experts_held * (1.0 - miss ** tokens)

    def tokens_flops(self, contexts: Iterable[int]) -> float:
        contexts = list(contexts)
        return (2.0 * self.matmul_params * len(contexts)
                + 4.0 * self.heads * self.head_dim * self._context_sum(contexts))

    def decode_steps_bytes(self, steps: int, contexts: Iterable[int]) -> float:
        """``steps`` steps, each reading the weights its mean active slots'
        tokens can touch, and the live cache of every token."""
        contexts = list(contexts)
        per_step = self._step_bytes(self.experts_touched(len(contexts)
                                                         / max(steps, 1)))
        per_pos = self.kv_bytes_per_token // self.layers
        return float(steps) * per_step + float(self._context_sum(contexts) * per_pos)


def _share(config: dict) -> Tuple[int, int, int]:
    """(router outputs, first held expert, experts held)."""
    share = config["expert_share"]
    lo, hi = share["held_experts"]
    if hi - lo != config["num_experts"] or not 0 <= lo < hi <= share["router_outputs"]:
        raise ValueError(f"held experts {share['held_experts']} do not match "
                         f"num_experts {config['num_experts']} of "
                         f"{share['router_outputs']}")
    return share["router_outputs"], lo, hi - lo


def _windowed(config: dict) -> int:
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError(f"{len(types)} layer types for "
                         f"{config['num_hidden_layers']} layers")
    return sum(t == "sliding_attention" for t in types)


def shapes(config: dict) -> Shapes:
    """The shapes of a configuration file (Hugging Face key names)."""
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError(f"{config['num_attention_heads']} query heads do not "
                         f"share {config['num_key_value_heads']} kv heads evenly")
    if set(config["mlp_layer_types"]) != {"sparse"} or not config["norm_topk_prob"]:
        raise ValueError("only sparse MLPs with renormalised gates are built")
    router, _, held = _share(config)
    return Shapes(
        layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_ff=config["moe_intermediate_size"], experts_held=held,
        router_outputs=router, top_k=config["num_experts_per_tok"],
        vocab=config["vocab_size"], window=config["sliding_window"],
        windowed_layers=_windowed(config),
        dtype_bytes=work.DTYPE_BYTES[config["torch_dtype"]])


# -- the program's configuration ---------------------------------------------
def _rope(config: dict):
    """(theta of the sliding layers, theta and YaRN terms of the full ones)."""
    rp = config["rope_parameters"]
    slide, full = rp["sliding_attention"], rp["full_attention"]
    if slide["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("sliding layers with default RoPE and full layers "
                         "with YaRN are built")
    return float(slide["rope_theta"]), full


def overrides(config: dict) -> dict:
    """The program's ``ModelConfig`` fields, as the configuration file
    states them (the file is the configuration as run)."""
    theta, full = _rope(config)
    if float(full["rope_theta"]) != theta:
        raise ValueError("the program runs one RoPE theta for both layer kinds")
    router, first, held = _share(config)
    return dict(
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=config["moe_intermediate_size"], vocab_size=config["vocab_size"],
        qk_norm=False, rope_theta=theta,
        layer_types=tuple(config["layer_types"]),
        layer_window=config["sliding_window"],
        rope_yarn=(float(full["factor"]),
                   float(full["original_max_position_embeddings"]),
                   float(full["beta_fast"]), float(full["beta_slow"]),
                   float(full["attention_factor"])),
        n_experts=router, experts_held=held, expert_offset=first,
        top_k=config["num_experts_per_tok"],
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["torch_dtype"], use_pallas=False,
    )


def tiny(config: dict) -> dict:
    """The configuration at the CPU rehearsal's size: every width, the
    vocabulary and the window shrunk; one whole period of layers (three
    windowed, one full); the expert share kept (a quarter of the router's
    outputs held, an eighth of them picked per token); the window shorter
    than most of the rehearsal's prompts.  Served in float32: in bfloat16
    at this size, near-ties in routing move single requests' gaps to
    0.03-0.19 (2 of 30 requests of one rehearsal over its 0.02 limit), so
    which requests finish in the rehearsal's seconds would decide it."""
    period = config["layer_types"][:4]
    return dict(config, num_hidden_layers=4, layer_types=period,
                torch_dtype="float32",
                mlp_layer_types=["sparse"] * 4, hidden_size=128,
                moe_intermediate_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, vocab_size=512,
                sliding_window=32, num_experts=8, num_experts_per_tok=4,
                expert_share=dict(config["expert_share"], router_outputs=32,
                                  held_experts=[0, 8]))


# -- weights -----------------------------------------------------------------
@partial(jax.jit, static_argnames=("s", "dtype"))
def _make(key, s: Shapes, dtype):
    L, d, f, E = s.layers, s.d_model, s.expert_ff, s.experts_held
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    ks = iter(jax.random.split(key, 10))

    def dense(shape, dt=dtype):
        return (jax.random.normal(next(ks), shape, dt)
                * jnp.asarray(shape[-2] ** -0.5, dt))

    ones = lambda *shape: jnp.ones(shape, dtype)
    return {
        "embed": jax.random.normal(next(ks), (s.vocab, d), dtype)
        * jnp.asarray(0.02, dtype),
        "final_norm": ones(d),
        "lm_head": dense((d, s.vocab)),
        "layers": {
            "ln1": ones(L, d), "ln2": ones(L, d),
            "attn": {"wq": dense((L, d, q)), "wk": dense((L, d, kv)),
                     "wv": dense((L, d, kv)), "wo": dense((L, q, d))},
            "moe": {"router": dense((L, d, s.router_outputs), jnp.float32),
                    "w_gate": dense((L, E, d, f)), "w_up": dense((L, E, d, f)),
                    "w_down": dense((L, E, f, d))},
        },
    }


def make_weights(config: dict, seed: int):
    """The parameter tree as the program's ``Model`` takes it (layer
    weights stacked on a leading layer axis; the held experts' on a second
    axis), in the served dtype, the router in float32."""
    return _make(weights.seed_key(seed), shapes(config),
                 jnp.dtype(config["torch_dtype"]).name)


# -- the reference -----------------------------------------------------------
def yarn_inv_freq(dim: int, theta: float, factor: float, original: float,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies, written from its definition (Hugging
    Face ``_compute_yarn_parameters``, truncate true)."""
    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low),
                   0, 1)
    extrapolation_factor = 1.0 - ramp
    return (interpolation * (1 - extrapolation_factor)
            + extrapolation * extrapolation_factor).astype(np.float32)


def _rope_tables(config: dict, T: int):
    """Per layer kind, (cos, sin) of shape (T, 1, D/2) at positions 0..T-1."""
    theta, full = _rope(config)
    D = config["head_dim"]
    default = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    yarn = yarn_inv_freq(D, float(full["rope_theta"]), float(full["factor"]),
                         float(full["original_max_position_embeddings"]),
                         float(full["beta_fast"]), float(full["beta_slow"]))
    pos = np.arange(T, dtype=np.float64)[:, None]

    def table(inv, scale):
        ang = jnp.asarray((pos * inv[None, :]).astype(np.float32))
        return (jnp.cos(ang)[:, None, :] * scale, jnp.sin(ang)[:, None, :] * scale)

    return {"sliding": table(default, 1.0),
            "full": table(yarn.astype(np.float64), float(full["attention_factor"]))}


def _rotate(x, cos, sin):
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


@partial(jax.jit, static_argnames=("s", "eps", "offset", "config_key", "mode"))
def _logits(params, tokens, rows, s: Shapes, eps: float, offset: int,
            config_key, mode: str):
    """Logits (len(rows), vocab) of one sequence ``tokens`` (T,) at
    positions ``rows``."""
    config = dict(config_key)
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    wcast = (lambda w: w.astype(jnp.float32)) if mode == "f32" else reference.fp8
    mm = partial(jnp.einsum, precision=reference.HI,
                 preferred_element_type=jnp.float32)
    rms = reference.rms
    T, hd = tokens.shape[0], s.head_dim
    G = s.heads // s.kv_heads
    x = jnp.take(wcast(params["embed"].T).T, tokens, axis=0).astype(dt)
    ropes = _rope_tables(config, T)
    qpos = jnp.arange(T)[:, None]
    kpos = jnp.arange(T)[None, :]
    causal = kpos <= qpos
    windowed = causal & (kpos > qpos - s.window)
    sliding = jnp.asarray([t == "sliding_attention" for t in config["layer_types"]])

    def layer(x, inputs):
        lp, slide = inputs
        a, m = lp["attn"], lp["moe"]
        h = rms(x, lp["ln1"], eps)
        q = mm("td,dk->tk", h, wcast(a["wq"])).astype(dt).reshape(T, s.heads, hd)
        k = mm("td,dk->tk", h, wcast(a["wk"])).astype(dt).reshape(T, s.kv_heads, hd)
        v = mm("td,dk->tk", h, wcast(a["wv"])).astype(dt).reshape(T, s.kv_heads, hd)
        cos = jnp.where(slide, ropes["sliding"][0], ropes["full"][0])
        sin = jnp.where(slide, ropes["sliding"][1], ropes["full"][1])
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        sc = mm("qhd,khd->hqk", q, k) / np.sqrt(hd)
        mask = jnp.where(slide, windowed, causal)
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        o = mm("hqk,khd->qhd", p.astype(dt), v).astype(dt).reshape(T, -1)
        x = x + mm("tq,qd->td", o, wcast(a["wo"])).astype(dt)
        h = rms(x, lp["ln2"], eps)
        probs = jax.nn.softmax(mm("td,de->te", h, wcast(m["router"])), axis=-1)
        top, picked = jax.lax.top_k(probs, s.top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        local = picked - offset                               # (T, k)
        gate = jnp.sum(jnp.where(local[:, :, None] == jnp.arange(s.experts_held),
                                 top[:, :, None], 0.0), axis=1)   # (T, held)
        g = mm("td,edf->etf", h, wcast(m["w_gate"]))
        u = mm("td,edf->etf", h, wcast(m["w_up"]))
        y = mm("etf,efd->etd", (jax.nn.silu(g) * u).astype(dt), wcast(m["w_down"]))
        return x + jnp.einsum("etd,te->td", y, gate).astype(dt), None

    x, _ = jax.lax.scan(layer, x, (params["layers"], sliding))
    xr = rms(jnp.take(x, rows, axis=0), params["final_norm"], eps)
    return mm("rd,dv->rv", xr, wcast(params["lm_head"]))


def served_gaps(params, config: dict, prompt: np.ndarray, served: np.ndarray,
                max_len: int, control: bool = False):
    """For one request: the gap by which each served token's logit lies
    below the float32 reference's best at its position, and (``control``)
    the gap of the token the float8 control puts first there
    (``reference.served_gaps``)."""
    _, first, _ = _share(config)
    ref_config = {"head_dim": config["head_dim"],
                  "layer_types": config["layer_types"],
                  "rope_parameters": config["rope_parameters"]}
    logits = partial(_logits, params, s=shapes(config),
                     eps=float(config["rms_norm_eps"]), offset=first,
                     config_key=_Config(ref_config))
    return reference.served_gaps(logits, prompt, served, max_len, control)


class _Config(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))

    def __eq__(self, other):
        return json.dumps(self, sort_keys=True) == json.dumps(other, sort_keys=True)
