"""Decoder / encoder transformer assembly with two-level layer scan.

Layers are stacked (leading dim L) and folded as L = G × Lg with G ≈ √L.
The forward runs ``scan(checkpoint(group), scan(checkpoint(layer)))``:
HLO size is O(1) in depth (one group body, one layer body) and training
memory is O(G·|x| + Lg·|x|) residuals — the √L remat policy sized in
DESIGN.md §5 so llama3-405b train_4k fits a 16 GB v5e chip.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import attention, layers, moe


def factor_layers(L: int, group: int = 0) -> Tuple[int, int]:
    """L = G × Lg.  Default G ≈ √L; ``group`` forces Lg (layers per remat
    group) when it divides L — fewer groups = smaller carry stacks at the
    cost of a longer recompute window (llama3 §Perf lever)."""
    if group and L % group == 0:
        return L // group, group
    best = (1, L)
    for g in range(1, L + 1):
        if L % g == 0 and abs(g - math.isqrt(L)) < abs(best[0] - math.isqrt(L)):
            best = (g, L // g)
    return best


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_layer(key: jax.Array, cfg: ModelConfig, dtype) -> Dict:
    ka, km = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": attention.init_attn_params(ka, cfg, dtype),
    }
    if cfg.is_moe:
        p["moe"] = moe.init_moe_params(km, cfg, dtype)
    elif cfg.mlp_type == "gelu":
        k1, k2 = jax.random.split(km, 2)
        p["mlp"] = {
            "w_up": layers.dense_init(k1, (cfg.d_model, cfg.d_ff), dtype),
            "w_down": layers.dense_init(k2, (cfg.d_ff, cfg.d_model), dtype),
        }
    else:
        k1, k2, k3 = jax.random.split(km, 3)
        p["mlp"] = {
            "w_gate": layers.dense_init(k1, (cfg.d_model, cfg.d_ff), dtype),
            "w_up": layers.dense_init(k2, (cfg.d_model, cfg.d_ff), dtype),
            "w_down": layers.dense_init(k3, (cfg.d_ff, cfg.d_model), dtype),
        }
    return p


def init_transformer_params(key: jax.Array, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "layers": jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.input_mode == "tokens":
        params["embed"] = layers.embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dtype)
    if cfg.family == "encoder":
        params["head"] = layers.dense_init(k_head, (cfg.d_model, cfg.vocab_size), dtype)
    elif not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(k_head, (cfg.d_model, cfg.vocab_size), dtype)
    return params


# ---------------------------------------------------------------------------
# forward (sequence form: training & prefill)
# ---------------------------------------------------------------------------


def seq_shard(x: jax.Array, mesh) -> jax.Array:
    """Megatron-style sequence parallelism for inter-block activations:
    (B, S, d) sharded (batch×seq) so the √L-remat residual stacks are 1/TP
    the size (llama3-405b: 15 GB → <1 GB/device; EXPERIMENTS.md §Dry-run).
    Norms/residual-adds stay local; XLA turns the TP psums into
    reduce-scatter + all-gather pairs around attention/MLP."""
    if mesh is None or "model" not in mesh.axis_names:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    B, S, _ = x.shape
    if S % mesh.shape["model"] != 0:
        return x
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    bspec = ba if (B % nb == 0 and B >= nb) else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(bspec, "model", None))
    )


def full_activation(x: jax.Array, mesh) -> jax.Array:
    """All-gather the sequence dim before a projection block (Megatron-SP:
    the AG here + the RS back to seq-sharded at the block output together
    cost what a single TP all-reduce would)."""
    if mesh is None or "model" not in mesh.axis_names:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    B = x.shape[0]
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    bspec = ba if (B % nb == 0 and B >= nb) else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(bspec, None, None))
    )


# a full layer's "window": wider than any cache, so its mask drops nothing
NO_WINDOW = 1 << 30


def layer_kinds(cfg: ModelConfig) -> Optional[Dict[str, jax.Array]]:
    """Each layer's attention kind, stacked on a leading layer axis to scan
    beside the weights: ``window`` (L,) int32 (``NO_WINDOW`` for a full
    layer), ``inv_freq`` (L, head_dim/2) and ``rope_scale`` (L,) float32 —
    default RoPE for windowed layers, the configured YaRN for full ones.
    None where every layer attends alike, so such a model's layer loop
    carries nothing new."""
    if not cfg.has_layer_kinds:
        return None
    hd = cfg.resolved_head_dim
    default = (1.0 / cfg.rope_theta ** (np.arange(0, hd, 2, dtype=np.float32)
                                        / hd)).astype(np.float32)
    full_freq, full_scale = default, 1.0
    if cfg.rope_yarn:
        factor, orig, beta_fast, beta_slow, attn_factor = cfg.rope_yarn
        full_freq = layers.yarn_frequencies(hd, cfg.rope_theta, factor, orig,
                                            beta_fast, beta_slow)
        full_scale = attn_factor
    windows = cfg.layer_windows
    return {
        "window": jnp.asarray([w or NO_WINDOW for w in windows], jnp.int32),
        "inv_freq": jnp.asarray(np.stack([default if w else full_freq
                                          for w in windows])),
        "rope_scale": jnp.asarray([1.0 if w else full_scale for w in windows],
                                  jnp.float32),
    }


def mlp_block(lp, h, cfg: ModelConfig, mesh):
    """Post-attention feed-forward dispatch (MoE / gelu / swiglu) shared by
    the sequence and cached-step layer bodies.  Returns (m, aux).  A cached
    step with fused swiglu weights consumes the pre-fused [w_gate|w_up]
    matrix instead (``run_layers_kv``)."""
    if cfg.is_moe:
        m, aux, _ = moe.moe_block(lp["moe"], h, cfg, mesh)
        return m, aux
    if cfg.mlp_type == "gelu":
        hu = jnp.einsum("...d,df->...f", h, lp["mlp"]["w_up"])
        hu = jax.nn.gelu(hu.astype(jnp.float32)).astype(h.dtype)
        m = jnp.einsum("...f,fd->...d", hu, lp["mlp"]["w_down"])
    else:
        m = layers.swiglu(h, lp["mlp"]["w_gate"], lp["mlp"]["w_up"],
                          lp["mlp"]["w_down"])
    return m, jnp.zeros((), jnp.float32)


def _layer_seq(lp, x, cfg: ModelConfig, mesh, return_cache: bool, kind=None):
    """One transformer layer on (B,S,d). Returns (x, (cache_k, cache_v), aux).

    With ``cfg.seq_parallel`` (a §Perf experiment), inter-block activations
    live sequence-sharded (Megatron-SP); measured on the CPU-backend SPMD
    partitioner this *raised* collective and FLOP terms (see EXPERIMENTS.md
    §Perf), so the default keeps activations replicated over 'model' and
    attacks residual memory via the chunked optimizer + remat policy."""
    sp = cfg.seq_parallel
    x = seq_shard(x, mesh) if sp else x
    h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if sp:
        h = full_activation(h, mesh)
    a, cache = attention.attention_block(
        lp["attn"], h, cfg, return_cache=return_cache, mesh=mesh, kind=kind,
    )
    x = x + (seq_shard(a, mesh) if sp else a)
    h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if sp:
        h = full_activation(h, mesh)
    m, aux = mlp_block(lp, h, cfg, mesh)
    x = x + (seq_shard(m, mesh) if sp else m)
    if return_cache:
        return x, (cache.k, cache.v), aux
    return x, None, aux


def run_layers_seq(
    params: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    mesh=None,
    *,
    return_cache: bool = False,
):
    """Two-level scanned layer stack. Returns (x, caches|None, aux)."""
    L = cfg.n_layers
    G, Lg = factor_layers(L, cfg.scan_group)
    grouped = jax.tree.map(
        lambda a: a.reshape(G, Lg, *a.shape[1:]),
        (params["layers"], layer_kinds(cfg)),
    )

    def layer_body(carry, inputs):
        x, aux = carry
        lp, kind = inputs
        x, cache, a = _layer_seq(lp, x, cfg, mesh, return_cache, kind)
        return (x, aux + a), cache

    def group_body(carry, gp):
        return lax.scan(jax.checkpoint(layer_body), carry, gp)

    (x, aux), caches = lax.scan(
        jax.checkpoint(group_body) if cfg.remat else group_body,
        (x, jnp.zeros((), jnp.float32)),
        grouped,
    )
    if return_cache and caches is not None:
        caches = jax.tree.map(
            lambda a: a.reshape(L, *a.shape[2:]), caches
        )
    return x, caches, aux


# ---------------------------------------------------------------------------
# cached steps (decode, mixed, continuation prefill) through all layers
# ---------------------------------------------------------------------------


def fused_decode_weights(params: Dict, cfg: ModelConfig):
    """Precompute the fused decode projection matrices on the stacked
    (L, ...) layer leaves: wqkv = [wq|wk|wv] and (swiglu only)
    w_gu = [w_gate|w_up].

    Call this OUTSIDE the token-generation scan (see ServingEngine) and
    pass the result to ``run_layers_kv``: the concats then run once per
    generate dispatch and enter the token loop as invariant operands, and
    the layer loop slices layer ``i``'s rows of them as it slices the
    other stacked weights (the KV cache, by contrast, rides the layer
    loop's carry).  Computing them *inside* the token loop re-materializes
    the concatenated matrices every token, which measurably costs decode
    throughput."""
    with jax.named_scope("fuse_weights"):
        wqkv = attention.fuse_qkv_weights(params["layers"]["attn"])
        w_gu = None
        if not cfg.is_moe and cfg.mlp_type != "gelu":
            w_gu = layers.fuse_gate_up_weights(
                params["layers"]["mlp"]["w_gate"], params["layers"]["mlp"]["w_up"]
            )
    return {"wqkv": wqkv, "w_gu": w_gu}


def run_layers_kv(
    params: Dict,
    x: jax.Array,                # (B, T, d): the new tokens' embeddings
    cache_k: jax.Array,          # (L, B, Sc, Hkv, Dh) or paged (L, P, ps, Hkv, Dh)
    cache_v: jax.Array,
    attn: Callable,              # attn(p, h, KVCache, layer, wqkv, kind) -> (a, KVCache)
    cfg: ModelConfig,
    mesh=None,
    fused: Optional[Dict] = None,   # fused_decode_weights(params, cfg)
):
    """The layer loop of every step that extends a KV cache: decode, the
    mixed (chunked prefill + decode) step and continuation prefill.

    The stacked cache rides the loop's carry beside the activations.  Layer
    ``i`` writes its new rows into it at index ``i`` and attends over that
    layer (``attn``: ``attention.attention_decode``, ``attention_mixed`` or
    ``attention_prefill_paged`` with their other arguments bound), so no
    per-layer copy of the cache is sliced out and no (L, ...) copy is
    stacked back: the final carry is the new cache, and under the caller's
    donation it is the input buffer updated in place.

    ``fused`` selects the fused QKV and gate/up projections of the decode
    hot path; without it a layer runs the plain ``mlp_block`` (the
    continuation prefill).  Where layers differ in window or RoPE, each
    layer's kind (``layer_kinds``) rides the scan beside its weights and
    reaches ``attn``; otherwise ``attn`` gets None.

    Returns (x, new_k, new_v, expert_rows): the (token, held expert) rows
    the expert layers computed, summed over the layers — None for a model
    without experts."""
    count = cfg.is_moe and mesh is None

    def body(carry, inputs):
        x, k, v, rows = carry
        lp, i, w, kind = inputs
        h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
        with jax.named_scope("attn"):
            a, kv = attn(lp["attn"], h, attention.KVCache(k=k, v=v), i,
                         None if w is None else w["wqkv"], kind)
        x = x + a
        h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            if count:
                m, _, r = moe.moe_block(lp["moe"], h, cfg, mesh)
                rows = rows + r
            elif w is None or w["w_gu"] is None:
                m, _ = mlp_block(lp, h, cfg, mesh)
            else:
                m = layers.swiglu_fused(h, w["w_gu"], lp["mlp"]["w_down"])
        x = x + m
        return (x, kv.k, kv.v, rows), None

    # small unroll: decode bodies are tiny, so the layer loop's while
    # overhead is material on CPU/small models; 4 keeps HLO size bounded
    L = cfg.n_layers
    rows0 = jnp.zeros((), jnp.int32) if count else None
    (x, k, v, rows), _ = lax.scan(
        body, (x, cache_k, cache_v, rows0),
        (params["layers"], jnp.arange(L, dtype=jnp.int32), fused,
         layer_kinds(cfg)),
        unroll=min(4, L),
    )
    return x, k, v, rows


# ---------------------------------------------------------------------------
# heads / losses
# ---------------------------------------------------------------------------


def logits_from_hidden(
    params: Dict, x: jax.Array, cfg: ModelConfig, mesh=None
) -> jax.Array:
    with jax.named_scope("lm_head"):
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.family == "encoder":
            w = params["head"]
        else:
            w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, w)
    # pin vocab-sharded logits: without this XLA may replicate (B,S,V) fp32
    # during the loss — tens of GB/device at 128k-150k vocabs.
    if mesh is not None and "model" in mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P

        V = logits.shape[-1]
        B = logits.shape[0]
        ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        nb = 1
        for a in ba:
            nb *= mesh.shape[a]
        bspec = ba if (B % nb == 0 and B >= nb) else None
        vspec = "model" if V % mesh.shape["model"] == 0 else None
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, P(bspec, None, vspec))
        )
    return logits


def softmax_xent(logits: jax.Array, targets: jax.Array,
                 mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean CE over valid positions; fp32; V may be model-sharded.

    The gold logit is selected with an iota-compare mask (elementwise on the
    sharded vocab dim) rather than take_along_axis — a gather along a
    sharded axis makes the SPMD partitioner all-gather the logits.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    vocab_iota = lax.broadcasted_iota(jnp.int32, lf.shape, len(lf.shape) - 1)
    gold = jnp.sum(
        jnp.where(vocab_iota == targets[..., None], lf, 0.0), axis=-1
    )
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
