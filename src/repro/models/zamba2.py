"""Zamba2 hybrid: Mamba2 backbone + one SHARED attention block.

Structure: ``n_layers`` Mamba2 layers in groups of ``attention_every``; after
each group the shared full-attention + MLP block runs (same weights every
application — zamba2's parameter-sharing trick).  The per-application LoRA
adapters of the released model are omitted (noted in DESIGN.md).

Caches: stacked Mamba2 caches (L, ...) plus per-application KV caches
(G, B, Sc, H, Dh) for the shared block (each application attends over its own
history).
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import attention, layers, mamba2


class Zamba2Cache(NamedTuple):
    conv: jax.Array       # (L, B, K-1, conv_ch)
    state: jax.Array      # (L, B, H, P, N) fp32
    attn_k: jax.Array     # (G, B, Sc, Hkv, Dh)
    attn_v: jax.Array


def n_groups(cfg: ModelConfig) -> int:
    assert cfg.n_layers % cfg.attention_every == 0
    return cfg.n_layers // cfg.attention_every


def init_zamba2_params(key: jax.Array, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    k_emb, k_layers, k_shared, k_head, k_mlp = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    k1, k2, k3 = jax.random.split(k_mlp, 3)
    return {
        "embed": layers.embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dtype),
        "layers": jax.vmap(lambda k: mamba2.init_mamba2_layer(k, cfg, dtype))(layer_keys),
        "shared": {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": attention.init_attn_params(k_shared, cfg, dtype),
            "mlp": {
                "w_gate": layers.dense_init(k1, (cfg.d_model, cfg.d_ff), dtype),
                "w_up": layers.dense_init(k2, (cfg.d_model, cfg.d_ff), dtype),
                "w_down": layers.dense_init(k3, (cfg.d_ff, cfg.d_model), dtype),
            },
        },
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "lm_head": layers.dense_init(k_head, (cfg.d_model, cfg.vocab_size), dtype),
    }


def _shared_block_seq(sp, x, cfg, return_cache, mesh=None):
    a, cache = attention.attention_block(
        sp["attn"], layers.rms_norm(x, sp["ln1"], cfg.norm_eps), cfg,
        return_cache=return_cache, mesh=mesh,
    )
    x = x + a
    h = layers.rms_norm(x, sp["ln2"], cfg.norm_eps)
    x = x + layers.swiglu(h, sp["mlp"]["w_gate"], sp["mlp"]["w_up"], sp["mlp"]["w_down"])
    return x, cache


def run_zamba2_seq(params, x, cfg: ModelConfig, mesh=None, *, return_cache=False):
    """x: (B,S,d). Returns (x, Zamba2Cache|None)."""
    G = n_groups(cfg)
    Lg = cfg.attention_every
    grouped = jax.tree.map(
        lambda a: a.reshape(G, Lg, *a.shape[1:]), params["layers"]
    )
    shared = params["shared"]

    def mamba_body(x, lp):
        x, cache = mamba2.mamba2_layer(lp, x, cfg, None, mesh)
        return x, cache if return_cache else None

    def group_body(x, gp):
        x, mcaches = lax.scan(jax.checkpoint(mamba_body), x, gp)
        x, acache = _shared_block_seq(shared, x, cfg, return_cache, mesh)
        ys = (mcaches, (acache.k, acache.v)) if return_cache else None
        return x, ys

    x, ys = lax.scan(
        jax.checkpoint(group_body) if cfg.remat else group_body, x, grouped
    )
    cache = None
    if return_cache:
        mcaches, (ak, av) = ys
        cache = Zamba2Cache(
            conv=mcaches.conv.reshape(cfg.n_layers, *mcaches.conv.shape[2:]),
            state=mcaches.state.reshape(cfg.n_layers, *mcaches.state.shape[2:]),
            attn_k=ak,
            attn_v=av,
        )
    return x, cache


def run_zamba2_decode(params, x, cache: Zamba2Cache, cache_len, cfg: ModelConfig, mesh=None):
    """x: (B,1,d). Returns (x, new_cache)."""
    G = n_groups(cfg)
    Lg = cfg.attention_every
    grouped = jax.tree.map(
        lambda a: a.reshape(G, Lg, *a.shape[1:]), params["layers"]
    )
    mconv = cache.conv.reshape(G, Lg, *cache.conv.shape[1:])
    mstate = cache.state.reshape(G, Lg, *cache.state.shape[1:])
    shared = params["shared"]

    def mamba_body(x, inputs):
        lp, conv, state = inputs
        x, c = mamba2.mamba2_layer_decode(
            lp, x, cfg, mamba2.Mamba2LayerCache(conv=conv, state=state)
        )
        return x, (c.conv, c.state)

    def group_body(x, inputs):
        gp, gconv, gstate, ak, av = inputs
        x, (nconv, nstate) = lax.scan(mamba_body, x, (gp, gconv, gstate))
        h = layers.rms_norm(x, shared["ln1"], cfg.norm_eps)
        a, ncache = attention.attention_decode(
            shared["attn"], h, attention.KVCache(k=ak[None], v=av[None]), 0,
            cache_len, cfg,
        )
        x = x + a
        h = layers.rms_norm(x, shared["ln2"], cfg.norm_eps)
        x = x + layers.swiglu(
            h, shared["mlp"]["w_gate"], shared["mlp"]["w_up"], shared["mlp"]["w_down"]
        )
        return x, (nconv, nstate, ncache.k[0], ncache.v[0])

    x, (nconv, nstate, nk, nv) = lax.scan(
        group_body, x, (grouped, mconv, mstate, cache.attn_k, cache.attn_v)
    )
    new_cache = Zamba2Cache(
        conv=nconv.reshape(cfg.n_layers, *nconv.shape[2:]),
        state=nstate.reshape(cfg.n_layers, *nstate.shape[2:]),
        attn_k=nk,
        attn_v=nv,
    )
    return x, new_cache


def empty_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Zamba2Cache:
    G = n_groups(cfg)
    d_inner, P, H, N, conv_ch = mamba2.dims(cfg)
    return Zamba2Cache(
        conv=jnp.zeros((cfg.n_layers, batch, mamba2.CONV_K - 1, conv_ch), dtype),
        state=jnp.zeros((cfg.n_layers, batch, H, P, N), jnp.float32),
        attn_k=jnp.zeros(
            (G, batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim), dtype
        ),
        attn_v=jnp.zeros(
            (G, batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim), dtype
        ),
    )
