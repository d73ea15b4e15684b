"""Model facade: one uniform API over all six families.

    model = Model(cfg, mesh)
    params = model.init(key)                       # real arrays
    specs  = model.param_specs(key)                # ShapeDtypeStructs (dry-run)
    loss, metrics = model.loss(params, batch)      # training objective
    logits, cache = model.prefill(params, batch)   # sequence -> KV/state cache
    logits, cache = model.decode(params, tokens, cache, cache_len)

Batch dict conventions (match launch.input_specs):
  tokens-LM : {"inputs": (B,S) i32, "targets": (B,S) i32}
  encoder   : {"embeds": (B,S,d), "targets": (B,S) i32, "mask": (B,S) f32}
  vlm       : {"inputs": (B,S_text) i32, "patches": (B,Np,d), "targets": (B,S_text) i32}
  decode    : tokens (B,1) i32 + cache pytree + cache_len scalar i32
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, rwkv6, transformer, zamba2

MOE_AUX_WEIGHT = 0.01


class DecoderKVCache(NamedTuple):
    k: jax.Array   # (L, B, Sc, Hkv, Dh), or the page pool (L, P, ps, Hkv, Dh)
    v: jax.Array


class Model:
    def __init__(self, cfg: ModelConfig, mesh: Optional[jax.sharding.Mesh] = None):
        self.cfg = cfg
        self.mesh = mesh

    # -- init ---------------------------------------------------------------
    def init(self, key: jax.Array):
        cfg = self.cfg
        if cfg.family == "rwkv":
            return rwkv6.init_rwkv_params(key, cfg)
        if cfg.family == "hybrid":
            return zamba2.init_zamba2_params(key, cfg)
        return transformer.init_transformer_params(key, cfg)

    def param_specs(self):
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))

    # -- embedding ----------------------------------------------------------
    def _embed(self, params, batch) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "encoder":
            return batch["embeds"].astype(jnp.dtype(cfg.dtype))
        x = jnp.take(params["embed"], batch["inputs"], axis=0)
        if cfg.family == "vlm" and "patches" in batch:
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        return x

    def _backbone_seq(self, params, x, *, return_cache: bool):
        cfg = self.cfg
        if cfg.family == "rwkv":
            x, cache = rwkv6.run_rwkv_seq(params, x, cfg, self.mesh, return_cache=return_cache)
            return x, cache, jnp.zeros((), jnp.float32)
        if cfg.family == "hybrid":
            x, cache = zamba2.run_zamba2_seq(
                params, x, cfg, self.mesh, return_cache=return_cache
            )
            return x, cache, jnp.zeros((), jnp.float32)
        x, caches, aux = transformer.run_layers_seq(
            params, x, cfg, self.mesh, return_cache=return_cache
        )
        cache = DecoderKVCache(k=caches[0], v=caches[1]) if return_cache else None
        return x, cache, aux

    # -- training loss -------------------------------------------------------
    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x = self._embed(params, batch)
        x, _, aux = self._backbone_seq(params, x, return_cache=False)
        logits = transformer.logits_from_hidden(params, x, cfg, self.mesh)
        targets = batch["targets"]
        mask = batch.get("mask")
        if cfg.family == "vlm":
            npatch = x.shape[1] - targets.shape[1]
            logits = logits[:, npatch:]
        ce = transformer.softmax_xent(logits, targets, mask)
        loss = ce + MOE_AUX_WEIGHT * aux
        return loss, {"ce": ce, "moe_aux": aux}

    # -- serving -------------------------------------------------------------
    def prefill(self, params, batch):
        """Returns (last-position logits (B,V), cache)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        x, cache, _ = self._backbone_seq(params, x, return_cache=True)
        logits = transformer.logits_from_hidden(params, x[:, -1:], cfg, self.mesh)[:, 0]
        return logits, cache

    def decode(self, params, tokens, cache, cache_len, fused=None,
               page_table=None):
        """tokens: (B,1) i32; cache_len: scalar i32 (tokens already cached)
        or (B,) per-slot lengths (continuous batching).

        ``fused`` is an optional ``fused_decode_weights(params)`` result —
        pass it when calling decode inside a token-generation scan so the
        fused projection matrices are built once per dispatch, not per step.

        ``page_table`` ((B, n_blocks) int32) switches the KV cache to the
        paged layout (``empty_page_pool``): each slot reads/writes the
        shared page pool through its table row (transformer families only).

        Returns (logits (B,V), new_cache)."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.family == "rwkv":
            if page_table is not None:
                raise ValueError("paged KV is not supported for rwkv caches")
            x, new_cache = rwkv6.run_rwkv_decode(params, x, cache, cfg)
        elif cfg.family == "hybrid":
            if page_table is not None:
                raise ValueError("paged KV is not supported for hybrid caches")
            x, new_cache = zamba2.run_zamba2_decode(
                params, x, cache, cache_len, cfg, self.mesh
            )
        else:
            if fused is None:
                fused = transformer.fused_decode_weights(params, cfg)

            def attn(p, h, kv, layer, wqkv):
                return attention.attention_decode(
                    p, h, kv, layer, cache_len, cfg, wqkv=wqkv,
                    page_table=page_table)

            x, nk, nv = transformer.run_layers_kv(
                params, x, cache.k, cache.v, attn, cfg, self.mesh,
                fused=fused,
            )
            new_cache = DecoderKVCache(k=nk, v=nv)
        logits = transformer.logits_from_hidden(params, x, cfg, self.mesh)[:, 0]
        return logits, new_cache

    def step_mixed(self, params, tokens, cache, cache_lens, new_lens,
                   fused=None, page_table=None, attn_window=None,
                   all_logits=False):
        """One mixed-batch engine step: each slot advances by its own
        ragged suffix ``tokens[b, :new_lens[b]]`` starting at cache
        position ``cache_lens[b]`` — decode steps (new_len 1) and prefill
        chunks (new_len up to Q) fused into ONE dispatch.

        ``tokens``: (B, Q) i32 (padding columns ignored); ``cache_lens``/
        ``new_lens``: (B,) i32.  Returns (last-valid-position logits (B, V),
        new_cache): logits are taken at column ``max(new_lens - 1, 0)`` —
        a decode slot's next-token logits, a finishing prompt's first-token
        logits (rows with new_len 0 return garbage the engine discards).

        ``all_logits=True`` returns (B, Q, V) logits at EVERY suffix
        position instead — position j is the next-token distribution after
        consuming ``tokens[b, :j+1]``, which is exactly what speculative-
        decode verification needs (each draft column checked against the
        distribution its prefix induces, all in this one dispatch).

        Transformer families with full attention only (the paged-KV
        constraint): SSM/RWKV decode state cannot replay multi-token
        suffixes in one step."""
        cfg = self.cfg
        if not self.supports_mixed_step:
            raise ValueError(f"{cfg.name}: mixed-batch step unsupported "
                             f"(family {cfg.family!r}, sliding_window="
                             f"{cfg.sliding_window})")
        x = jnp.take(params["embed"], tokens, axis=0)
        if fused is None:
            fused = transformer.fused_decode_weights(params, cfg)

        def attn(p, h, kv, layer, wqkv):
            return attention.attention_mixed(
                p, h, kv, layer, cache_lens, new_lens, cfg, wqkv=wqkv,
                page_table=page_table, attn_window=attn_window)

        x, nk, nv = transformer.run_layers_kv(
            params, x, cache.k, cache.v, attn, cfg, self.mesh,
            fused=fused,
        )
        if all_logits:
            logits = transformer.logits_from_hidden(params, x, cfg, self.mesh)
            return logits, DecoderKVCache(k=nk, v=nv)
        last = jnp.maximum(jnp.asarray(new_lens, jnp.int32) - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = transformer.logits_from_hidden(params, x_last, cfg, self.mesh)[:, 0]
        return logits, DecoderKVCache(k=nk, v=nv)

    @property
    def supports_mixed_step(self) -> bool:
        """Mixed-batch chunked prefill shares the paged-KV structural
        contract: a (L, ..., S, Hkv, Dh) KV cache whose positions can be
        written out of lockstep, and full (non-ring) attention."""
        cfg = self.cfg
        return (cfg.supports_decode
                and cfg.family not in ("rwkv", "hybrid")
                and cfg.sliding_window == 0
                and cfg.input_mode == "tokens")

    def fused_decode_weights(self, params):
        """Precomputed decode projection fusions for the scanned hot path
        (transformer families only; None-able pass-through otherwise)."""
        if self.cfg.family in ("rwkv", "hybrid"):
            return None
        return transformer.fused_decode_weights(params, self.cfg)

    # -- cache allocation ----------------------------------------------------
    def empty_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if cfg.family == "rwkv":
            c = rwkv6.empty_cache(cfg, batch, dtype)
            return rwkv6.RWKVLayerCache(
                state=jnp.zeros((cfg.n_layers, *c.state.shape), jnp.float32),
                shift_att=jnp.zeros((cfg.n_layers, *c.shift_att.shape), dtype),
                shift_ffn=jnp.zeros((cfg.n_layers, *c.shift_ffn.shape), dtype),
            )
        if cfg.family == "hybrid":
            return zamba2.empty_cache(cfg, batch, max_len, dtype)
        lc = attention.empty_cache(cfg, batch, max_len, dtype)
        L = cfg.n_layers
        return DecoderKVCache(
            k=jnp.zeros((L, *lc.k.shape), dtype),
            v=jnp.zeros((L, *lc.v.shape), dtype),
        )

    def cache_specs(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.empty_cache(batch, max_len))

    def prefill_paged(self, params, tokens, pool, page_row, start):
        """Continuation prefill into a paged cache: run the (1, T) prompt
        suffix ``tokens`` through every layer in one dispatch, scattering
        its KV into the pages named by ``page_row`` at positions
        [start, start+T).  Returns (last-position logits (1, V), new_pool).

        The prefix-hit admission path: cached pages cover [0, start), so
        only the un-cached suffix pays model compute."""
        cfg = self.cfg
        if not self.supports_paged_kv:
            raise ValueError(f"{cfg.name}: paged prefill unsupported")
        x = jnp.take(params["embed"], tokens, axis=0)

        def attn(p, h, kv, layer, wqkv):
            return attention.attention_prefill_paged(
                p, h, kv, layer, page_row, start, cfg)

        x, nk, nv = transformer.run_layers_kv(
            params, x, pool.k, pool.v, attn, cfg, self.mesh)
        logits = transformer.logits_from_hidden(
            params, x[:, -1:], cfg, self.mesh
        )[:, 0]
        return logits, DecoderKVCache(k=nk, v=nv)

    @property
    def supports_paged_kv(self) -> bool:
        """Paged KV needs the (L, ..., S, Hkv, Dh) DecoderKVCache layout and
        full (non-ring) attention; SSM/RWKV state caches have no pages to
        share and the SWA ring already bounds its own memory."""
        cfg = self.cfg
        return (cfg.supports_decode
                and cfg.family not in ("rwkv", "hybrid")
                and cfg.sliding_window == 0)

    def empty_page_pool(self, num_pages: int, page_size: int):
        """Shared paged-KV pool: DecoderKVCache of (L, P, ps, Hkv, Dh)."""
        cfg = self.cfg
        if not self.supports_paged_kv:
            raise ValueError(f"{cfg.name}: family {cfg.family!r} (sliding_window="
                             f"{cfg.sliding_window}) cannot use paged KV")
        dtype = jnp.dtype(cfg.dtype)
        lc = attention.empty_page_pool(cfg, num_pages, page_size, dtype)
        L = cfg.n_layers
        return DecoderKVCache(
            k=jnp.zeros((L, *lc.k.shape), dtype),
            v=jnp.zeros((L, *lc.v.shape), dtype),
        )
