"""Serving engine: prefill/decode steps, scanned batched generation.

One ``ServingEngine`` is a model-server *replica* — the executable behind a
deployment unit DU_i = (arch, tier, framework).  The orchestrator (core.*)
decides how many replicas exist and where traffic goes; this layer executes
the actual JAX steps.

Decode-path design
------------------
The paper prices every DU by its measured per-replica throughput ``t_max``
(Eq. 5/6), so engine overhead directly inflates cost-optimized cost and
shrinks capacity-optimized headroom.  The token loop is therefore fully
fused:

* ``generate`` runs ONE jitted ``lax.scan`` over the decode steps — the
  sampler, KV-cache update, and ``cache_len`` advance all live inside the
  scan body, so a call costs one dispatch and one device→host transfer
  (the final (B, steps) token block) regardless of ``steps``.  The seed
  implementation dispatched one jitted decode per token and synced
  ``np.asarray(tok)`` per token: O(steps) host↔device round trips.
* ``serve_queue`` is the continuous-batching variant driven by
  ``DecodeSlots``: fixed decode slots with *per-slot* cache lengths (the
  (B,) ragged form of ``model.decode``), and decoding in jitted scan chunks
  of ``chunk`` steps between admission points.  Slots that finish mid-chunk
  produce discarded tokens until the chunk boundary — chunk-granularity
  iteration-level scheduling.
* Admission is a MIXED BATCH by default (``EngineConfig.mixed_step``):
  prompts split into fixed-quantum chunks that run in the SAME jitted
  dispatch as the ongoing decode steps (``model.step_mixed`` — each slot
  carries (cache_len, new_len); see docs/serving.md).  Prefill never
  preempts decode and admission adds zero per-request dispatches; the
  per-step token budget is the live TTFT/TPOT knob.  ``mixed_step=False``
  keeps the legacy loop (one B=1 prefill dispatch per admission) as the
  reference control — the mixed engine is token-exact with it, greedy,
  on both cache layouts.
* The loop body lives in ``QueueSession``: a *resumable* session object
  (``submit`` requests any time, ``pump`` one admission+chunk cycle) so a
  fleet runtime can interleave many replica sessions, observe per-pump
  telemetry (``PumpReport``), and recover in-flight request ids when a
  replica is killed mid-decode.  ``serve_queue`` is the drain-to-empty
  wrapper over one session and is token-exact with the pre-refactor loop.
* Sampling semantics (greedy / temperature with a carried split key) are
  bit-identical to the seed per-step loop, which the fast-path tests
  assert token-exactly.

Paged KV cache (``EngineConfig.paged_kv``)
------------------------------------------
With paging the per-slot contiguous cache stripes are replaced by a shared
page pool (L, P, page_size, Hkv, Dh) plus per-slot block tables, managed by
``serving.paged_kv.BlockAllocator``:

* admission allocates ``ceil((prompt+max_new)/page_size)`` pages instead of
  a ``max_len`` stripe, so KV memory tracks *actual* request lengths and
  page capacity (not slot count) bounds concurrency;
* requests sharing a prompt prefix share physical pages.  An identical
  prompt (full-prompt cache hit) skips prefill entirely — the cached final
  logits reproduce the first sampled token bit-exactly; a block-aligned
  prefix hit reuses the cached pages and teacher-forces only the suffix
  through the paged decode path (one scan dispatch);
* pages a finished request leaves behind stay cached (LRU) until
  allocation pressure evicts them; copy-on-write keeps a shared page
  exclusive before any slot writes into it.

The paged chunk scan is the same jitted loop with ``page_table`` threaded
through ``model.decode``; greedy outputs are token-exact with the
contiguous path, which the paged tests assert end-to-end.

The jitted scan donates the KV cache, so the compiled step updates the
decode buffer in place; ``serve_prefill``/``serve_decode`` remain the units
the multi-pod dry-run lowers (launch.dryrun).
"""
from __future__ import annotations

import math
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.model import Model
from repro.obs.trace import Span, Tracer
from repro.serving.backends import StateFrontier
from repro.serving.paged_kv import TRASH_PAGE, BlockAllocator, KVFrontier
from repro.serving.spec import (
    Drafter,
    NgramDrafter,
    spec_quantum,
    verify_tokens,
)


@dataclass
class EngineConfig:
    max_len: int = 4096
    decode_batch: int = 8
    temperature: float = 0.0        # 0 => greedy
    seed: int = 0
    decode_chunk: int = 8           # scan steps between continuous-batching
                                    # admission points (serve_queue)
    # -- mixed-batch chunked prefill (serve_queue / QueueSession only) -------
    mixed_step: bool = True         # fuse prefill chunks into the decode
                                    # dispatch (False = PR-3 legacy admission:
                                    # one B=1 prefill dispatch per request)
    prefill_chunk: int = 64         # token budget per mixed step: decode
                                    # slots take 1 token each, prefill chunks
                                    # pack the remainder (the TTFT/TPOT knob;
                                    # sessions can retune it live)
    # -- paged KV cache (serve_queue / QueueSession only) --------------------
    paged_kv: bool = False          # block-based KV with prefix reuse
    page_size: int = 16             # tokens per KV page
    num_pages: int = 0              # 0 => auto-size from decode_batch/max_len
    page_headroom: float = 1.5      # auto-size multiplier over the worst-case
                                    # live set: the slack is what lets finished
                                    # prompts stay cached for prefix reuse
    prefix_reuse: bool = True       # cross-request prompt-prefix sharing
    # -- speculative decoding (mixed-step sessions only) ---------------------
    spec_k: int = 0                 # draft tokens per decode round (0 = off);
                                    # sessions can retune it live (the
                                    # controller's goodput-protection knob)
    spec_ngram: int = 3             # n-gram length of the default prompt-
                                    # lookup drafter (engine.drafter swaps in
                                    # any Drafter implementation)


@dataclass
class EngineTelemetry:
    """Measured engine-side counters (aggregated over every session sharing
    this engine's compiled functions).  ``tokens_per_s`` is the *measured*
    decode rate the fleet telemetry bus feeds back to the controller — the
    live replacement for the Table-1 ``t_max`` constants."""

    prefills: int = 0                # PROMPTS prefilled to completion (one per
                                     # admitted request that touched the model,
                                     # however many chunks it took)
    prefill_chunks: int = 0          # prompt chunks dispatched (mixed mode)
    mixed_steps: int = 0             # fused prefill+decode dispatches
    chunks: int = 0
    decode_s: float = 0.0            # wall time inside chunk scans (+ sync)
    useful_tokens: int = 0           # tokens delivered to some request
    wasted_tokens: int = 0           # idle/finished-slot tokens in the chunk
    completed_requests: int = 0
    # paged-KV prefix cache effectiveness (zero when paging is off)
    prefix_hits: int = 0             # full-prompt + block-aligned hits
    prefix_misses: int = 0
    reused_tokens: int = 0           # prompt tokens served from cached pages
    prefilled_tokens: int = 0        # prompt tokens run through the model
    # durable-KV recovery (zero when no frontiers are restored)
    recovered_tokens: int = 0        # KV tokens resumed from injected frontiers
    recomputed_prefill_tokens: int = 0  # retry prefill re-run through the model
    # speculative decoding (zero when spec_k is 0).  ONLY accepted tokens
    # count toward useful_tokens / tokens_per_s — a rejected draft is paid
    # compute, not delivered output, so goodput and $/1k-tokens never
    # inflate under low acceptance.
    drafted_tokens: int = 0          # draft tokens dispatched for verification
    accepted_tokens: int = 0         # drafts that survived verification
    spec_rounds: int = 0             # fused verify dispatches (>=1 draft in)
    # sparse experts (zero without them): (token, held expert) rows the
    # steps' expert layers computed, every row of the step (padding and
    # idle slots included), summed over layers
    expert_rows: int = 0

    @property
    def tokens_per_s(self) -> float:
        return self.useful_tokens / self.decode_s if self.decode_s > 0 else 0.0

    @property
    def spec_accept_rate(self) -> float:
        return (self.accepted_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0)

    @property
    def efficiency(self) -> float:
        total = self.useful_tokens + self.wasted_tokens
        return self.useful_tokens / total if total else 1.0

    @property
    def cache_hit_rate(self) -> float:
        total = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / total if total else 0.0


def _add_rows(rows, r):
    """Running expert-row count of a scan (None stays None)."""
    return None if rows is None else rows + r


def _rows_of(rows) -> int:
    """A step's expert rows as read back (0 where the model has none)."""
    return 0 if rows is None else int(rows)


class ServingEngine:
    def __init__(self, model: Model, params, cfg: EngineConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.telemetry = EngineTelemetry()
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode, donate_argnums=(2,))
        self._gen = jax.jit(
            self._gen_scan, static_argnums=(5,), donate_argnums=(2,)
        )
        self._chunk = jax.jit(
            self._chunk_scan, static_argnums=(6,), donate_argnums=(1,)
        )
        self._place = jax.jit(self._place_slot, donate_argnums=(0,))
        # -- mixed-batch chunked prefill -------------------------------------
        # one trace per power-of-2 q-chunk width Q (tokens.shape[1]); the
        # counter ticks once per trace, which the compile-count regression
        # test pins (jit only re-runs this python body on a cache miss)
        self.mixed = bool(cfg.mixed_step) and model.supports_mixed_step
        self.mixed_traces = 0
        self._mixed = jax.jit(
            self._mixed_step_fn, static_argnums=(7,), donate_argnums=(1,)
        )
        self._mixed_paged = jax.jit(
            self._mixed_step_paged_fn, static_argnums=(8,), donate_argnums=(1,)
        )
        # -- speculative decoding --------------------------------------------
        # the pluggable drafter (spec.Drafter protocol); sessions read it
        # per round, so swapping in a draft model is one attribute write
        self.drafter: Drafter = NgramDrafter(max(1, cfg.spec_ngram))
        self._spec = jax.jit(
            self._spec_step_fn, static_argnums=(8,), donate_argnums=(1,)
        )
        self._spec_paged = jax.jit(
            self._spec_step_paged_fn, static_argnums=(9,), donate_argnums=(1,)
        )
        # -- paged-KV resolution (sessions consult these) --------------------
        if cfg.paged_kv and not model.supports_paged_kv:
            raise ValueError(
                f"paged_kv=True but {model.cfg.name} (family {model.cfg.family!r}, "
                f"sliding_window={model.cfg.sliding_window}) has no pageable KV "
                "cache — drop the flag or pick a full-attention transformer arch"
            )
        self.paged = bool(cfg.paged_kv)
        ps = max(1, cfg.page_size)
        self.max_blocks = -(-cfg.max_len // ps)
        # auto pool: every slot can hold a max_len request, times
        # ``page_headroom`` so finished prompts can stay cached instead of
        # evicting immediately (page 0 is the reserved trash page).  Note
        # pages track ACTUAL request lengths, so real usage of the live
        # set is usually well under the worst-case decode_batch*max_blocks.
        self.num_pages = cfg.num_pages or (
            1 + math.ceil(cfg.page_headroom * cfg.decode_batch * self.max_blocks)
        )
        self._chunk_paged = jax.jit(
            self._chunk_scan_paged, static_argnums=(7,), donate_argnums=(1,)
        )
        self._prefill_paged = jax.jit(model.prefill_paged, donate_argnums=(2,))
        self._place_pages = jax.jit(self._place_pages_fn, donate_argnums=(0,))
        self._copy_page = jax.jit(self._copy_page_fn, donate_argnums=(0,))
        self._inject_pages = jax.jit(self._inject_pages_fn, donate_argnums=(0,))

    def new_session(self) -> "QueueSession":
        """The session factory replicas call: one resumable continuous-
        batching session over this engine's compiled functions.  Job-style
        engines (``serving.diffusion.DiffusionEngine``) override this with
        their own ``CacheBackend``-compatible session type."""
        return QueueSession(self)

    # -- single-shot steps ----------------------------------------------------
    def prefill(self, batch: Dict[str, Any]):
        return self._prefill(self.params, batch)

    def decode(self, tokens, cache, cache_len):
        """One decode step.  ``cache_len``: scalar (fixed batch) or (B,)
        per-slot lengths (continuous batching)."""
        return self._decode(self.params, tokens, cache, jnp.asarray(cache_len, jnp.int32))

    # -- fused generation -----------------------------------------------------
    def _sample(self, logits, key):
        if self.cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / self.cfg.temperature).astype(jnp.int32)

    def _gen_scan(self, params, tok0, cache, cache_len, key, steps: int):
        """One jitted scan: emits the carried token, decodes, samples next.
        Greedy mode carries no PRNG key (argmax needs none), and a small
        unroll amortizes the while-loop overhead of tiny per-step graphs."""
        greedy = self.cfg.temperature <= 0.0
        # fused projection weights built ONCE per dispatch, outside the
        # scan: they enter the while loop as invariant operands instead of
        # being re-concatenated every token.
        fused = self.model.fused_decode_weights(params)

        def step(carry, _):
            tok, cache, clen, key = carry
            logits, cache = self.model.decode(
                params, tok[:, None], cache, clen, fused=fused
            )
            if not greedy:
                key, sub = jax.random.split(key)
                nxt = self._sample(logits, sub)
            else:
                nxt = self._sample(logits, key)
            return (nxt, cache, clen + 1, key), tok

        (_, cache, _, _), toks = lax.scan(
            step, (tok0, cache, cache_len, key), None, length=steps,
            unroll=min(4, steps),
        )
        return toks.T, cache                      # (B, steps)

    def generate(
        self, prompt: Dict[str, Any], steps: int, prompt_len: int
    ) -> np.ndarray:
        """Greedy/temperature generation for a fixed batch of prompts.

        ``prompt['inputs']`` is (B, S_prompt); returns (B, steps) tokens.
        O(1) host↔device transfers: one prefill dispatch, one scan dispatch,
        one np.asarray of the full token block.
        """
        if prompt_len + steps > self.cfg.max_len:
            raise ValueError(
                f"prompt_len={prompt_len} + steps={steps} exceeds "
                f"max_len={self.cfg.max_len}"
            )
        B = jax.tree.leaves(prompt)[0].shape[0]
        logits, pcache = self.prefill(prompt)
        cache = self._expand_cache(pcache, B, prompt_len)
        key = jax.random.key(self.cfg.seed)
        tok0 = self._sample(logits, key)
        toks, _ = self._gen(
            self.params, tok0, cache, jnp.int32(prompt_len), key, steps
        )
        return np.asarray(toks)

    def _expand_cache(self, pcache, batch: int, prompt_len: int):
        """Pad the prefill cache into the fixed decode buffer."""
        buf = self.model.empty_cache(batch, self.cfg.max_len)

        def place(b, c):
            if b.shape == c.shape:
                return c
            # KV-style: pad along the sequence axis (axis 2 of (L,B,S,...))
            idx = tuple([slice(0, s) for s in c.shape])
            return b.at[idx].set(c.astype(b.dtype))

        return jax.tree.map(place, buf, pcache)

    # -- continuous batching (DecodeSlots-driven) ----------------------------
    def _rows0(self):
        """The expert-row count a step starts from: None without experts,
        so a dense model's programs carry and return nothing new."""
        return jnp.zeros((), jnp.int32) if self.model.cfg.is_moe else None

    def _chunk_scan(self, params, cache, tok, lens, active, key, steps: int):
        """Ragged decode chunk: every ``active`` slot advances ``steps``
        tokens with its own cache length; empty/finished/mid-prefill slots
        decode discarded garbage and their cache length stays frozen (the
        garbage KV lands at a position real writes overwrite before any
        attention unmasks it).  Returns (cache, tok, lens, key, toks,
        expert_rows), the last None without experts."""
        max_row = jnp.int32(self.cfg.max_len - 1)
        greedy = self.cfg.temperature <= 0.0
        fused = self.model.fused_decode_weights(params)

        def step(carry, _):
            tok, cache, lens, key, rows = carry
            logits, cache, r = self.model.decode(
                params, tok[:, None], cache, lens, fused=fused,
                count_experts=True,
            )
            if not greedy:
                key, sub = jax.random.split(key)
                nxt = self._sample(logits, sub)
            else:
                nxt = self._sample(logits, key)
            lens = jnp.where(active, jnp.minimum(lens + 1, max_row), lens)
            return (nxt, cache, lens, key, _add_rows(rows, r)), tok

        (tok, cache, lens, key, rows), toks = lax.scan(
            step, (tok, cache, lens, key, self._rows0()), None, length=steps,
            unroll=min(4, steps),
        )
        return cache, tok, lens, key, toks, rows  # toks: (steps, B)

    # -- paged-KV jitted bodies ----------------------------------------------
    def _chunk_scan_paged(self, params, pool, tables, tok, lens, active, key,
                          steps: int):
        """The ragged chunk scan over the shared page pool: identical loop,
        with every decode reading/writing KV through the block tables."""
        max_row = jnp.int32(self.cfg.max_len - 1)
        greedy = self.cfg.temperature <= 0.0
        fused = self.model.fused_decode_weights(params)

        def step(carry, _):
            tok, pool, lens, key, rows = carry
            logits, pool, r = self.model.decode(
                params, tok[:, None], pool, lens, fused=fused,
                page_table=tables, count_experts=True,
            )
            if not greedy:
                key, sub = jax.random.split(key)
                nxt = self._sample(logits, sub)
            else:
                nxt = self._sample(logits, key)
            lens = jnp.where(active, jnp.minimum(lens + 1, max_row), lens)
            return (nxt, pool, lens, key, _add_rows(rows, r)), tok

        (tok, pool, lens, key, rows), toks = lax.scan(
            step, (tok, pool, lens, key, self._rows0()), None, length=steps,
            unroll=min(4, steps),
        )
        return pool, tok, lens, key, toks, rows   # toks: (steps, B)

    # -- mixed-batch (chunked prefill + decode) jitted bodies -----------------
    def _mixed_tokens(self, chunks, tok, is_decode):
        """Column 0 of a decode row is its carried token; prefill rows keep
        their host-built chunk tokens."""
        Q = chunks.shape[1]
        col0 = jnp.arange(Q, dtype=jnp.int32)[None, :] == 0
        return jnp.where(is_decode[:, None] & col0, tok[:, None], chunks)

    def _mixed_step_fn(self, params, cache, chunks, tok, lens, new_lens,
                       is_decode, attn_window: int):
        """ONE dispatch advancing every slot by its ragged suffix: decode
        slots by their carried token, prefill slots by a prompt chunk.
        ``attn_window`` (static, pow-2-bucketed by the caller) bounds the
        cache span attention reads — the content frontier, so score work
        tracks actual lengths instead of max_len.  Returns
        (last-valid-position logits (B, V), cache, advanced lens,
        expert_rows), the last None without experts."""
        self.mixed_traces += 1
        fused = self.model.fused_decode_weights(params)
        tokens = self._mixed_tokens(chunks, tok, is_decode)
        logits, cache, rows = self.model.step_mixed(
            params, tokens, cache, lens, new_lens, fused=fused,
            attn_window=attn_window, count_experts=True,
        )
        return logits, cache, lens + new_lens, rows

    def _mixed_step_paged_fn(self, params, pool, tables, chunks, tok, lens,
                             new_lens, is_decode, attn_window: int):
        self.mixed_traces += 1
        fused = self.model.fused_decode_weights(params)
        tokens = self._mixed_tokens(chunks, tok, is_decode)
        logits, pool, rows = self.model.step_mixed(
            params, tokens, pool, lens, new_lens, fused=fused,
            page_table=tables, attn_window=attn_window, count_experts=True,
        )
        return logits, pool, lens + new_lens, rows

    # -- speculative-decode jitted bodies -------------------------------------
    def _spec_step_fn(self, params, cache, chunks, tok, lens, new_lens,
                      is_decode, key, attn_window: int):
        """ONE fused verify dispatch: every decoding slot advances by its
        carried token plus its draft columns (``new_lens`` = 1 + d, ragged
        per row) through the SAME mixed-step machinery a prompt chunk
        rides, and the (B, Q, V) all-position logits reduce on device to
        the (3, B, Q) accept/replacement/bonus verdict — O(B·Q) comes back
        to the host, never the vocab axis.  Rejected columns DO write KV;
        the caller simply never advances its length mirror past the
        accepted frontier, so the garbage sits beyond every unmasked
        position until real writes overwrite it (the exact invariant the
        ragged chunk scan already relies on for idle slots)."""
        self.mixed_traces += 1
        fused = self.model.fused_decode_weights(params)
        tokens = self._mixed_tokens(chunks, tok, is_decode)
        logits, cache, rows = self.model.step_mixed(
            params, tokens, cache, lens, new_lens, fused=fused,
            attn_window=attn_window, all_logits=True, count_experts=True,
        )
        # drafts sit in token columns 1..d: column j's logits judge the
        # token in column j+1 (the shifted view; last column is padding)
        drafts = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        verdict, key = verify_tokens(logits, drafts, key,
                                     self.cfg.temperature)
        return verdict, cache, key, rows

    def _spec_step_paged_fn(self, params, pool, tables, chunks, tok, lens,
                            new_lens, is_decode, key, attn_window: int):
        self.mixed_traces += 1
        fused = self.model.fused_decode_weights(params)
        tokens = self._mixed_tokens(chunks, tok, is_decode)
        logits, pool, rows = self.model.step_mixed(
            params, tokens, pool, lens, new_lens, fused=fused,
            page_table=tables, attn_window=attn_window, all_logits=True,
            count_experts=True,
        )
        drafts = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        verdict, key = verify_tokens(logits, drafts, key,
                                     self.cfg.temperature)
        return verdict, pool, key, rows

    def warm_spec_traces(self, ks: Sequence[int]) -> int:
        """Pre-compile the spec-verify trace grid: for each draft depth's
        pow-2 column quantum, every pow-2 attention-window bucket up to
        max_len — the same enumeration discipline as ``warm_mixed_traces``
        so controller retunes of ``spec_k`` never compile mid-pump."""
        if not self.mixed:
            return 0
        n = self.cfg.decode_batch
        before = self.mixed_traces
        qs = sorted({spec_quantum(k) for k in ks if k > 0})
        for Q in qs:
            chunks = jnp.zeros((n, Q), jnp.int32)
            tok = jnp.zeros((n,), jnp.int32)
            lens = jnp.zeros((n,), jnp.int32)
            new_lens = jnp.ones((n,), jnp.int32)
            isd = jnp.ones((n,), bool)
            key = jax.random.key(self.cfg.seed)
            aw = Q
            while True:
                aw_b = min(aw, self.cfg.max_len)
                if self.paged:
                    pool = self.model.empty_page_pool(
                        self.num_pages, self.cfg.page_size
                    )
                    tables = jnp.full((n, self.max_blocks), TRASH_PAGE,
                                      jnp.int32)
                    out = self._spec_paged(
                        self.params, pool, tables, chunks, tok, lens,
                        new_lens, isd, key, aw_b,
                    )
                else:
                    cache = self.model.empty_cache(n, self.cfg.max_len)
                    out = self._spec(
                        self.params, cache, chunks, tok, lens, new_lens,
                        isd, key, aw_b,
                    )
                jax.block_until_ready(out[0])
                if aw_b >= self.cfg.max_len:
                    break
                aw *= 2
        return self.mixed_traces - before

    def chunk_quantum(self, token_budget: int) -> int:
        """The FIXED q-chunk width a budget implies: pow2(budget / slots).
        Every mixed step uses exactly this Q (tail chunks ride the same
        grid with masked columns), so the trace space is ONE Q bucket per
        budget times the attention-window buckets — fully enumerable by
        ``warm_mixed_traces`` instead of emerging from workload dynamics."""
        per_slot = max(1, int(token_budget) // max(1, self.cfg.decode_batch))
        q = 1 << (per_slot - 1).bit_length()
        return min(q, 1 << (self.cfg.max_len - 1).bit_length())

    def warm_mixed_traces(self, budgets: Sequence[int]) -> int:
        """Pre-compile the mixed-step trace grid for the given token
        budgets: for each budget's Q quantum, every pow-2 attention-window
        bucket up to max_len (the buckets a session can ever request).
        Keeps jit compiles out of measured pumps; returns traces compiled."""
        if not self.mixed:
            return 0
        n = self.cfg.decode_batch
        before = self.mixed_traces
        qs = sorted({self.chunk_quantum(b) for b in budgets})
        for Q in qs:
            chunks = jnp.zeros((n, Q), jnp.int32)
            tok = jnp.zeros((n,), jnp.int32)
            lens = jnp.zeros((n,), jnp.int32)
            new_lens = jnp.ones((n,), jnp.int32)
            isd = jnp.zeros((n,), bool)
            aw = Q
            while True:
                aw_b = min(aw, self.cfg.max_len)
                if self.paged:
                    pool = self.model.empty_page_pool(
                        self.num_pages, self.cfg.page_size
                    )
                    tables = jnp.full((n, self.max_blocks), TRASH_PAGE,
                                      jnp.int32)
                    out = self._mixed_paged(
                        self.params, pool, tables, chunks, tok, lens,
                        new_lens, isd, aw_b,
                    )
                else:
                    cache = self.model.empty_cache(n, self.cfg.max_len)
                    out = self._mixed(
                        self.params, cache, chunks, tok, lens, new_lens,
                        isd, aw_b,
                    )
                jax.block_until_ready(out[0])
                if aw_b >= self.cfg.max_len:
                    break
                aw *= 2
        return self.mixed_traces - before

    def _place_pages_fn(self, pool, pcache, pages):
        """Scatter a B=1 prefill cache into ``pages`` of the page pool.

        The prefill leaf (L, 1, Sp, H, D) is padded to whole pages and
        written with one advanced-index scatter per leaf; ``pages`` is a
        (ceil(Sp/ps),) int32 array so the same compiled function serves any
        page assignment at a given prompt length.
        """
        ps = self.cfg.page_size

        def place(buf, c):
            L, _, Sp = c.shape[:3]
            nb = pages.shape[0]
            pad = nb * ps - Sp
            if pad:
                c = jnp.pad(c, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (c.ndim - 3))
            c = c.reshape(L, nb, ps, *c.shape[3:]).astype(buf.dtype)
            return buf.at[:, pages].set(c)

        return jax.tree.map(place, pool, pcache)

    def _copy_page_fn(self, pool, src, dst):
        """Device copy-on-write: duplicate page ``src`` into ``dst`` across
        every layer leaf (used before a slot writes into a shared page)."""
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pool)

    def _inject_pages_fn(self, pool, kv, pages):
        """Scatter host frontier pages back into the pool: the inverse of
        ``extract_pages``.  ``kv`` leaves are (L, nb, ps, H, D); ``pages``
        is the (nb,) destination page list (traces key on nb)."""
        return jax.tree.map(
            lambda buf, c: buf.at[:, pages].set(c.astype(buf.dtype)), pool, kv
        )

    def extract_pages(self, pool, pages: Sequence[int]):
        """Host snapshot of ``pages`` from the page pool: one gather per
        leaf, leaves shaped (L, nb, ps, H, D) — the ``KVFrontier`` payload.
        Read-only; the pool is untouched.  The gather is padded to a pow-2
        page count (the op compiles per index length) and sliced back on
        the host, mirroring ``_inject_pages_fn``'s bucketing."""
        idx = np.asarray(pages, np.int32)
        nb = int(idx.size)
        nb_pad = 1 << max(0, nb - 1).bit_length()
        if nb_pad > nb:
            idx = np.concatenate(
                [idx, np.full(nb_pad - nb, TRASH_PAGE, np.int32)])
        jidx = jnp.asarray(idx)
        return jax.tree.map(lambda a: np.asarray(a[:, jidx])[:, :nb], pool)

    def _place_slot(self, cache, pcache, slot):
        """Write a B=1 prefill cache into slot ``slot`` of the decode buffer.

        Works for every cache family whose leaves carry batch at axis 1
        (KV: (L,B,S,H,D); SSM/RWKV states: (L,B,...)) — the prefill leaf is
        placed at a zero offset in every axis except batch."""
        slot = jnp.asarray(slot, jnp.int32)

        def place(buf, c):
            start = tuple(
                slot if a == 1 else jnp.int32(0) for a in range(buf.ndim)
            )
            return lax.dynamic_update_slice(buf, c.astype(buf.dtype), start)

        return jax.tree.map(place, cache, pcache)

    def serve_queue(
        self,
        requests: Sequence[Tuple[np.ndarray, int]],   # [(inputs (1,Sp), max_new)]
        *,
        slots: Optional["DecodeSlots"] = None,
        on_complete: Optional[Callable[[int, np.ndarray], None]] = None,
    ) -> Dict[int, np.ndarray]:
        """DEPRECATED batch facade — a thin shim over the streaming client
        API (``repro.serving.api.EngineClient``), kept token-exact with the
        pre-streaming drain loop.  New code should hold ``RequestHandle``s
        and stream: tokens become visible per pump, requests can be
        cancelled mid-flight, and TTFT is observed at the first token
        instead of inferred at completion.

        Admits queued requests into free decode slots, decodes the full
        slot batch in jitted scan chunks, refills as requests finish.
        Returns {request_index: (max_new,) tokens}.  ``on_complete(rid,
        tokens)`` fires the moment a request's last token crosses a chunk
        boundary.
        """
        from repro.serving.api import EngineClient, InferenceRequest

        warnings.warn(
            "serve_queue is a deprecation shim; use "
            "repro.serving.api.EngineClient for the streaming request "
            "lifecycle (submit -> stream -> cancel)",
            DeprecationWarning, stacklevel=2,
        )
        client = EngineClient(self, slots=slots)
        handles = [
            client.submit(InferenceRequest(prompt=np.asarray(inp),
                                           max_new=max_new), rid=rid)
            for rid, (inp, max_new) in enumerate(requests)
        ]
        while not client.idle:
            report = client.tick()
            if on_complete is not None:
                for rid, toks in report.completed.items():
                    on_complete(rid, toks)
        return {h.rid: h.result() for h in handles}


@dataclass
class PumpReport:
    """What one ``QueueSession.pump`` observed (the fleet telemetry unit)."""

    admitted: List[int] = field(default_factory=list)     # rids entering a slot
    emitted: Dict[int, int] = field(default_factory=dict)  # rid -> token count
    # per-slot token DELTAS this pump (rid -> tokens emitted, in order) —
    # the streaming-client feed: concatenated across pumps these are
    # byte-identical to the completion-time array in ``completed``
    tokens: Dict[int, List[int]] = field(default_factory=dict)
    completed: Dict[int, np.ndarray] = field(default_factory=dict)
    chunk_steps: int = 0
    prefill_chunks: int = 0           # prompt chunks dispatched (mixed mode)
    mixed_steps: int = 0              # fused prefill+decode dispatches
    useful_tokens: int = 0
    wasted_tokens: int = 0
    occupancy: float = 0.0            # slot occupancy entering the chunk
    wall_s: float = 0.0               # pump wall time: the engine.pump span
    # paged-KV prefix cache activity this pump (zero when paging is off)
    prefix_hits: int = 0              # admissions served from cached pages
    prefix_misses: int = 0            # admissions that ran a full prefill
    reused_tokens: int = 0            # prompt tokens skipped via the cache
    prefilled_tokens: int = 0         # prompt tokens run through the model
    page_occupancy: float = 0.0       # live fraction of the page pool
    cached_pages: int = 0             # reusable (refcount-0) pages held
    # durable-KV recovery activity this pump (zero when no frontiers move)
    recovered_tokens: int = 0         # KV tokens resumed from injected frontiers
    recomputed_prefill_tokens: int = 0  # retry prompt tokens re-run through
                                      # the model (zero on a store hit)
    # speculative decoding this pump (zero when spec_k is 0); only ACCEPTED
    # drafts ever reach emitted/tokens/useful_tokens
    drafted_tokens: int = 0           # draft tokens dispatched for verification
    accepted_tokens: int = 0          # drafts that survived verification
    spec_rounds: int = 0              # fused verify dispatches (>=1 draft in)
    expert_rows: int = 0              # (token, held expert) rows computed
    # per-pump phase walls (the observability breakdown of ``wall_s``),
    # read from the pump's phase spans: admission (``pump.admit``: queue
    # pops + prefill setup/dispatch in legacy mode), dispatch
    # (``pump.prefill`` less its ``pump.publish_sync`` reads, and
    # ``pump.decode``: jitted mixed-step / chunk-scan launches), host sync
    # (``pump.publish_sync``, ``pump.emit_sync``, ``pump.draft``,
    # ``pump.decode_sync``: device->host transfers, drafting and
    # per-token host accounting)
    admit_s: float = 0.0
    dispatch_s: float = 0.0
    sync_s: float = 0.0


def traced_pump(tracer: Tracer, tags: Dict[str, Any],
                run: Callable[[], PumpReport]) -> PumpReport:
    """Run one pump cycle inside its ``engine.pump`` span: the span's wall
    is the report's ``wall_s``, and its event carries the phase walls the
    cycle's ``pump.*`` spans measured (plus ``tags``: replica, tier)."""
    with tracer.begin("engine.pump", cat="engine", sampled=True, **tags) as sp:
        report = run()
        sp.end(admit_s=report.admit_s, dispatch_s=report.dispatch_s,
               sync_s=report.sync_s, occupancy=report.occupancy,
               completed=len(report.completed))
    report.wall_s = sp.wall_s
    return report


class QueueSession:
    """Resumable continuous-batching session over one engine.

    The loop body of ``serve_queue`` factored into an object: requests may
    be ``submit``-ed at any time, each ``pump`` runs one admission pass plus
    one jitted chunk scan, and per-pump effects come back as a
    ``PumpReport``.  A fleet replica owns exactly one session; killing the
    replica mid-decode means dropping the session and requeueing
    ``inflight_rids()`` elsewhere (greedy sampling makes the retried output
    token-exact, which the failover drill asserts).
    """

    def __init__(self, engine: ServingEngine, *, slots: Optional["DecodeSlots"] = None):
        self.eng = engine
        n_slots = engine.cfg.decode_batch
        self.slots = slots if slots is not None else DecodeSlots(n_slots)
        self.paged = engine.paged
        if self.paged:
            self.cache = engine.model.empty_page_pool(
                engine.num_pages, engine.cfg.page_size
            )
            self.allocator = BlockAllocator(
                engine.num_pages, engine.cfg.page_size,
                enable_reuse=engine.cfg.prefix_reuse,
            )
            self.tables = np.full((n_slots, engine.max_blocks), TRASH_PAGE,
                                  dtype=np.int32)
            self._slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
            self._slot_of: Dict[int, int] = {}        # rid -> decoding slot
        else:
            self.allocator = None
            self.cache = engine.model.empty_cache(n_slots, engine.cfg.max_len)
        # scan-state backend: rwkv/hybrid decode state is a CONSTANT-SIZE
        # per-slot pytree (no pages), so frontiers externalize as one state
        # snapshot per slot (backends.StateFrontier) instead of KV pages
        self.scan_state = (not self.paged
                           and engine.model.cfg.family in ("rwkv", "hybrid"))
        self.lens = jnp.zeros((n_slots,), jnp.int32)
        self.tok = jnp.zeros((n_slots,), jnp.int32)
        self.key = jax.random.key(engine.cfg.seed)
        self.queue: List[Tuple[int, np.ndarray, int]] = []
        self.results: Dict[int, np.ndarray] = {}      # every completed rid
        self._out: Dict[int, List[int]] = {}
        self._admissions = 0
        self._instant: List[int] = []                 # max_new<=0 completions
        # SLO-aware admission order: rid -> (class_rank, -priority,
        # deadline_at, seq).  All-default submissions collapse to FIFO
        # (seq tiebreak), keeping the legacy paths token-exact.
        self._slo: Dict[int, Tuple[int, int, float, int]] = {}
        self._seq = 0
        # -- mixed-batch chunked prefill ------------------------------------
        self.mixed = engine.mixed
        # the live TTFT/TPOT knob: new tokens per mixed step (decode slots
        # count 1 each; prefill chunks pack the remainder).  Mutable so the
        # fleet controller can retune it tick-by-tick without recompiling —
        # jit traces key on the pow-2 chunk bucket, not the budget.
        self.token_budget = max(1, engine.cfg.prefill_chunk)
        # -- speculative decoding --------------------------------------------
        # the second live knob, retuned tick-by-tick like token_budget:
        # draft depth per decode round (0 disables speculation without
        # recompiling — spec traces key on the pow-2 column quantum)
        self.spec_k = max(0, int(engine.cfg.spec_k))
        # rids that opted out of speculation (InferenceRequest.speculate)
        self._no_spec: set = set()
        # per-session acceptance-rate EWMA over verify rounds (None until
        # the first drafted round); pumps fold it into PumpReport for the
        # fleet telemetry bus
        self.spec_accept_ewma: Optional[float] = None
        # slot -> in-progress prompt ingestion (admitted, not yet decoding)
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        # host mirror of per-slot cache lengths: every advance is host-
        # deterministic (admission sets, mixed steps add new_lens, chunk
        # scans add their step count), so the attention-window bucket is
        # computed without a device sync
        self._lens_host = np.zeros((n_slots,), np.int64)
        # -- durable-KV recovery state ---------------------------------------
        # rid -> validated KVFrontier awaiting a slot (admission injects it
        # instead of prefilling); rid -> prompt tuple for frontier extraction
        self._frontiers: Dict[int, KVFrontier] = {}
        self._prompt_of: Dict[int, Tuple[int, ...]] = {}
        # rids whose retry prefill counts as RECOMPUTED work (the request
        # completed its first prefill on a replica that later died)
        self._recompute: set = set()
        # restored emissions to replay through the next report.tokens (the
        # streaming client reconciles by position, so a restored request
        # "replays" from 0 and the client forwards only the unseen suffix)
        self._restored: List[Tuple[int, List[int]]] = []
        self._pending_recovered = 0
        self._pending_recomputed = 0
        # -- flight recorder -------------------------------------------------
        # the owning replica hands over the fleet's tracer and the tags its
        # pump events carry; a bare session times its phases on a disabled
        # tracer and records nothing
        self.tracer: Tracer = Tracer.disabled()
        self.trace_tags: Dict[str, Any] = {}

    # -- request intake -------------------------------------------------------
    def submit(self, rid: int, inp: np.ndarray, max_new: int, *,
               slo_class: str = "interactive", priority: int = 0,
               deadline_s: Optional[float] = None,
               recompute: bool = False,
               frontier: Optional[KVFrontier] = None,
               speculate: bool = True) -> None:
        """Queue a request.  ``slo_class``/``priority``/``deadline_s`` set
        its admission order (interactive before batch, higher priority
        first, soonest deadline first, then FIFO); defaults reproduce the
        legacy FIFO admission exactly.

        ``speculate=False`` opts this request out of speculative decoding:
        its slot is never drafted, it decodes one token per round even
        while the rest of the batch speculates (greedy outputs are token-
        exact either way; the opt-out exists for temperature>0 callers who
        want the plain carried-key sample stream).

        ``frontier`` resumes a previously checkpointed request: admission
        injects its KV pages and continues decode from its token frontier
        instead of prefilling (token-exact with the replay path).  A
        frontier that doesn't match this session (prompt, page size, or
        paging off) is ignored and the request prefills normally.
        ``recompute`` marks prefill work on this request as RECOMPUTED in
        telemetry (its first prefill already completed on a replica that
        died)."""
        if rid in self._out or rid in self.results:
            raise ValueError(f"request id {rid} already in session")
        inp = np.asarray(inp)
        max_new = int(max_new)
        if max_new <= 0:                              # nothing to generate
            self.results[rid] = np.asarray([], np.int64)
            self._instant.append(rid)
            return
        if inp.shape[1] + max_new > self.eng.cfg.max_len:
            raise ValueError(
                f"request {rid}: prompt_len={inp.shape[1]} + "
                f"max_new={max_new} exceeds max_len={self.eng.cfg.max_len}"
            )
        if self.paged:
            need = self.allocator.blocks_for(inp.shape[1] + max_new)
            if need > self.allocator.usable:
                raise ValueError(
                    f"request {rid}: needs {need} KV pages but the pool only "
                    f"has {self.allocator.usable}"
                )
        if recompute:
            self._recompute.add(rid)
        if frontier is not None:
            if self.paged:
                ok = (isinstance(frontier, KVFrontier)
                      and frontier.page_size == self.allocator.page_size
                      and tuple(int(t) for t in inp[0])
                      == tuple(frontier.prompt))
            elif self.scan_state:
                ok = (isinstance(frontier, StateFrontier)
                      and tuple(int(t) for t in inp[0])
                      == tuple(frontier.prompt))
            else:
                ok = False
            if ok and len(frontier.generated) >= max_new:
                # the frontier already covers everything this submission
                # asked for: complete instantly off the checkpointed tokens
                self.results[rid] = np.asarray(
                    list(frontier.generated[:max_new]), np.int64
                )
                self._instant.append(rid)
                self._recompute.discard(rid)
                self._pending_recovered += len(frontier.prompt) + max_new
                return
            if ok:
                self._frontiers[rid] = frontier
        from repro.serving.api import slo_order_key

        deadline_at = (time.monotonic() + deadline_s
                       if deadline_s is not None else math.inf)
        self._slo[rid] = slo_order_key(slo_class, priority, deadline_at,
                                       self._seq)
        self._seq += 1
        if not speculate:
            self._no_spec.add(rid)
        self._out[rid] = []
        self.queue.append((rid, inp, max_new))

    def _pop_next(self) -> Tuple[int, np.ndarray, int]:
        """Remove and return the queued request that should admit next
        (SLO order; position in ``self.queue`` is storage, not order)."""
        best = min(range(len(self.queue)),
                   key=lambda i: self._slo[self.queue[i][0]])
        return self.queue.pop(best)

    def _retire(self, rid: int) -> None:
        self._slo.pop(rid, None)
        self._prompt_of.pop(rid, None)
        self._frontiers.pop(rid, None)
        self._recompute.discard(rid)
        self._no_spec.discard(rid)

    def cancel(self, rid: int) -> bool:
        """Abandon a request (hedge loser): drop it from the queue or free
        its slot mid-decode.  Returns False if it already completed."""
        if rid in self.results:
            return False
        before = len(self.queue)
        self.queue = [q for q in self.queue if q[0] != rid]
        hit = len(self.queue) < before
        for s in np.nonzero(self.slots.request_id == rid)[0]:
            self.slots.request_id[s] = -1
            self.slots.remaining[s] = 0
            hit = True
        for s, st in list(self._prefilling.items()):
            if st["rid"] == rid:              # abandoned mid-prompt-ingest
                del self._prefilling[s]
                hit = True
        if self.paged:
            self._release_rid(rid)
        self._out.pop(rid, None)
        self._retire(rid)
        return hit

    def fits(self, prompt_len: int, max_new: int) -> bool:
        """Whether a request of this shape can EVER be admitted here — the
        same bounds ``submit`` enforces with ValueError, as a predicate so
        dispatchers can route around an undersized replica instead of
        crashing on it."""
        if prompt_len + max_new > self.eng.cfg.max_len:
            return False
        if self.paged:
            return self.allocator.blocks_for(prompt_len + max_new) <= self.allocator.usable
        return True

    # -- paged-KV bookkeeping -------------------------------------------------
    def prefix_match_len(self, prompt) -> int:
        """Reusable-prefix length of ``prompt`` ((1, Sp) array or pre-built
        token tuple) against this session's cache — the dispatcher's
        prefix-affinity score."""
        if not self.paged:
            return 0
        toks = prompt if type(prompt) is tuple else np.asarray(prompt)[0]
        return self.allocator.match_len(toks)

    def _set_table(self, s: int, pages: List[int]) -> None:
        self.tables[s, :] = TRASH_PAGE
        self.tables[s, :len(pages)] = pages

    def _release_rid(self, rid: int) -> None:
        """Free a request's pages (completion, cancel): deref every page it
        held — private gen pages free immediately, published prompt pages
        park in the LRU for future prefix hits — and trash the table row."""
        s = self._slot_of.pop(rid, None)
        if s is None:
            return
        for p in self._slot_pages[s]:
            self.allocator.deref(p)
        self._slot_pages[s] = []
        self.tables[s, :] = TRASH_PAGE

    def _extend_alloc(self, pages: List[int], total_blocks: int,
                      reserve: int = 0) -> bool:
        """Grow ``pages`` to ``total_blocks`` with fresh pages; all-or-
        nothing.  The up-front capacity check matters: alloc() under
        pressure evicts cached pages (destroying their prefix-cache
        entries permanently), so a grab that cannot fully succeed must
        fail BEFORE evicting anything.  ``reserve`` holds back capacity
        the caller still needs (e.g. an upcoming COW page)."""
        al = self.allocator
        need = total_blocks - len(pages) + reserve
        if need > al.free_pages + al.cached_pages:
            return False
        added: List[int] = []
        while len(pages) + len(added) < total_blocks:
            p = al.alloc()
            if p is None:                 # can't happen given the pre-check,
                for q in added:           # but stay all-or-nothing regardless
                    al.deref(q)
                return False
            added.append(p)
        pages.extend(added)
        return True

    def _admit_paged(self, s: int, rid: int, inp: np.ndarray, max_new: int) -> bool:
        """Paged admission: reuse cached prefix pages where possible, then
        allocate the remainder of the request's block budget.

        Cache-effectiveness counters live in ``self.allocator.stats`` only;
        ``pump`` derives its per-report fields as deltas of those totals.

        Returns False (with ALL page state rolled back) when the pool
        cannot satisfy the request right now — the caller requeues it and
        retries after running decodes release pages.
        """
        eng, al = self.eng, self.allocator
        ps = al.page_size
        tokens = [int(t) for t in np.asarray(inp)[0]]
        plen = len(tokens)
        total_blocks = al.blocks_for(plen + max_new)
        akey = jax.random.fold_in(self.key, self._admissions)

        entry = al.lookup_prompt(tokens)
        if entry is not None:
            # full-prompt hit: zero prefill.  The cached last-position
            # logits reproduce the first sampled token bit-exactly.
            pages = [int(p) for p in entry.pages]
            for p in pages:
                al.ref(p)
            # the partial boundary block takes this request's first gen
            # write; if another reader still holds it, reserve the COW page
            # up front so a doomed admission never evicts cache entries
            bi = plen // ps
            cow_needed = bool(plen % ps) and al.refcount[pages[bi]] > 1
            ok = self._extend_alloc(pages, total_blocks,
                                    reserve=1 if cow_needed else 0)
            if ok and cow_needed:
                fresh = al.cow(pages[bi])
                if fresh is None:
                    ok = False
                else:
                    self.cache = eng._copy_page(
                        self.cache, jnp.int32(pages[bi]), jnp.int32(fresh)
                    )
                    pages[bi] = fresh
            if not ok:
                for p in pages:
                    al.deref(p)
                return False
            self._set_table(s, pages)
            tok0 = eng._sample(jnp.asarray(entry.logits)[None], akey)[0]
            self.lens = self.lens.at[s].set(plen)
            al.stats.full_hits += 1
            al.stats.reused_tokens += plen
        else:
            m, shared = al.match_prefix(tokens)
            pages = [int(p) for p in shared]
            for p in pages:
                al.ref(p)
            if not self._extend_alloc(pages, total_blocks):
                for p in pages:
                    al.deref(p)
                return False
            if m > 0:
                # block-aligned prefix hit: the first m tokens never touch
                # the model — one continuation-prefill dispatch extends the
                # cached pages by the suffix and yields first-token logits.
                self._set_table(s, pages)
                suffix = jnp.asarray([tokens[m:]], jnp.int32)
                logits, self.cache = eng._prefill_paged(
                    eng.params, suffix, self.cache,
                    jnp.asarray(self.tables[s], jnp.int32), jnp.int32(m),
                )
                tok0 = eng._sample(logits, akey)[0]
                self.lens = self.lens.at[s].set(plen)
                # publish the completed prompt too: an identical repeat then
                # takes the zero-prefill full-hit path instead of re-running
                # this suffix prefill every time
                al.publish(tokens, pages[:al.blocks_for(plen)],
                           np.asarray(logits[0]))
                al.stats.prefix_hits += 1
                al.stats.reused_tokens += m
                al.stats.prefilled_tokens += plen - m
                if rid in self._recompute:
                    self._pending_recomputed += plen - m
                eng.telemetry.prefills += 1    # suffix prefill IS a dispatch
            else:
                self._set_table(s, pages)
                logits, pcache = eng.prefill({"inputs": jnp.asarray(inp)})
                nb_p = al.blocks_for(plen)
                self.cache = eng._place_pages(
                    self.cache, pcache, jnp.asarray(pages[:nb_p], jnp.int32)
                )
                al.publish(tokens, pages[:nb_p], np.asarray(logits[0]))
                tok0 = eng._sample(logits, akey)[0]
                self.lens = self.lens.at[s].set(plen)
                al.stats.misses += 1
                al.stats.prefilled_tokens += plen
                if rid in self._recompute:
                    self._pending_recomputed += plen
                eng.telemetry.prefills += 1
        self._admissions += 1
        self.tok = self.tok.at[s].set(tok0)
        self._slot_pages[s] = pages
        self._slot_of[rid] = s
        self._prompt_of[rid] = tuple(tokens)
        return True

    # -- introspection --------------------------------------------------------
    @property
    def idle(self) -> bool:
        """No work left AND no completion events still to report (instant
        max_new<=0 completions surface through the next pump)."""
        return (not self.queue and not self._instant and not self._prefilling
                and self.slots.occupancy == 0.0)

    @property
    def load(self) -> int:
        """Queued + ingesting + actively decoding requests."""
        return (len(self.queue) + len(self._prefilling)
                + int(np.sum(self.slots.request_id >= 0)))

    def inflight_rids(self) -> List[int]:
        """Incomplete rids, slot occupants first (the requeue set when this
        session's replica dies): decoding, then mid-prefill, then queued."""
        active = [int(r) for r in self.slots.request_id if r >= 0]
        active += [st["rid"] for _, st in sorted(self._prefilling.items())]
        return active + [rid for rid, _, _ in self.queue]

    # -- durable-KV checkpoint / restore --------------------------------------
    @property
    def supports_frontiers(self) -> bool:
        """Whether decoding requests can externalize resumable frontiers:
        paged sessions snapshot KV pages, scan-state sessions snapshot the
        constant-size recurrent state.  Contiguous-stripe sessions don't
        (an O(max_len) stripe copy per checkpoint is not worth paying)."""
        return self.paged or self.scan_state

    def extract_frontier(self, rid: int):
        """Snapshot one DECODING request's resumable state: prompt + tokens
        generated so far, the carried next token, and host copies of the KV
        pages (paged) or the per-slot recurrent state (scan-state) covering
        that frontier.  None for anything not actively decoding (queued and
        mid-prefill requests have nothing worth externalizing — their retry
        is a plain re-prefill, not recompute of paid-for work) and on
        backends without frontiers."""
        if self.scan_state:
            return self._extract_frontier_state(rid)
        if not self.paged:
            return None
        s = self._slot_of.get(rid)
        if s is None or int(self.slots.request_id[s]) != rid:
            return None
        prompt = self._prompt_of.get(rid)
        if prompt is None:
            return None
        # an inflight request never hits the max_len-1 clamp, so the host
        # bookkeeping IS the device lens: n == len(prompt) + len(generated)
        # exactly (avoids a device sync per checkpoint)
        n = len(prompt) + len(self._out.get(rid, ()))
        if n <= 0:
            return None
        al = self.allocator
        pages = al.extract_kv(self._slot_pages[s][:al.blocks_for(n)])
        return KVFrontier(
            prompt=prompt,
            generated=tuple(self._out.get(rid, ())),
            carry_tok=int(np.asarray(self.tok)[s]),
            pages_kv=self.eng.extract_pages(self.cache, pages),
            page_size=al.page_size,
        )

    def _extract_frontier_state(self, rid: int) -> Optional[StateFrontier]:
        """Scan-state checkpoint: one batch-axis slice per cache leaf —
        constant-size regardless of how far decode has progressed (the
        whole point of the backend).  Leaves keep the batch axis as a
        singleton so restore reuses the jitted ``_place`` admission
        dispatch."""
        hits = np.nonzero(self.slots.request_id == rid)[0]
        if hits.size == 0:
            return None
        s = int(hits[0])
        prompt = self._prompt_of.get(rid)
        if prompt is None:
            return None
        state = jax.tree.map(
            lambda a: np.asarray(a[:, s:s + 1]), self.cache
        )
        return StateFrontier(
            prompt=prompt,
            generated=tuple(self._out.get(rid, ())),
            carry_tok=int(np.asarray(self.tok)[s]),
            state=state,
        )

    def _admit_restored_state(self, s: int, rid: int, fr: StateFrontier,
                              max_new: int) -> bool:
        """Admit straight into decode from a checkpointed scan state: the
        slot's state leaves take the snapshot, decode resumes at the
        carried token — zero prefill, token-exact with the uninterrupted
        run (greedy).  Constant state means no allocation can fail, so
        unlike the paged twin this always succeeds."""
        eng = self.eng
        n = fr.tokens
        gen = list(fr.generated)
        self.cache = eng._place(
            self.cache, jax.tree.map(jnp.asarray, fr.state), int(s)
        )
        self.lens = self.lens.at[s].set(n)
        self._lens_host[s] = n
        self.tok = self.tok.at[s].set(jnp.int32(fr.carry_tok))
        self._prompt_of[rid] = tuple(fr.prompt)
        self._out[rid] = list(gen)
        self._admissions += 1
        self.slots.admit(s, rid, max_new - len(gen))
        # replay through report.tokens; the streaming client reconciles by
        # position and forwards only the unseen suffix
        self._restored.append((rid, gen))
        self._pending_recovered += n
        return True

    def extract_frontiers(self) -> List[Tuple[int, Any]]:
        """Checkpoint every decoding request (the periodic flush unit and
        the preemption-drain payload)."""
        out: List[Tuple[int, KVFrontier]] = []
        for r in self.slots.request_id:
            if r < 0:
                continue
            fr = self.extract_frontier(int(r))
            if fr is not None:
                out.append((int(r), fr))
        return out

    def decoding_lens(self) -> Dict[int, int]:
        """rid -> current frontier length for every decoding request,
        computed host-side (no device sync) — what an incremental flush
        checks before paying for a full ``extract_frontier``."""
        out: Dict[int, int] = {}
        for r in self.slots.request_id:
            rid = int(r)
            if rid < 0 or rid not in self._prompt_of:
                continue
            out[rid] = len(self._prompt_of[rid]) + len(self._out.get(rid, ()))
        return out

    def _admit_restored(self, s: int, rid: int, fr: KVFrontier,
                        max_new: int) -> bool:
        """Admit straight into decode from an injected frontier: fresh pages
        take the checkpointed KV, the slot resumes at the carried token —
        zero prefill, token-exact with the uninterrupted run (greedy).
        Returns False (no state change) under pool pressure; the caller
        requeues with the frontier intact."""
        eng, al = self.eng, self.allocator
        n = fr.tokens
        gen = list(fr.generated)
        pages = al.inject_kv(al.blocks_for(len(fr.prompt) + max_new))
        if pages is None:
            return False
        nb = al.blocks_for(n)
        dst = list(pages[:nb])
        # pad the inject to the next pow-2 block count: the jit traces key
        # on nb, so padding bounds compilation to log2(max_blocks) shapes
        # (pad rows land on TRASH_PAGE, the designated scribble page).
        # Padding happens host-side in numpy — a device concat would itself
        # compile once per distinct nb, which is what the bucket avoids.
        kv_host = fr.pages_kv
        nb_pad = 1 << (nb - 1).bit_length()
        if nb_pad > nb:
            pad = nb_pad - nb
            kv_host = jax.tree.map(
                lambda c: np.concatenate(
                    [c, np.zeros(c.shape[:1] + (pad,) + c.shape[2:],
                                 c.dtype)], axis=1), kv_host)
            dst += [TRASH_PAGE] * pad
        self.cache = eng._inject_pages(
            self.cache, jax.tree.map(jnp.asarray, kv_host),
            jnp.asarray(dst, jnp.int32))
        self._set_table(s, pages)
        self._slot_pages[s] = pages
        self._slot_of[rid] = s
        self._prompt_of[rid] = tuple(fr.prompt)
        self.tok = self.tok.at[s].set(jnp.int32(fr.carry_tok))
        self.lens = self.lens.at[s].set(n)
        self._lens_host[s] = n
        self._out[rid] = list(gen)
        self._admissions += 1
        self.slots.admit(s, rid, max_new - len(gen))
        # replay the checkpointed tokens through report.tokens: the
        # streaming client reconciles by per-replica position, so it
        # forwards only what the handle hasn't seen yet
        self._restored.append((rid, gen))
        self._pending_recovered += n
        return True

    def _drain_recovery(self, report: "PumpReport") -> None:
        report.recovered_tokens += self._pending_recovered
        report.recomputed_prefill_tokens += self._pending_recomputed
        self._pending_recovered = 0
        self._pending_recomputed = 0

    def _emit_restored(self, report: "PumpReport") -> None:
        for rid, toks in self._restored:
            if rid in self._out and toks:
                report.emitted[rid] = report.emitted.get(rid, 0) + len(toks)
                report.tokens.setdefault(rid, []).extend(toks)
        self._restored = []

    # -- the loop body --------------------------------------------------------
    def pump(self) -> PumpReport:
        """One engine cycle; safe to call when idle.

        Mixed mode (default): one token-budget admission+scheduling pass,
        fused prefill+decode dispatches until the admitted prompts are
        ingested (decode advances in every one), then one decode chunk
        scan — prefill never preempts decode and admission adds zero
        per-request dispatches.  Legacy mode (``mixed_step=False``): the
        PR-3 loop — one B=1 prefill dispatch per admission, then the
        chunk scan.

        The cycle runs inside an ``engine.pump`` span and each phase in a
        ``pump.*`` span on ``self.tracer``; the report's ``wall_s`` and
        phase walls are those spans' walls."""
        report = traced_pump(
            self.tracer, self.trace_tags,
            self._pump_mixed if self.mixed else self._pump_legacy)
        if report.mixed_steps or report.chunk_steps:      # the model ran
            self.eng.telemetry.decode_s += report.wall_s
        return report

    def _phase(self, name: str) -> Span:
        return self.tracer.begin(name, cat="engine", sampled=True)

    def _trace_admitted(self, report: PumpReport) -> None:
        """``req.admitted`` for each request this pump gave a slot, stamped
        as the pump's admission phase ends."""
        for rid in report.admitted:
            self.tracer.event("req.admitted", cat="req", rid=rid,
                              **self.trace_tags)

    def _pump_legacy(self) -> PumpReport:
        """One admission pass + one chunk scan (per-request prefill)."""
        eng, slots = self.eng, self.slots
        chunk = max(1, eng.cfg.decode_chunk)
        report = PumpReport()
        if self.paged:
            st = self.allocator.stats
            stats0 = (st.full_hits + st.prefix_hits, st.misses,
                      st.reused_tokens, st.prefilled_tokens)
        with self._phase("pump.admit") as sp:
            self._admit_legacy(report)
        report.admit_s = sp.wall_s
        self._trace_admitted(report)

        report.occupancy = slots.occupancy
        if self.paged:
            st = self.allocator.stats
            report.prefix_hits = st.full_hits + st.prefix_hits - stats0[0]
            report.prefix_misses = st.misses - stats0[1]
            report.reused_tokens = st.reused_tokens - stats0[2]
            report.prefilled_tokens = st.prefilled_tokens - stats0[3]
            report.page_occupancy = self.allocator.occupancy
            report.cached_pages = self.allocator.cached_pages
        if report.occupancy == 0.0:                   # nothing to decode
            self._drain_recovery(report)
            return report

        # decode one chunk for the whole slot batch
        with self._phase("pump.decode") as sp:
            active = jnp.asarray(slots.request_id >= 0)
            if self.paged:
                self.cache, self.tok, self.lens, self.key, toks, rows = eng._chunk_paged(
                    eng.params, self.cache, jnp.asarray(self.tables),
                    self.tok, self.lens, active, self.key, chunk
                )
            else:
                self.cache, self.tok, self.lens, self.key, toks, rows = eng._chunk(
                    eng.params, self.cache, self.tok, self.lens, active,
                    self.key, chunk
                )
        report.dispatch_s = sp.wall_s
        with self._phase("pump.decode_sync") as sp:
            toks_np, rows = jax.device_get((toks, rows))   # ONE read per chunk
            report.expert_rows += _rows_of(rows)
            n_slots = slots.n_slots
            for t in range(chunk):
                active = np.nonzero(slots.request_id >= 0)[0]
                for s in active:
                    rid = int(slots.request_id[s])
                    val = int(toks_np[t, s])
                    self._out[rid].append(val)
                    report.emitted[rid] = report.emitted.get(rid, 0) + 1
                    report.tokens.setdefault(rid, []).append(val)
                report.useful_tokens += len(active)
                report.wasted_tokens += n_slots - len(active)
                for rid in slots.step():
                    tokens = np.asarray(self._out.pop(rid), np.int64)
                    self.results[rid] = tokens
                    report.completed[rid] = tokens
                    self._retire(rid)
                    if self.paged:
                        self._release_rid(rid)
            if self.paged:
                # re-sample AFTER completions released their pages, so a
                # draining session reports decaying occupancy, not the
                # admission-time peak
                report.page_occupancy = self.allocator.occupancy
                report.cached_pages = self.allocator.cached_pages
        report.sync_s = sp.wall_s
        report.chunk_steps = chunk
        self._drain_recovery(report)

        tel = eng.telemetry
        tel.chunks += 1
        tel.useful_tokens += report.useful_tokens
        tel.wasted_tokens += report.wasted_tokens
        tel.completed_requests += len(report.completed)
        tel.prefix_hits += report.prefix_hits
        tel.prefix_misses += report.prefix_misses
        tel.reused_tokens += report.reused_tokens
        tel.prefilled_tokens += report.prefilled_tokens
        tel.recovered_tokens += report.recovered_tokens
        tel.recomputed_prefill_tokens += report.recomputed_prefill_tokens
        tel.expert_rows += report.expert_rows
        return report

    def _admit_legacy(self, report: PumpReport) -> None:
        """The legacy admission pass: while there is work and a free slot,
        place each request (restored frontier, paged admission, or one B=1
        prefill dispatch)."""
        eng, slots = self.eng, self.slots
        for rid in self._instant:
            report.completed[rid] = self.results[rid]
        self._instant = []
        for s in slots.free:
            if not self.queue:
                break
            rid, inp, max_new = self._pop_next()
            fr = self._frontiers.pop(rid, None)
            if fr is not None:
                admit = (self._admit_restored_state if self.scan_state
                         else self._admit_restored)
                if not admit(int(s), rid, fr, max_new):
                    # page pressure: requeue with the frontier intact so the
                    # retry still resumes instead of re-prefilling
                    self._frontiers[rid] = fr
                    self.queue.insert(0, (rid, inp, max_new))
                    break
                report.admitted.append(rid)
                continue
            if self.paged:
                if not self._admit_paged(int(s), rid, inp, max_new):
                    # page pressure: put it back and retry after decodes
                    # release pages (completions free at chunk boundaries)
                    self.queue.insert(0, (rid, inp, max_new))
                    break
            else:
                logits, pcache = eng.prefill({"inputs": jnp.asarray(inp)})
                self.cache = eng._place(self.cache, pcache, int(s))
                self.lens = self.lens.at[s].set(inp.shape[1])
                akey = jax.random.fold_in(self.key, self._admissions)
                self._admissions += 1
                self.tok = self.tok.at[s].set(eng._sample(logits, akey)[0])
                if self.scan_state:
                    # frontier extraction needs the prompt tuple; scan
                    # sessions track it so mid-decode checkpoints work
                    self._prompt_of[rid] = tuple(
                        int(t) for t in np.asarray(inp)[0]
                    )
                if rid in self._recompute:
                    self._pending_recomputed += int(inp.shape[1])
                eng.telemetry.prefills += 1
            slots.admit(int(s), rid, max_new)
            report.admitted.append(rid)
        self._emit_restored(report)

    # -- mixed-batch chunked prefill ------------------------------------------
    def _akey(self) -> Optional[jax.Array]:
        """Per-admission sampling key.  Greedy mode returns None without
        touching the device — argmax needs no key, and a fold_in per
        admission is measurable dispatch chatter at high request rates."""
        if self.eng.cfg.temperature <= 0.0:
            self._admissions += 1
            return None
        akey = jax.random.fold_in(self.key, self._admissions)
        self._admissions += 1
        return akey

    def _admit_mixed(self, s: int, rid: int, inp: np.ndarray, max_new: int) -> None:
        """Contiguous mixed admission: the prompt enters the slot as pending
        chunks; NO dispatch happens here — the prompt rides the next mixed
        steps alongside the ongoing decodes."""
        self._lens_host[s] = 0
        # the drafter needs the prompt history even without paging (paged
        # admissions record it for frontier extraction already)
        self._prompt_of[rid] = tuple(int(t) for t in np.asarray(inp)[0])
        self._prefilling[s] = dict(
            rid=rid, rem=np.asarray(inp)[0].astype(np.int64),
            plen=int(inp.shape[1]), max_new=int(max_new), akey=self._akey(),
            tokens=None,
        )

    def _admit_paged_mixed(self, s: int, rid: int, inp: np.ndarray,
                           max_new: int) -> bool:
        """Paged mixed admission.  Full-prompt cache hits go straight to
        decode off the cached logits (zero model work, identical to the
        legacy path); everything else allocates the request's whole block
        budget up front and queues the un-cached suffix as pending chunks
        — ``prefilled_tokens`` then accrues per chunk *dispatched*, never
        double-counting a prompt token across chunks.

        Returns False (all page state rolled back) under pool pressure."""
        eng, al = self.eng, self.allocator
        ps = al.page_size
        tokens = [int(t) for t in np.asarray(inp)[0]]
        plen = len(tokens)
        total_blocks = al.blocks_for(plen + max_new)

        entry = al.lookup_prompt(tokens)
        if entry is not None:
            # full-prompt hit: zero prefill, bit-exact first token
            pages = [int(p) for p in entry.pages]
            for p in pages:
                al.ref(p)
            bi = plen // ps
            cow_needed = bool(plen % ps) and al.refcount[pages[bi]] > 1
            ok = self._extend_alloc(pages, total_blocks,
                                    reserve=1 if cow_needed else 0)
            if ok and cow_needed:
                fresh = al.cow(pages[bi])
                if fresh is None:
                    ok = False
                else:
                    self.cache = eng._copy_page(
                        self.cache, jnp.int32(pages[bi]), jnp.int32(fresh)
                    )
                    pages[bi] = fresh
            if not ok:
                for p in pages:
                    al.deref(p)
                return False
            self._set_table(s, pages)
            tok0 = eng._sample(jnp.asarray(entry.logits)[None], self._akey())[0]
            self.tok = self.tok.at[s].set(tok0)
            self._lens_host[s] = plen
            al.stats.full_hits += 1
            al.stats.reused_tokens += plen
            self._slot_pages[s] = pages
            self._slot_of[rid] = s
            self._prompt_of[rid] = tuple(tokens)
            self.slots.admit(s, rid, max_new)     # decoding immediately
            return True

        if al.enable_reuse and self._ingest_overlap(tokens):
            # another slot is mid-ingest on this prompt (or a block-sharing
            # sibling): admitting now would redundantly re-prefill KV the
            # cache is about to hold.  Defer — publish lands when that slot's
            # last chunk completes, and the retry becomes a cache hit (the
            # legacy path got this for free because its admission prefill
            # was synchronous).
            return False

        m, shared = al.match_prefix(tokens)
        pages = [int(p) for p in shared]
        for p in pages:
            al.ref(p)
        if not self._extend_alloc(pages, total_blocks):
            for p in pages:
                al.deref(p)
            return False
        self._set_table(s, pages)
        self._slot_pages[s] = pages
        self._slot_of[rid] = s
        self._prompt_of[rid] = tuple(tokens)
        if m > 0:
            # block-aligned prefix hit: the first m tokens never touch the
            # model — only the suffix is queued for chunked prefill
            al.stats.prefix_hits += 1
            al.stats.reused_tokens += m
        else:
            al.stats.misses += 1
        self._lens_host[s] = m
        self._prefilling[s] = dict(
            rid=rid, rem=np.asarray(tokens[m:], np.int64), plen=plen,
            max_new=int(max_new), akey=self._akey(), tokens=tokens,
        )
        return True

    def _ingest_overlap(self, tokens: List[int]) -> bool:
        """Whether any slot is currently ingesting a prompt this one would
        share cached pages with once published: an identical prompt, or one
        sharing at least a whole block-aligned prefix."""
        ps = self.allocator.page_size
        for st in self._prefilling.values():
            ft = st["tokens"]
            if ft is None:
                continue
            if tokens == ft:
                return True
            nb = min(len(tokens), len(ft)) // ps
            if nb > 0 and tokens[:nb * ps] == ft[:nb * ps]:
                return True
        return False

    def _schedule_chunks(self) -> List[Tuple[int, np.ndarray]]:
        """Token-budget packing for the next mixed step: decode slots take
        one token each off the budget; ingesting slots get one fixed-width
        chunk quantum each until the remainder runs out.  The quantum is
        the ONLY chunk width ever dispatched (tails ride the same grid with
        masked columns), so traces never depend on prompt lengths or wave
        mixtures.  At least one slot is always scheduled, so ingestion
        cannot starve under a tiny budget or a decode-saturated batch."""
        # SLO admission order applies to chunk scheduling too: under a
        # budget that cannot feed every ingesting slot, interactive /
        # high-priority / deadline-soonest prompts take their chunk first.
        # All-default metadata degenerates to submission (FIFO) order —
        # within one pump's admission wave that coincides with the legacy
        # slot order, since free slots fill in ascending index from a FIFO
        # queue
        pending = sorted(
            self._prefilling.items(),
            key=lambda kv: (self._slo.get(kv[1]["rid"], (0, 0, math.inf, 0)),
                            kv[0]),
        )
        if not pending:
            return []
        n_decode = int(np.sum(self.slots.request_id >= 0))
        room = max(1, int(self.token_budget) - n_decode)
        quantum = self.eng.chunk_quantum(self.token_budget)
        k = max(1, room // quantum)
        return [(s, st["rem"][:quantum]) for s, st in pending[:k]]

    def _pump_mixed(self) -> PumpReport:
        """One mixed cycle: admission -> budget-bounded fused prefill+decode
        dispatches until this pump's admissions are fully ingested (decode
        rows advance a token in every one) -> one decode chunk scan."""
        eng, slots = self.eng, self.slots
        chunk = max(1, eng.cfg.decode_chunk)
        n_slots = slots.n_slots
        report = PumpReport()

        if self.paged:
            st0 = self.allocator.stats
            stats0 = (st0.full_hits + st0.prefix_hits, st0.misses,
                      st0.reused_tokens, st0.prefilled_tokens)
        with self._phase("pump.admit") as sp:
            self._admit_wave(report)
        report.admit_s = sp.wall_s
        self._trace_admitted(report)

        decode_active = slots.request_id >= 0
        report.occupancy = (
            int(np.sum(decode_active)) + len(self._prefilling)
        ) / n_slots

        def _complete(rid: int) -> None:
            tokens = np.asarray(self._out.pop(rid), np.int64)
            self.results[rid] = tokens
            report.completed[rid] = tokens
            self._retire(rid)
            if self.paged:
                self._release_rid(rid)

        def _paged_report_tail() -> None:
            if not self.paged:
                return
            st1 = self.allocator.stats
            report.prefix_hits = st1.full_hits + st1.prefix_hits - stats0[0]
            report.prefix_misses = st1.misses - stats0[1]
            report.reused_tokens = st1.reused_tokens - stats0[2]
            report.prefilled_tokens = st1.prefilled_tokens - stats0[3]
            # post-release sample: a draining session reports decaying
            # occupancy, not the admission-time peak
            report.page_occupancy = self.allocator.occupancy
            report.cached_pages = self.allocator.cached_pages

        sched = self._schedule_chunks()
        if not sched and not decode_active.any():       # nothing to run
            _paged_report_tail()
            self._drain_recovery(report)
            return report

        if sched:
            # the fused prefill+decode dispatches; the paged tier's logits
            # reads inside them are host syncs, not dispatch
            with self._phase("pump.prefill") as sp:
                emits, done, publish_s = self._ingest(report, sched)
                sp.end(steps=report.mixed_steps)
            report.dispatch_s += sp.wall_s - publish_s
            report.sync_s += publish_s
            # flush the deferred emitted-token reads (one D2H per step, all
            # issued after the dispatches), then the completions they finish
            with self._phase("pump.emit_sync") as sp:
                for tok_dev, pairs, rows in emits:
                    vals, rows = jax.device_get((tok_dev, rows))
                    report.expert_rows += _rows_of(rows)
                    for s, rid in pairs:
                        val = int(vals[s])
                        self._out[rid].append(val)
                        report.emitted[rid] = report.emitted.get(rid, 0) + 1
                        report.tokens.setdefault(rid, []).append(val)
                for rid in done:
                    _complete(rid)
            report.sync_s += sp.wall_s

        # ---- the decode phase ---------------------------------------------
        decode_active = slots.request_id >= 0
        if decode_active.any() and self.spec_k > 0:
            # speculative rounds replace the chunk scan: each round is one
            # fused draft-verify dispatch advancing every decoding slot by
            # 1 + accepted tokens (>= the scan's 1 token per step)
            self._decode_speculative(report, chunk, _complete)
        elif decode_active.any():
            with self._phase("pump.decode") as sp:
                active_j = jnp.asarray(decode_active)
                lens_dev = jnp.asarray(self._lens_host, jnp.int32)
                if self.paged:
                    (self.cache, self.tok, self.lens, self.key, toks,
                     rows) = eng._chunk_paged(
                        eng.params, self.cache, jnp.asarray(self.tables),
                        self.tok, lens_dev, active_j, self.key, chunk
                    )
                else:
                    (self.cache, self.tok, self.lens, self.key, toks,
                     rows) = eng._chunk(
                        eng.params, self.cache, self.tok, lens_dev, active_j,
                        self.key, chunk
                    )
                self._lens_host[decode_active] = np.minimum(
                    self._lens_host[decode_active] + chunk, eng.cfg.max_len - 1
                )
            report.dispatch_s += sp.wall_s
            with self._phase("pump.decode_sync") as sp:
                toks_np, rows = jax.device_get((toks, rows))  # ONE read per chunk
                report.expert_rows += _rows_of(rows)
                for t in range(chunk):
                    active = np.nonzero(slots.request_id >= 0)[0]
                    for s in active:
                        rid = int(slots.request_id[s])
                        val = int(toks_np[t, s])
                        self._out[rid].append(val)
                        report.emitted[rid] = report.emitted.get(rid, 0) + 1
                        report.tokens.setdefault(rid, []).append(val)
                    report.useful_tokens += len(active)
                    report.wasted_tokens += n_slots - len(active)
                    for rid in slots.step():
                        _complete(rid)
            report.chunk_steps = chunk
            report.sync_s += sp.wall_s

        _paged_report_tail()
        self._drain_recovery(report)

        tel = eng.telemetry
        tel.mixed_steps += report.mixed_steps
        tel.prefill_chunks += report.prefill_chunks
        if report.chunk_steps:
            tel.chunks += 1
        tel.useful_tokens += report.useful_tokens
        tel.wasted_tokens += report.wasted_tokens
        tel.completed_requests += len(report.completed)
        tel.prefix_hits += report.prefix_hits
        tel.prefix_misses += report.prefix_misses
        tel.reused_tokens += report.reused_tokens
        tel.prefilled_tokens += report.prefilled_tokens
        tel.recovered_tokens += report.recovered_tokens
        tel.recomputed_prefill_tokens += report.recomputed_prefill_tokens
        tel.drafted_tokens += report.drafted_tokens
        tel.accepted_tokens += report.accepted_tokens
        tel.spec_rounds += report.spec_rounds
        tel.expert_rows += report.expert_rows
        return report

    def _admit_wave(self, report: PumpReport) -> None:
        """The mixed admission pass: while there is work and a slot neither
        decoding nor ingesting, queue the request's prompt for chunked
        prefill (or inject its restored frontier)."""
        slots = self.slots
        for rid in self._instant:
            report.completed[rid] = self.results[rid]
        self._instant = []
        for s in slots.free:
            if not self.queue:
                break
            s = int(s)
            if s in self._prefilling:
                continue
            rid, inp, max_new = self._pop_next()
            fr = self._frontiers.pop(rid, None)
            if fr is not None:
                if not self._admit_restored(s, rid, fr, max_new):
                    # page pressure: requeue with the frontier intact so the
                    # retry still resumes instead of re-prefilling
                    self._frontiers[rid] = fr
                    self.queue.insert(0, (rid, inp, max_new))
                    break
            elif self.paged:
                if not self._admit_paged_mixed(s, rid, inp, max_new):
                    # page pressure: put it back and retry after decodes
                    # release pages (completions free at chunk boundaries)
                    self.queue.insert(0, (rid, inp, max_new))
                    break
            else:
                self._admit_mixed(s, rid, inp, max_new)
            report.admitted.append(rid)
        self._emit_restored(report)

    def _ingest(self, report: PumpReport, sched: List[Tuple[int, np.ndarray]]
                ) -> Tuple[List[Tuple[Any, List[Tuple[int, int]], Any]],
                           List[int], float]:
        """Drive this pump's admissions to completion: every iteration is
        one budget-bounded mixed step, and decode rows advance a token in
        each — ingestion wall is decode wall, never a stall (the legacy
        pump symmetrically runs ALL its B=1 admission prefills per cycle,
        with every decode slot idle while it does).  Emitted-token reads
        are deferred past the loop: the carried-token arrays stay valid
        (tok is never donated), so the steps pipeline with no per-step
        sync.  Returns the deferred (token array, (slot, rid) pairs, expert
        rows) reads, the rids the steps finished, and the seconds spent in
        the paged tier's ``pump.publish_sync`` logits reads."""
        eng, slots = self.eng, self.slots
        n_slots = slots.n_slots
        greedy = eng.cfg.temperature <= 0.0
        deferred_emits: List[Tuple[Any, List[Tuple[int, int]], Any]] = []
        deferred_done: List[int] = []
        publish_s = 0.0
        while sched:
            decode_active = slots.request_id >= 0
            Q = eng.chunk_quantum(self.token_budget)    # the one chunk width
            chunks_np = np.zeros((n_slots, Q), np.int32)
            new_lens = np.zeros((n_slots,), np.int32)
            for s, c in sched:
                chunks_np[s, :len(c)] = c
                new_lens[s] = len(c)
            new_lens[decode_active] = 1
            # decode rows emit their carried token; record WHICH (slot, rid)
            # pairs emit now, read the values after the loop
            pairs = [(int(s), int(slots.request_id[s]))
                     for s in np.nonzero(decode_active)[0]]
            carried = self.tok
            is_decode = jnp.asarray(decode_active)
            # attention window: pow-2 bucket over the step's content
            # frontier, so score work tracks real lengths, not max_len.
            # Only rows actually advancing count — a freed slot's stale
            # mirror entry must not ratchet the window up for the rest of
            # the session's life.  Floored at Q so (Q, aw) pairs stay
            # inside the enumerated warm_mixed_traces grid (aw >= Q, both
            # pow-2, aw <= max_len).
            need = int(np.max(np.where(new_lens > 0,
                                       self._lens_host + new_lens, 0)))
            aw = max(1 << (max(1, need) - 1).bit_length(), Q)
            aw = min(aw, eng.cfg.max_len)
            # device lens comes from the host mirror: admissions never touch
            # the device, so the mirror is the single source of truth here
            lens_dev = jnp.asarray(self._lens_host, jnp.int32)
            if self.paged:
                logits, self.cache, self.lens, rows = eng._mixed_paged(
                    eng.params, self.cache, jnp.asarray(self.tables),
                    jnp.asarray(chunks_np), self.tok, lens_dev,
                    jnp.asarray(new_lens), is_decode, aw,
                )
            else:
                logits, self.cache, self.lens, rows = eng._mixed(
                    eng.params, self.cache, jnp.asarray(chunks_np), self.tok,
                    lens_dev, jnp.asarray(new_lens), is_decode, aw,
                )
            deferred_emits.append((carried, pairs, rows))
            self._lens_host += new_lens
            report.mixed_steps += 1
            # rows finishing their prompt THIS step start decoding from the
            # step's last-position logits
            completing = [s for s, c in sched
                          if len(self._prefilling[s]["rem"]) == len(c)]
            # decode rows advanced one token: emit the carried one, sample
            # next.  Greedy mode folds the completing rows' first-token
            # argmax into the SAME batched sample (argmax needs no key).
            if greedy:
                nxt = eng._sample(logits, self.key)
                upd = decode_active.copy()
                upd[completing] = True
                self.tok = jnp.where(jnp.asarray(upd), nxt, self.tok)
            else:
                self.key, sub = jax.random.split(self.key)
                nxt = eng._sample(logits, sub)
                self.tok = jnp.where(is_decode, nxt, self.tok)
                for s in completing:
                    tok0 = eng._sample(logits[s][None],
                                       self._prefilling[s]["akey"])[0]
                    self.tok = self.tok.at[s].set(tok0)
            logits_np = None
            if self.paged and completing:
                # the prefix cache publishes the finished prompts' logits:
                # this read waits for the step to finish on the device
                with self._phase("pump.publish_sync") as sp:
                    logits_np = np.asarray(logits)
                publish_s += sp.wall_s
            report.useful_tokens += len(pairs)
            report.wasted_tokens += n_slots - len(pairs) - len(sched)
            deferred_done.extend(slots.step())
            # prefill rows consumed their chunk
            for s, c in sched:
                stt = self._prefilling[s]
                stt["rem"] = stt["rem"][len(c):]
                report.prefill_chunks += 1
                if stt["rid"] in self._recompute:
                    self._pending_recomputed += len(c)
                if self.paged:
                    self.allocator.stats.prefilled_tokens += len(c)
                if len(stt["rem"]) == 0:
                    if self.paged:
                        al = self.allocator
                        al.publish(
                            stt["tokens"],
                            self._slot_pages[s][:al.blocks_for(stt["plen"])],
                            logits_np[s],
                        )
                    slots.admit(s, stt["rid"], stt["max_new"])
                    del self._prefilling[s]
                    eng.telemetry.prefills += 1
            sched = self._schedule_chunks()
        return deferred_emits, deferred_done, publish_s

    # -- speculative decode rounds -------------------------------------------
    def _decode_speculative(self, report: PumpReport, rounds: int,
                            complete: Callable[[int], None]) -> None:
        """The decode phase with speculation on: up to ``rounds`` draft +
        fused-verify rounds instead of the ragged chunk scan.

        Per round: the drafter proposes up to ``spec_k`` continuation
        tokens per decoding slot from its full token history (prompt +
        generated + carried token, all host-known); drafts ride token
        columns 1..d of ONE spec mixed step (``new_len = 1 + d``, ragged
        per row — opted-out or fully-emitted slots just run d = 0); the
        device returns the (3, B, Q) verdict and the host emits the carry
        plus the longest accepted prefix.  The per-round host sync is
        inherent to speculation — the next round's drafts need this
        round's accepted tokens — but each synced dispatch now yields up
        to ``spec_k + 1`` tokens per slot instead of the scan's 1.

        Rollback is the write-then-trim contract: rejected draft columns
        already wrote KV at positions >= the accepted frontier, but
        ``_lens_host`` (the single source of truth for cache lengths, and
        what ``extract_frontier`` derives its page count from) only ever
        advances by 1 + accepted, so those positions stay masked garbage
        until the next round's real writes land on them.  Contiguous
        stripes need nothing else; paged pools need no allocator calls
        either, because every page at or beyond a slot's write frontier
        is slot-exclusive by the admission COW invariant — shared
        prefix-cache pages are never scribbled on."""
        eng, slots = self.eng, self.slots
        n_slots = slots.n_slots
        Qs = spec_quantum(self.spec_k)
        drafter = eng.drafter
        # ONE initial carry sync; afterwards the verdicts keep it host-known
        with self._phase("pump.decode_sync") as sp:
            carry = np.asarray(self.tok).astype(np.int64).copy()
        report.sync_s += sp.wall_s
        executed = 0
        for _ in range(rounds):
            active = np.nonzero(slots.request_id >= 0)[0]
            if len(active) == 0:
                break
            draft = self._phase("pump.draft")
            chunks_np = np.zeros((n_slots, Qs), np.int32)
            new_lens = np.zeros((n_slots,), np.int32)
            d_of = np.zeros((n_slots,), np.int64)
            for s in active:
                rid = int(slots.request_id[s])
                d = 0
                # never draft past the request's budget: emitting carry +
                # accepted <= remaining keeps completions exact and the
                # write frontier inside the allocated pages
                k = min(self.spec_k, int(slots.remaining[s]) - 1, Qs - 1)
                if k > 0 and rid not in self._no_spec:
                    ctx = list(self._prompt_of.get(rid, ()))
                    ctx += self._out[rid]
                    ctx.append(int(carry[s]))
                    drafts = drafter.propose(ctx, k)[:k]
                    d = len(drafts)
                    if d:
                        chunks_np[s, 1:1 + d] = drafts
                d_of[s] = d
                new_lens[s] = 1 + d
            # attention window: same pow-2 bucket rule as the mixed loop,
            # floored at the spec column quantum so (Qs, aw) pairs stay on
            # the warm_spec_traces grid
            need = int(np.max(self._lens_host[active] + new_lens[active]))
            aw = max(1 << (max(1, need) - 1).bit_length(), Qs)
            aw = min(aw, eng.cfg.max_len)
            is_decode = jnp.asarray(slots.request_id >= 0)
            lens_dev = jnp.asarray(self._lens_host, jnp.int32)
            tok_dev = jnp.asarray(carry.astype(np.int32))
            draft.end()
            with self._phase("pump.decode") as disp:
                if self.paged:
                    verdict, self.cache, self.key, rows = eng._spec_paged(
                        eng.params, self.cache, jnp.asarray(self.tables),
                        jnp.asarray(chunks_np), tok_dev, lens_dev,
                        jnp.asarray(new_lens), is_decode, self.key, aw,
                    )
                else:
                    verdict, self.cache, self.key, rows = eng._spec(
                        eng.params, self.cache, jnp.asarray(chunks_np), tok_dev,
                        lens_dev, jnp.asarray(new_lens), is_decode, self.key, aw,
                    )
            sync = self._phase("pump.decode_sync")
            v, rows = jax.device_get((verdict, rows))  # ONE read a round
            report.expert_rows += _rows_of(rows)
            counts = np.zeros(n_slots, np.int64)
            round_drafted = 0
            round_accepted = 0
            for s in active:
                rid = int(slots.request_id[s])
                d = int(d_of[s])
                a = 0
                while a < d and v[0, s, a]:
                    a += 1
                vals = [int(carry[s])]
                vals += [int(chunks_np[s, 1 + j]) for j in range(a)]
                self._out[rid].extend(vals)
                report.emitted[rid] = report.emitted.get(rid, 0) + len(vals)
                report.tokens.setdefault(rid, []).extend(vals)
                # next carry: the replacement at the first rejection, or
                # the bonus token after a fully accepted draft run
                carry[s] = int(v[1, s, a]) if a < d else int(v[2, s, d])
                self._lens_host[s] += len(vals)
                counts[s] = len(vals)
                round_drafted += d
                round_accepted += a
            report.useful_tokens += int(counts.sum())
            # rejected drafts are paid-for, undelivered compute — wasted,
            # exactly like the scan's idle-slot tokens
            report.wasted_tokens += (n_slots - len(active))
            report.wasted_tokens += round_drafted - round_accepted
            report.drafted_tokens += round_drafted
            report.accepted_tokens += round_accepted
            if round_drafted:
                report.spec_rounds += 1
                rate = round_accepted / round_drafted
                self.spec_accept_ewma = (
                    rate if self.spec_accept_ewma is None
                    else 0.3 * rate + 0.7 * self.spec_accept_ewma)
            executed += 1
            for rid in slots.advance(counts):
                complete(rid)
            sync.end()
            report.dispatch_s += disp.wall_s
            report.sync_s += draft.wall_s + sync.wall_s
        # re-sync the device-side mirrors once for whoever reads them next
        # (legacy-path admissions, introspection); _lens_host stayed exact
        self.tok = jnp.asarray(carry.astype(np.int32))
        self.lens = jnp.asarray(self._lens_host.astype(np.int32))
        report.chunk_steps = executed


class DecodeSlots:
    """Continuous batching: fixed decode slots, per-slot request ids.

    The engine decodes a full (B_slots) batch every step; finished or empty
    slots are refilled from the queue (prefill on admit).  Slot occupancy is
    what utilization metrics report to the autoscaler.
    """

    def __init__(self, n_slots: int):
        self.n_slots = n_slots
        self.request_id = np.full(n_slots, -1, dtype=np.int64)
        self.remaining = np.zeros(n_slots, dtype=np.int64)

    @property
    def free(self) -> np.ndarray:
        return np.nonzero(self.request_id < 0)[0]

    @property
    def occupancy(self) -> float:
        return float(np.mean(self.request_id >= 0))

    def admit(self, slot: int, request_id: int, new_tokens: int) -> None:
        self.request_id[slot] = request_id
        self.remaining[slot] = new_tokens

    def step(self) -> list:
        """Advance one decode step; returns request ids that finished."""
        active = self.request_id >= 0
        self.remaining[active] -= 1
        done = np.nonzero(active & (self.remaining <= 0))[0]
        finished = self.request_id[done].tolist()
        self.request_id[done] = -1
        return finished

    def advance(self, counts: np.ndarray) -> list:
        """Variable-width step (speculative rounds): every active slot
        advances by its own ``counts[slot]`` emitted tokens; returns
        request ids that finished.  ``step()`` is ``advance(ones)``."""
        active = self.request_id >= 0
        c = np.asarray(counts, np.int64)
        self.remaining[active] -= c[active]
        done = np.nonzero(active & (self.remaining <= 0))[0]
        finished = self.request_id[done].tolist()
        self.request_id[done] = -1
        return finished
