#!/usr/bin/env python3
"""Chip smoke test: the fleet's serving path on one TPU at qwen3-0.6b's
published width.

Drives the normal path (``FleetClient`` -> ``Dispatcher`` -> ``Replica`` ->
``QueueSession.pump`` -> ``Model.step_mixed``) through ``FleetRuntime`` in
this one process, with qwen3-0.6b's published config (28 layers, d_model
1024, 16/8 heads of 128, d_ff 3072, vocabulary 151,936, bf16) and random
weights from ``--seed``.  Two tiers, each one replica: ``cost`` (contiguous
KV cache) and ``capacity`` (paged KV cache).  Two phases serve the same
requests: ``xla`` (XLA attention) and ``pallas`` (the Pallas decode and
mixed-step kernels).

Checks, any failure exits non-zero:
  * every request completes with ``max_new`` tokens inside the vocabulary,
    none dropped or failed;
  * the logits of each engine's chunked prefill, and of one decode step
    after it, are finite and agree with a plain float32 whole-sequence
    forward (``Model.prefill``) on the same weights within ``LOGIT_TOL``;
  * the Pallas phase's logits agree with the XLA phase's within the same
    tolerance.

The lines before the last are smoke readings, not benchmark numbers.  The
last line is one JSON object naming the device.  Off a TPU the script
stops at once and names the platform it found.

    python3 chip_smoke.py [--seed 0] [--requests 16]
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-0.6b"
MAX_LEN, BATCH, PAGE = 2048, 8, 16
PREFILL_CHUNK = 512          # mixed-step token budget: Q = 512 / 8 = 64
MAX_NEW = 32
PROMPT_LENS = (128, 1024)    # traffic prompt lengths, uniform from --seed
CHECK_ROWS, CHECK_LEN = 4, 300   # reference prompts: 4 chunks of 64 + 44
# bf16 serving vs a float32 forward: relative L2 error of a logits row,
# ||a - b|| / ||b||, the largest over the checked rows
LOGIT_TOL = 0.05


def say(*parts) -> None:
    print(*parts, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    it is set (JAX reads it itself), else one fixed directory in the
    checkout, so that a second run on the same disk hits."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Backend compiles (and persistent-cache hits) that JAX reports."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.s, self.hits


def make_tiers(seed: int, use_pallas: bool, reduced: bool = False):
    from repro.fleet.runtime import TierSpec

    common = dict(
        arch=ARCH, reduced=reduced, param_seed=seed,
        model_overrides={"use_pallas": True} if use_pallas else None,
        max_len=MAX_LEN, decode_batch=BATCH, decode_chunk=8, queue_limit=4,
        prefill_chunk=PREFILL_CHUNK, capacity_prefill_chunk=PREFILL_CHUNK,
        # one replica per tier, never more: each holds its own KV cache
        # (BATCH x MAX_LEN tokens, 1.75 GiB at published width)
        base_capacity=1, initial_replicas=1, min_replicas=1,
    )
    return [
        TierSpec(name="cost", cost_per_hour=1.0, nominal_t_max=1.0,
                 latency_s=2.0, **common),
        TierSpec(name="capacity", cost_per_hour=3.0, nominal_t_max=3.0,
                 latency_s=1.0, paged_kv=True, page_size=PAGE,
                 num_pages=1 + BATCH * MAX_LEN // PAGE, **common),
    ]


def make_traffic(seed: int, n: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=n)
    prompts = [rng.integers(0, vocab, size=int(t), dtype=np.int64) for t in lens]
    check = rng.integers(0, vocab, size=(CHECK_ROWS, CHECK_LEN), dtype=np.int32)
    nxt = rng.integers(0, vocab, size=CHECK_ROWS, dtype=np.int32)
    return prompts, check, nxt


def engine_logits(eng, prompts: np.ndarray, nxt: np.ndarray):
    """Logits of the engine's own jitted steps: its chunked prefill of
    each prompt (one per slot, the mixed step at the warmed Q), then one
    decode step (the served decode path) feeding ``nxt``.  Returns
    (prefill, decode) float32 arrays of shape (rows, vocab)."""
    import jax
    import jax.numpy as jnp

    n, Q = eng.cfg.decode_batch, eng.chunk_quantum(eng.cfg.prefill_chunk)
    rows, L = prompts.shape
    lens = np.zeros(n, np.int32)
    tok, isd = jnp.zeros(n, jnp.int32), jnp.zeros(n, bool)
    tables = None
    if eng.paged:
        # slot b owns pages 1 + b*max_blocks ... (page 0 is the trash page)
        tables = jnp.asarray(1 + np.arange(n * eng.max_blocks, dtype=np.int32)
                             .reshape(n, eng.max_blocks))
        cache = eng.model.empty_page_pool(eng.num_pages, eng.cfg.page_size)
    else:
        cache = eng.model.empty_cache(n, eng.cfg.max_len)
    for s in range(0, L, Q):
        piece = prompts[:, s:s + Q]
        chunks = np.zeros((n, Q), np.int32)
        chunks[:rows, :piece.shape[1]] = piece
        new = np.zeros(n, np.int32)
        new[:rows] = piece.shape[1]
        aw = min(max(1 << (s + piece.shape[1] - 1).bit_length(), Q), eng.cfg.max_len)
        args = (jnp.asarray(chunks), tok, jnp.asarray(lens), jnp.asarray(new), isd, aw)
        if eng.paged:
            logits, cache = eng._mixed_paged(eng.params, cache, tables, *args)[:2]
        else:
            logits, cache = eng._mixed(eng.params, cache, *args)[:2]
        # finish each step before the next, as a served pump does: on the
        # CPU backend, back-to-back steps that donate the cache now and
        # then returned wrong logits
        jax.block_until_ready(cache)
        lens[:rows] += piece.shape[1]
    prefill = np.asarray(logits[:rows], np.float32)
    toks = np.zeros((n, 1), np.int32)
    toks[:rows, 0] = nxt
    logits, _ = eng._decode(eng.params, jnp.asarray(toks), cache,
                            jnp.asarray(lens), None, tables)
    return prefill, np.asarray(logits[:rows], np.float32)


def reference_logits(seed: int, prompts: np.ndarray, nxt: np.ndarray,
                     reduced: bool = False):
    """The plain reference: a float32 whole-sequence ``Model.prefill`` on
    the same (seeded) weights, at the prompt's last position and at the
    position after ``nxt``."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduce()
    params = Model(cfg).init(jax.random.key(seed))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    model = Model(dataclasses.replace(cfg, dtype="float32"))
    fwd = jax.jit(lambda p, x: model.prefill(p, {"inputs": x})[0])
    pre = np.asarray(fwd(params, jnp.asarray(prompts)), np.float32)
    ext = np.concatenate([prompts, nxt[:, None]], axis=1)
    dec = np.asarray(fwd(params, jnp.asarray(ext)), np.float32)
    return pre, dec


def rel_err(a: np.ndarray, b: np.ndarray) -> float:
    """Largest relative L2 error ||a - b|| / ||b|| over rows."""
    return float(np.max(np.linalg.norm(a - b, axis=-1)
                        / np.linalg.norm(b, axis=-1)))


def run_phase(name: str, seed: int, prompts, check, nxt, counter,
              reduced: bool = False):
    """Serve ``prompts`` through a fresh fleet; return (outputs by request
    index, {tier: (prefill logits, decode logits)}, readings)."""
    import jax

    from repro.fleet.client import FleetClient
    from repro.fleet.runtime import FleetConfig, FleetRuntime
    from repro.serving.api import InferenceRequest, RequestStatus

    c0 = counter.snapshot()
    t0 = time.perf_counter()
    rt = FleetRuntime(make_tiers(seed, name == "pallas", reduced), [],
                      FleetConfig(seed=seed))
    client = FleetClient(rt)
    handles = [client.submit(InferenceRequest(prompt=p, max_new=MAX_NEW))
               for p in prompts]
    rt.warmup()
    t1 = time.perf_counter()
    client.drain()
    t2 = time.perf_counter()
    c1 = counter.snapshot()

    failures = []
    vocab = rt._engine_for(rt.tiers[0]).model.cfg.vocab_size
    outs = {}
    for i, h in enumerate(handles):
        if h.status is not RequestStatus.COMPLETED:
            failures.append(f"request {i}: {h.status.value} {h.failure_reason or ''}")
            continue
        toks = np.asarray(h.result())
        if toks.shape != (MAX_NEW,) or toks.min() < 0 or toks.max() >= vocab:
            failures.append(f"request {i}: tokens {toks.shape} in "
                            f"[{toks.min()}, {toks.max()}], vocab {vocab}")
        outs[i] = toks
    if rt.request_log.dropped:
        failures.append(f"dropped rids {rt.request_log.dropped}")
    logits = {}
    for spec in rt.tiers:
        logits[spec.name] = engine_logits(rt._engine_for(spec), check, nxt)
        for part in logits[spec.name]:
            if not np.isfinite(part).all():
                failures.append(f"{spec.name}: non-finite logits")
    t3 = time.perf_counter()
    served = {spec.name: sum(1 for h in handles if h.tier == spec.name)
              for spec in rt.tiers}
    stats = jax.devices()[0].memory_stats() or {}
    readings = {
        "phase": name,
        "setup_and_warmup_s": t1 - t0,
        "serve_s": t2 - t1,
        "logit_checks_s": t3 - t2,
        "compiles_in_warmup": c1[0] - c0[0],
        "compile_s_in_warmup": c1[1] - c0[1],
        "cache_hits_in_warmup": c1[2] - c0[2],
        "compiles_while_serving": len(rt.tracer.select(name="engine.compile")),
        "ticks": rt.ticks,
        "served_by_tier": served,
        "mode_trace": [(t, int(m)) for t, m in rt.mode_trace],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }
    del rt, client, handles
    gc.collect()
    return outs, logits, readings, failures


def smoke(seed: int, n_requests: int, reduced: bool = False) -> list:
    """Both phases and the reference check; returns the failures.
    ``reduced`` swaps in the reduced smoke config for a CPU rehearsal."""
    from repro.configs import get_config

    counter = CompileCounter()
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduce()
    say(f"[smoke] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}, "
        f"vocab {cfg.vocab_size}, {cfg.dtype}; max_len {MAX_LEN}, "
        f"batch {BATCH}, {n_requests} requests x {MAX_NEW} new tokens")
    prompts, check, nxt = make_traffic(seed, n_requests, cfg.vocab_size)

    failures = []
    phases = {}
    for name in ("xla", "pallas"):
        outs, logits, readings, fails = run_phase(
            name, seed, prompts, check, nxt, counter, reduced)
        phases[name] = (outs, logits)
        failures += [f"{name}: {f}" for f in fails]
        say(f"[smoke] phase {json.dumps(readings)}")

    t0 = time.perf_counter()
    ref = reference_logits(seed, check, nxt, reduced)
    errs = {}
    for tier in phases["xla"][1]:
        for i, part in enumerate(("prefill", "decode")):
            for name in ("xla", "pallas"):
                errs[f"{name}/{tier}/{part}_vs_f32"] = rel_err(
                    phases[name][1][tier][i], ref[i])
            errs[f"pallas_vs_xla/{tier}/{part}"] = rel_err(
                phases["pallas"][1][tier][i], phases["xla"][1][tier][i])
    failures += [f"logits {k}: relative error {v:.4g} > {LOGIT_TOL}"
                 for k, v in errs.items() if not v <= LOGIT_TOL]
    same = np.mean([np.array_equal(phases["xla"][0].get(i), phases["pallas"][0].get(i))
                    for i in range(len(prompts))])
    say(f"[smoke] reference f32 forward {time.perf_counter() - t0:.1f}s; "
        f"relative logit errors (tolerance {LOGIT_TOL}): {json.dumps(errs)}")
    say(f"[smoke] greedy streams identical across phases: {same:.3f} "
        "(informational: argmax of random bf16 logits is fragile)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=16)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    cache_dir = use_compile_cache()
    say(f"[smoke] device {dev.device_kind} ({dev.platform}, "
        f"{len(jax.devices())} devices), jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    failures = smoke(args.seed, args.requests)
    stats = dev.memory_stats() or {}
    say(f"[smoke] peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"of bytes_limit {stats.get('bytes_limit')}")
    for f in failures:
        say(f"[smoke] FAIL {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
