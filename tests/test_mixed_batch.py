"""Mixed-batch chunked prefill: the fused prefill+decode engine step.

The acceptance bar is exactness: the mixed engine (greedy, same seeds)
must be token-exact with the legacy per-request-prefill engine on both
the contiguous and paged paths — through prefix hits, ragged chunk
boundaries, and a mid-decode session kill.  Plus the issue checklist:
the q-chunk kernels against their lax oracles, the compile-count
regression (pow-2 buckets => one trace serves many prompt lengths), the
telemetry counter audit under chunked admission, and the fleet-side
chunk-budget/TTFT-p99 plumbing.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Model
from repro.serving import EngineConfig, QueueSession, ServingEngine


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen3-0.6b").reduce()
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _engine(model, params, *, mixed=True, paged=False, budget=8, batch=3,
            max_len=64, page_size=8, num_pages=0):
    return ServingEngine(model, params, EngineConfig(
        max_len=max_len, decode_batch=batch, temperature=0.0, decode_chunk=4,
        mixed_step=mixed, prefill_chunk=budget,
        paged_kv=paged, page_size=page_size, num_pages=num_pages))


def _drain(sess):
    while not sess.idle:
        sess.pump()
    return sess.results


# ---------------------------------------------------------------------------
# q-chunk kernels vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Hkv,G,Q", [(2, 4, 5), (1, 8, 1), (2, 2, 8)])
def test_mixed_kernel_vs_ref(Hkv, G, Q):
    from repro.kernels.decode_attention.kernel import mixed_attention_pallas
    from repro.kernels.decode_attention.ref import mixed_attention_ref

    B, S, D = 3, 64, 32
    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[0], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, Q, Hkv * G, D), jnp.float32)
    lens = jnp.array([0, 17, S - Q], jnp.int32)
    out = mixed_attention_pallas(q, k, v, lens, block_k=16, interpret=True)
    ref = mixed_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-3)


def test_mixed_kernel_q1_is_flash_decoding():
    """Q=1 must degenerate to the decode kernel's math exactly
    (lengths = cache_lens + 1)."""
    from repro.kernels.decode_attention.kernel import mixed_attention_pallas
    from repro.kernels.decode_attention.ref import decode_attention_ref

    B, S, Hkv, G, D = 2, 32, 2, 2, 16
    ks = jax.random.split(jax.random.key(1), 3)
    k = jax.random.normal(ks[0], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, 1, Hkv * G, D), jnp.float32)
    lens = jnp.array([0, 30], jnp.int32)
    out = mixed_attention_pallas(q, k, v, lens, block_k=8, interpret=True)
    ref = decode_attention_ref(q[:, 0], k, v, lens + 1)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=2e-5, rtol=1e-3)


def test_mixed_paged_kernel_vs_ref():
    from repro.kernels.decode_attention.kernel import mixed_attention_paged
    from repro.kernels.decode_attention.ref import mixed_attention_paged_ref

    B, Hkv, G, D, Q = 3, 2, 4, 32, 5
    P, ps, nb = 20, 8, 6
    ks = jax.random.split(jax.random.key(2), 3)
    kp = jax.random.normal(ks[0], (P, ps, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[1], (P, ps, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, Q, Hkv * G, D), jnp.float32)
    rng = np.random.default_rng(0)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[: B * nb].reshape(B, nb),
                      jnp.int32)
    lens = jnp.array([0, 11, nb * ps - Q], jnp.int32)
    out = mixed_attention_paged(q, kp, vp, tbl, lens, interpret=True)
    ref = mixed_attention_paged_ref(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-3)


def test_paged_splitk_ref_matches_single_pass():
    from repro.kernels.decode_attention.ref import (
        decode_attention_paged_ref,
        decode_attention_paged_splitk_ref,
    )

    P, ps, Hkv, D, B, nb = 18, 8, 2, 16, 2, 8
    ks = jax.random.split(jax.random.key(3), 3)
    kp = jax.random.normal(ks[0], (P, ps, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[1], (P, ps, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, 4, D), jnp.float32)
    rng = np.random.default_rng(1)
    tbl = jnp.asarray(rng.permutation(np.arange(1, P))[: B * nb].reshape(B, nb),
                      jnp.int32)
    lens = jnp.array([nb * ps, 3 * ps + 5], jnp.int32)
    out = decode_attention_paged_splitk_ref(q, kp, vp, tbl, lens, k_splits=4)
    ref = decode_attention_paged_ref(q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-3)


# ---------------------------------------------------------------------------
# engine: mixed vs legacy token exactness
# ---------------------------------------------------------------------------


def test_mixed_token_exact_contiguous(qwen):
    cfg, model, params = qwen
    rng = np.random.default_rng(0)
    legacy = _engine(model, params, mixed=False)
    mixed = _engine(model, params, budget=8)
    reqs = [(rng.integers(0, cfg.vocab_size, (1, p)), n)
            for p, n in [(12, 6), (5, 9), (17, 3), (30, 7), (12, 5), (8, 1)]]
    ref = legacy.serve_queue(reqs)
    out = mixed.serve_queue(reqs)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])
    tel = mixed.telemetry
    assert tel.mixed_steps > 0 and tel.prefill_chunks >= len(reqs)


def test_mixed_token_exact_paged_with_prefix_hits(qwen):
    """Chunked admission over the paged cache: misses, a full-prompt
    duplicate, and a block-aligned sibling — exact AND the prefix cache
    stays as effective as the legacy synchronous-prefill path."""
    cfg, model, params = qwen
    rng = np.random.default_rng(1)
    legacy = _engine(model, params, mixed=False, paged=True)
    mixed = _engine(model, params, paged=True)
    p0 = rng.integers(0, cfg.vocab_size, (1, 12))
    p1 = np.concatenate([p0[:, :8], rng.integers(0, cfg.vocab_size, (1, 4))],
                        axis=1)
    reqs = [(p0, 6), (p0, 6), (p1, 7),
            (rng.integers(0, cfg.vocab_size, (1, 10)), 5), (p0, 9)]
    ref = legacy.serve_queue(reqs)
    sess = QueueSession(mixed)
    for rid, (inp, n) in enumerate(reqs):
        sess.submit(rid, inp, n)
    _drain(sess)
    for rid in ref:
        np.testing.assert_array_equal(sess.results[rid], ref[rid])
    st = sess.allocator.stats
    assert st.full_hits >= 2            # dup admissions deferred, then hit
    assert st.prefix_hits >= 1          # p1 reused p0's first block
    assert st.reused_tokens >= 12 + 8
    assert sess.allocator.live_pages == 0


def test_mixed_chunk_spans_pumps(qwen):
    """A prompt longer than the whole per-pump ingest capacity still
    admits, spans multiple mixed steps, and completes exactly."""
    cfg, model, params = qwen
    rng = np.random.default_rng(2)
    legacy = _engine(model, params, mixed=False, batch=2)
    mixed = _engine(model, params, budget=2, batch=2)   # quantum 1
    reqs = [(rng.integers(0, cfg.vocab_size, (1, 20)), 5),
            (rng.integers(0, cfg.vocab_size, (1, 7)), 4)]
    ref = legacy.serve_queue(reqs)
    out = mixed.serve_queue(reqs)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])


def test_mixed_session_kill_and_requeue_token_exact(qwen):
    """The PR-2 drill at session level: kill a mixed session mid-decode
    (and mid-ingest), requeue the recovered rids on a fresh session —
    outputs byte-identical to an undisturbed legacy run."""
    cfg, model, params = qwen
    rng = np.random.default_rng(3)
    reqs = {rid: (rng.integers(0, cfg.vocab_size, (1, 10 + rid)), 6 + rid)
            for rid in range(5)}
    legacy = _engine(model, params, mixed=False, paged=True)
    ref = legacy.serve_queue([reqs[r] for r in sorted(reqs)])

    mixed = _engine(model, params, paged=True, budget=4)
    sess = QueueSession(mixed)
    for rid, (inp, n) in reqs.items():
        sess.submit(rid, inp, n)
    sess.pump()                                   # some decoding, some mid-ingest
    done = dict(sess.results)
    lost = sess.inflight_rids()
    assert lost                                   # the kill recovered work
    sess2 = QueueSession(mixed)                   # fresh replica, same engine
    for rid in lost:
        sess2.submit(rid, *reqs[rid])
    _drain(sess2)
    for i, rid in enumerate(sorted(reqs)):
        got = done.get(rid, sess2.results.get(rid))
        np.testing.assert_array_equal(got, ref[i])


def test_mixed_cancel_releases_slot_and_pages(qwen):
    """Cancel against a mixed paged session: a queued request and an
    actively-decoding one both release their state/pages.  (A pump drives
    its admissions' ingestion to completion before returning, so there is
    no observable mid-ingest state between pumps to cancel into — the
    _prefilling sweep in cancel() is defensive.)"""
    cfg, model, params = qwen
    rng = np.random.default_rng(4)
    eng = _engine(model, params, paged=True, budget=2, batch=2)  # quantum 1
    sess = QueueSession(eng)
    for rid in range(3):
        sess.submit(rid, rng.integers(0, cfg.vocab_size, (1, 16)), 8)
    sess.pump()                         # 2 decoding (ingest done), 1 queued
    assert not sess._prefilling         # ingestion never spans pumps
    live_before = sess.allocator.live_pages
    assert live_before > 0
    assert sess.cancel(0)               # active slot
    assert sess.cancel(2)               # still queued
    assert sess.allocator.live_pages < live_before
    _drain(sess)
    assert set(sess.results) == {1}
    assert sess.allocator.live_pages == 0


# ---------------------------------------------------------------------------
# compile-count regression: pow-2 buckets serve many lengths
# ---------------------------------------------------------------------------


def test_one_trace_serves_many_prompt_lengths(qwen):
    """The bucketing satellite: prompts of many lengths must reuse the
    SAME mixed-step traces — one fixed Q quantum, pow-2 attention-window
    buckets — instead of compiling per prompt length."""
    cfg, model, params = qwen
    rng = np.random.default_rng(5)
    eng = _engine(model, params, budget=12, batch=3)
    assert eng.chunk_quantum(12) == 4
    reqs = [(rng.integers(0, cfg.vocab_size, (1, p)), 3)
            for p in (3, 5, 6, 7, 9, 11, 13, 17, 21, 26)]
    eng.serve_queue(reqs)
    # aw buckets possible at max_len=64: {4, 8, 16, 32, 64} with Q=4
    assert eng.mixed_traces <= 5, eng.mixed_traces

    # pre-enumeration covers the grid: a fresh engine compiles everything
    # up front and the same workload then adds ZERO traces
    eng2 = _engine(model, params, budget=12, batch=3)
    eng2.warm_mixed_traces([12])
    warmed = eng2.mixed_traces
    eng2.serve_queue(reqs)
    assert eng2.mixed_traces == warmed


# ---------------------------------------------------------------------------
# telemetry counter audit under chunked admission
# ---------------------------------------------------------------------------


def test_counters_no_double_count_across_chunks(qwen):
    """A prompt ingested over many chunks counts each token ONCE, one
    prefill per request, and the hit-rate channels stay truthful."""
    cfg, model, params = qwen
    rng = np.random.default_rng(6)
    eng = _engine(model, params, paged=True, budget=4, batch=2)  # quantum 2
    p0 = rng.integers(0, cfg.vocab_size, (1, 13))
    sess = QueueSession(eng)
    sess.submit(0, p0, 6)
    _drain(sess)
    st = sess.allocator.stats
    assert st.prefilled_tokens == 13          # once, despite ceil(13/2) chunks
    assert st.misses == 1 and st.full_hits == 0
    assert eng.telemetry.prefills == 1        # one PROMPT, many chunks
    assert eng.telemetry.prefill_chunks == -(-13 // 2)
    # identical repeat: zero prefill, reuse counted once
    sess.submit(1, p0, 4)
    _drain(sess)
    st = sess.allocator.stats
    assert st.prefilled_tokens == 13          # unchanged
    assert st.full_hits == 1 and st.reused_tokens == 13
    assert eng.telemetry.prefills == 1        # full hit never prefills
    assert eng.telemetry.cache_hit_rate == pytest.approx(0.5)
    # emitted == delivered: useful_tokens covers exactly the outputs
    assert eng.telemetry.useful_tokens == 6 + 4
    assert sess.results[0].size == 6 and sess.results[1].size == 4


def test_pump_report_fields_under_chunked_admission(qwen):
    cfg, model, params = qwen
    rng = np.random.default_rng(7)
    eng = _engine(model, params, paged=True, budget=64, batch=2)
    sess = QueueSession(eng)
    sess.submit(0, rng.integers(0, cfg.vocab_size, (1, 12)), 8)
    rep = sess.pump()
    assert rep.admitted == [0]
    assert rep.prefix_misses == 1 and rep.prefilled_tokens == 12
    assert rep.mixed_steps >= 1 and rep.prefill_chunks >= 1
    assert rep.page_occupancy > 0
    assert rep.wall_s > 0
    while not sess.idle:
        rep = sess.pump()
    assert rep.page_occupancy == 0.0          # drained: post-release sample


# ---------------------------------------------------------------------------
# fleet plumbing: chunk-budget knob + TTFT p99
# ---------------------------------------------------------------------------


def test_replica_chunk_budget_knob(qwen):
    from repro.fleet.replica import Replica

    cfg, model, params = qwen
    eng = _engine(model, params, budget=16, batch=2)
    rep = Replica("t/r1", "t", eng)
    rep.set_chunk_budget(999)                 # no session yet: no-op
    rep.activate(0.0)
    assert rep.session.token_budget == 16
    rep.set_chunk_budget(64)
    assert rep.session.token_budget == 64
    assert eng.chunk_quantum(64) == 32
    rep.set_chunk_budget(0)                   # floored, never zero
    assert rep.session.token_budget == 1


def test_runtime_mode_drives_chunk_budget(qwen):
    """Capacity mode must widen the live sessions' ingest budget and cost
    mode must narrow it back (the TTFT/TPOT trade the controller owns)."""
    from repro.fleet.runtime import build_saturated_fleet

    rt = build_saturated_fleet(n_requests=4, n_replicas=1, decode_batch=2,
                               prompt_len=8, prefill_chunk=16, seed=0)
    rt.cfg.warmup = False
    rt.tick()
    spec = rt.tiers[0]
    reps = [r for r in rt.replicas[spec.name] if r.session is not None]
    assert reps
    mode = rt.mode_trace[-1][1]
    want = (spec.capacity_prefill_chunk or 4 * spec.prefill_chunk) \
        if mode == 1 else spec.prefill_chunk
    assert all(r.session.token_budget == want for r in reps)


def test_telemetry_ttft_p99_channel():
    from repro.fleet.telemetry import TelemetryBus

    bus = TelemetryBus(["t"], alpha=0.3)
    assert bus.ttft_p99("t") == 0.0
    for v in [0.1] * 98 + [5.0, 9.0]:
        bus.record_completion("t", "t/r1", v, 0.01, tokens=4)
    p99 = bus.ttft_p99("t")
    assert 4.0 < p99 <= 9.0                   # the tail, not the EWMA mean
    assert bus.snapshot()["t"]["ttft_p99_s"] == pytest.approx(p99)
    assert bus.snapshot()["t"]["ttft_s"] < p99


# ---------------------------------------------------------------------------
# property: ragged chunk boundaries (hypothesis)
# ---------------------------------------------------------------------------


def test_ragged_chunk_boundaries_property(qwen):
    """Randomized prompt lengths / output budgets / chunk budgets around
    quantum boundaries: mixed == legacy, token-exact.  Uses hypothesis when
    available; otherwise a fixed adversarial sweep (boundary-straddling
    lengths: exact multiples of the quantum, one off either side, singles)
    so the property is exercised on hypothesis-less boxes too."""
    cfg, model, params = qwen
    legacy = _engine(model, params, mixed=False, batch=2)
    engines = {}

    def check(plens, news, budget, seed):
        rng = np.random.default_rng(seed)
        reqs = [(rng.integers(0, cfg.vocab_size, (1, p)), n)
                for p, n in zip(plens, news)]
        ref = legacy.serve_queue(reqs)
        if budget not in engines:       # one engine per budget: reuse jits
            engines[budget] = _engine(model, params, budget=budget, batch=2)
        out = engines[budget].serve_queue(reqs)
        for rid in ref:
            np.testing.assert_array_equal(out[rid], ref[rid])

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        for case in [
            ([1, 25, 8], [3, 1, 8], 2, 0),      # quantum 1: every boundary
            ([7, 8, 9], [4, 4, 4], 16, 1),      # one off either side of 8
            ([4, 12, 5], [8, 2, 6], 5, 2),      # odd budget, odd lengths
            ([16], [8, 1, 1], 8, 3),            # lone prompt == 4x quantum
        ]:
            check(*case)
        return

    settings(max_examples=8, deadline=None)(given(
        plens=st.lists(st.integers(1, 25), min_size=1, max_size=3),
        news=st.lists(st.integers(1, 8), min_size=3, max_size=3),
        budget=st.sampled_from([2, 5, 8, 16]),
        seed=st.integers(0, 3),
    )(check))()


# ---------------------------------------------------------------------------
# the layer loop writes the carried cache only at the new positions
# ---------------------------------------------------------------------------


def _layer_loop_ref(model, params, x, cache, attn, fused):
    """A plain per-layer Python loop over the same attention functions:
    layer l's cache cut out of the stack, run as a one-layer stack, and the
    layers stacked back — what the scanned loop computes, without the scan
    or the carried cache."""
    from repro.models import attention, layers, transformer

    cfg = model.cfg
    ks, vs = [], []
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        h = layers.rms_norm(x, lp["ln1"], cfg.norm_eps)
        one = attention.KVCache(k=cache.k[l:l + 1], v=cache.v[l:l + 1])
        a, one = attn(lp["attn"], h, one, 0,
                      None if fused is None else fused["wqkv"][l])
        x = x + a
        h = layers.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if fused is None or fused["w_gu"] is None:
            m, _ = transformer.mlp_block(lp, h, cfg, None)
        else:
            m = layers.swiglu_fused(h, fused["w_gu"][l], lp["mlp"]["w_down"])
        x = x + m
        ks.append(one.k[0])
        vs.append(one.v[0])
    return x, jnp.stack(ks), jnp.stack(vs)


_LAYER_LOOP_CASES = {
    # case: (arch, step, paged)
    "decode-ragged": ("qwen3-0.6b", "decode", False),
    "decode-scalar": ("qwen3-0.6b", "decode_scalar", False),
    "decode-paged": ("qwen3-0.6b", "decode", True),
    "mixed": ("qwen3-0.6b", "mixed", False),
    "mixed-paged": ("qwen3-0.6b", "mixed", True),
    "ring-mixtral": ("mixtral-8x22b", "decode", False),
    "moe-mixed": ("arctic-480b", "mixed", False),
    "prefill-paged": ("qwen3-0.6b", "prefill_paged", True),
}


@pytest.mark.parametrize("case", list(_LAYER_LOOP_CASES))
def test_layer_loop_writes_only_new_rows(case):
    """``run_layers_kv`` (through the ``Model`` step) against a plain
    per-layer loop: the same logits and the same cache, and every cache row
    but the step's new positions bit-identical to the input.  Six layers,
    so the four-layer unroll runs as a loop with a remainder."""
    from repro.models import attention, transformer

    arch, step, paged = _LAYER_LOOP_CASES[case]
    cfg = get_config(arch).reduce()
    cfg = dataclasses.replace(cfg, n_layers=6)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    B, S, ps, Q = 4, 64, 8, 8
    nb = S // ps
    kc, kt, kx = jax.random.split(jax.random.key(1), 3)
    if paged:
        shape = model.empty_page_pool(1 + B * nb, ps).k.shape
    else:
        shape = model.empty_cache(B, S).k.shape
    W = shape[2]                                  # ring width or page size
    cache = type(model.empty_cache(1, 1))(
        k=jax.random.normal(kc, shape), v=jax.random.normal(kt, shape))
    table = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    table = table.at[3].set(0)                    # slot 3 idle: the trash page
    lens = jnp.array([3, 17, 0, 40], jnp.int32)
    if step == "decode" and cfg.sliding_window:
        lens = jnp.array([3, 70, 0, 129], jnp.int32)   # wraps the ring
    news = jnp.array([1, 5, 0, 8], jnp.int32)
    fused = transformer.fused_decode_weights(params, cfg)
    pt = table if paged else None

    if step in ("decode", "decode_scalar"):
        cl = jnp.int32(21) if step == "decode_scalar" else lens
        tokens = jax.random.randint(kx, (B, 1), 0, cfg.vocab_size)
        run = jax.jit(lambda c: model.decode(params, tokens, c, cl, fused=fused,
                                             page_table=pt))

        def attn(p, h, kv, layer, w):
            return attention.attention_decode(p, h, kv, layer, cl, cfg, wqkv=w,
                                              page_table=pt)

        rows = [(b, int(jnp.broadcast_to(cl, (B,))[b])) for b in range(B)]
        pick = lambda x: x[:, 0]
        ref_fused = fused
    elif step == "mixed":
        tokens = jax.random.randint(kx, (B, Q), 0, cfg.vocab_size)
        run = jax.jit(lambda c: model.step_mixed(
            params, tokens, c, lens, news, fused=fused, page_table=pt,
            attn_window=S))

        def attn(p, h, kv, layer, w):
            return attention.attention_mixed(p, h, kv, layer, lens, news, cfg,
                                             wqkv=w, page_table=pt,
                                             attn_window=S)

        rows = [(b, int(lens[b]) + j) for b in range(B) for j in range(Q)
                if j < int(news[b]) or paged]     # paged padding: trash page
        last = jnp.maximum(news - 1, 0)
        pick = lambda x: jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        ref_fused = fused
    else:
        start, T = 13, 9
        tokens = jax.random.randint(kx, (1, T), 0, cfg.vocab_size)
        run = jax.jit(lambda c: model.prefill_paged(params, tokens, c, table[1],
                                                    jnp.int32(start)))

        def attn(p, h, kv, layer, w):
            return attention.attention_prefill_paged(p, h, kv, layer, table[1],
                                                     jnp.int32(start), cfg)

        rows = [(1, start + j) for j in range(T)]
        pick = lambda x: x[:, -1]
        ref_fused = None

    def ref(c):
        x = jnp.take(params["embed"], tokens, axis=0)
        x, k, v = _layer_loop_ref(model, params, x, c, attn, ref_fused)
        return pick(transformer.logits_from_hidden(params, x, cfg)), k, v

    want_logits, want_k, want_v = jax.jit(ref)(cache)
    logits, new = run(cache)
    # same math; the scan and the unrolled reference compile to different
    # CPU fusions, which may round float32 differently in the last bits
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits), **tol)
    np.testing.assert_allclose(np.asarray(new.k), np.asarray(want_k), **tol)
    np.testing.assert_allclose(np.asarray(new.v), np.asarray(want_v), **tol)

    # the step's new positions, as (slot or page, row) of one layer
    written = np.zeros(shape[1:3], bool)
    for b, pos in rows:
        if paged:
            padding = step == "mixed" and pos >= int(lens[b]) + int(news[b])
            page = 0 if padding else int(table[b, pos // ps])
            written[page, pos % ps] = True
        else:
            written[b, pos % W] = True
    old_k, old_v = np.asarray(cache.k), np.asarray(cache.v)
    new_k, new_v = np.asarray(new.k), np.asarray(new.v)
    np.testing.assert_array_equal(new_k[:, ~written], old_k[:, ~written])
    np.testing.assert_array_equal(new_v[:, ~written], old_v[:, ~written])
    assert (new_k[:, written] != old_k[:, written]).any(axis=(-2, -1)).all()
    assert (new_v[:, written] != old_v[:, written]).any(axis=(-2, -1)).all()
