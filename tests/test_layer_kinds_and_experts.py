"""Per-layer attention kinds and the held share of an expert layer, at
small sizes on the CPU: YaRN's frequencies against the written formula,
a windowed layer's mask, the four chips' shares of one expert layer
against the uncut layer, and the expert rows the engine counts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention.ref import mixed_attention_ref
from repro.models import Model, layers, moe, transformer
from repro.serving.engine import EngineConfig, QueueSession, ServingEngine


def test_yarn_frequencies_follow_the_written_formula():
    """Mellum's full layers: d = 128, theta 5e5, factor 16 over 8192
    original positions, beta 32/1.  The correction dimensions are
    d ln(8192 / (2 pi beta)) / (2 ln theta): 18.08 -> 18 for beta_fast,
    34.98 -> 35 for beta_slow; below the first the frequency is
    theta^(-2i/d), above the second that over 16, linear in between."""
    d, theta = 128, 500_000.0
    got = layers.yarn_frequencies(d, theta, 16.0, 8192.0, 32.0, 1.0)
    base = theta ** (-np.arange(0, d, 2) / d)
    ramp = np.clip((np.arange(d // 2) - 18) / (35 - 18), 0, 1)
    want = base * (1 - ramp) + base / 16 * ramp
    np.testing.assert_allclose(got, want, rtol=2e-6)   # float32 of a float64 formula
    assert got[17] == pytest.approx(base[17], rel=2e-6)
    assert got[35] == pytest.approx(base[35] / 16, rel=2e-6)

    cfg = get_config("mellum2-12b-a2.5b")
    kinds = transformer.layer_kinds(cfg)
    full = np.array([t == "full_attention" for t in cfg.layer_types])
    assert full.sum() == 7 and list(full[:4]) == [False, False, False, True]
    np.testing.assert_array_equal(
        np.asarray(kinds["window"]), np.where(full, transformer.NO_WINDOW, 1024))
    np.testing.assert_allclose(np.asarray(kinds["rope_scale"]),
                               np.where(full, 1.2772588722239782, 1.0), rtol=1e-7)
    np.testing.assert_allclose(np.asarray(kinds["inv_freq"])[3], got, rtol=1e-7)
    np.testing.assert_allclose(np.asarray(kinds["inv_freq"])[0], base, rtol=2e-6)

    # cos and sin both scaled: a rotated vector's norm grows by the factor
    x = jax.random.normal(jax.random.key(0), (1, 5, 2, d))
    pos = jnp.arange(5)[None]
    y = layers.apply_rope(x, pos, theta, inv_freq=jnp.asarray(got), scale=1.5)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               1.5 * jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_a_dense_model_carries_no_layer_kinds():
    """One RoPE kind and no window: the layer loop scans nothing new and
    the engine's steps count no expert rows."""
    cfg = get_config("qwen3-0.6b").reduce()
    assert transformer.layer_kinds(cfg) is None
    eng = ServingEngine(Model(cfg), None, EngineConfig())
    assert eng._rows0() is None


@pytest.mark.parametrize("window", [1, 5, 16])
def test_a_windowed_query_sees_only_its_window(window):
    """Query at position p attends keys in (p - W, p]: with values that
    are one-hot in the key's position, the output's mass sits there."""
    B, Q, S = 2, 6, 24
    cache_lens = jnp.array([3, 11], jnp.int32)
    q = jnp.zeros((B, Q, 1, S))
    k = jnp.zeros((B, S, 1, S))
    v = jnp.broadcast_to(jnp.eye(S)[None, :, None, :], (B, S, 1, S))
    out = mixed_attention_ref(q, k, v, cache_lens, window=window)
    pos = np.asarray(cache_lens)[:, None] + np.arange(Q)[None]
    keys = np.arange(S)
    seen = (keys <= pos[..., None]) & (keys > pos[..., None] - window)
    want = seen / seen.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(out)[:, :, 0], want, atol=1e-6)


def _uncut_layer(p, x, top_k):
    """The expert layer as written: softmax over every expert, the top_k
    picks with gates renormalised, the sum of gate x SwiGLU of each pick."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        picks = np.argsort(-np.asarray(probs[t]), kind="stable")[:top_k]
        gates = np.asarray(probs[t])[picks]
        gates = gates / gates.sum()
        for e, g in zip(picks, gates):
            h = jax.nn.silu(x[t] @ p["w_gate"][e]) * (x[t] @ p["w_up"][e])
            out[t] += g * np.asarray(h @ p["w_down"][e])
    return out


def test_the_four_chips_shares_add_up_to_the_uncut_layer():
    """Experts 0-15 of 16 split four ways, as the 4-chip deployment holds
    them: each share routes over all 16 and computes its own experts'
    part; the parts sum to the uncut layer, nothing counted twice."""
    E, k, d, f, T = 16, 4, 32, 24, 40
    base = dataclasses.replace(get_config("mellum2-12b-a2.5b").reduce(),
                               d_model=d, d_ff=f, n_experts=E, top_k=k,
                               dtype="float32")
    p = moe.init_moe_params(jax.random.key(0), base, jnp.float32)
    p["router"] = p["router"] * 40.0          # spread the routing
    x = jax.random.normal(jax.random.key(1), (1, T, d))
    whole, _, rows = moe.moe_block(p, x, base)
    assert int(rows) == T * k
    parts, counted = 0.0, 0
    for chip in range(4):
        share = dataclasses.replace(base, experts_held=4, expert_offset=4 * chip)
        held = {n: w[4 * chip:4 * chip + 4] for n, w in p.items() if n != "router"}
        part, _, r = moe.moe_block(dict(held, router=p["router"]), x, share)
        parts, counted = parts + part, counted + int(r)
    assert counted == T * k                   # every pick computed exactly once
    want = _uncut_layer(p, x[0], k)
    np.testing.assert_allclose(np.asarray(parts)[0], want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole)[0], want, rtol=1e-4, atol=1e-5)


def test_the_engine_counts_the_expert_rows_it_computes():
    """``EngineTelemetry.expert_rows`` sums the pumps' ``PumpReport``
    counts: every (token, held expert) row of every step, padding and idle
    slots included, which for a model holding every expert is the steps'
    tokens x top_k x layers."""
    cfg = dataclasses.replace(get_config("mellum2-12b-a2.5b").reduce(),
                              dtype="float32")
    model = Model(cfg)
    eng = ServingEngine(model, model.init(jax.random.key(0)),
                        EngineConfig(max_len=64, decode_batch=2, decode_chunk=4,
                                     prefill_chunk=16))
    sess = QueueSession(eng)
    sess.submit(0, np.arange(1, 21, dtype=np.int32)[None], 6)
    total = 0
    while not sess.idle:
        total += sess.pump().expert_rows
    Q = eng.chunk_quantum(16)
    per_token = cfg.top_k * cfg.n_layers
    mixed = eng.telemetry.mixed_steps * 2 * Q * per_token
    scans = eng.telemetry.chunks * 4 * 2 * per_token
    assert total == eng.telemetry.expert_rows == mixed + scans > 0
