"""Mellum2's served path against its plain reference (``archs/mellum.py``)
at the CPU rehearsal's size: chunked prefill in mixed steps, then decode
through the cache, on both tiers' cache layouts, with prompts longer than
the windowed layers' window so that both kinds of layer and both RoPE
kinds are checked; and a request's logits the same alone as in a full
batch of requests routed to the same experts, which a layer that dropped
picks over an expert's capacity would fail."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import tiny_config
from benchmarks.chip import run
from repro.configs import get_config
from repro.models import Model

CONFIG = "mellum2-12b-a2.5b"
MAX_LEN, PAGE, Q = 128, 16, 16


@pytest.fixture(scope="module")
def served():
    """(config, reference module, weights, the program's model) at tiny
    size, served in float32 as the rehearsal serves it."""
    config = tiny_config(run.load_json(f"{run.HERE}/configs/{CONFIG}.json"))
    assert config["torch_dtype"] == "float32"
    arch = run.load_arch(config)
    params = arch.make_weights(config, 2**31 + 21)
    cfg = dataclasses.replace(get_config(CONFIG), **arch.overrides(config))
    return config, arch, params, Model(cfg)


def _reference(config, arch, params, tokens):
    """The reference's logits at every position of ``tokens`` (T,)."""
    _, first, _ = arch._share(config)
    ref_config = arch._Config({k: config[k] for k in
                               ("head_dim", "layer_types", "rope_parameters")})
    return arch._logits(params, jnp.asarray(tokens), jnp.arange(len(tokens)),
                        s=arch.shapes(config), eps=float(config["rms_norm_eps"]),
                        offset=first, config_key=ref_config, mode="f32")


def _serve(model, params, tokens, paged: bool, prompt_len: int):
    """Logits (B, T, V) of ``tokens`` (B, T) as the engine's steps run
    them: the prompt in mixed steps of ``Q`` columns a slot, then one
    decode step a token, through a contiguous cache or the page pool."""
    B, T = tokens.shape
    if paged:
        nb = MAX_LEN // PAGE
        cache = model.empty_page_pool(1 + B * nb, PAGE)
        table = 1 + jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    else:
        cache, table = model.empty_cache(B, MAX_LEN), None
    mixed = jax.jit(partial(model.step_mixed, page_table=table,
                            attn_window=MAX_LEN, all_logits=True))
    decode = jax.jit(partial(model.decode, page_table=table))
    lens, out = jnp.zeros((B,), jnp.int32), []
    for c in range(0, prompt_len, Q):
        n = min(Q, prompt_len - c)
        chunk = jnp.pad(tokens[:, c:c + n], ((0, 0), (0, Q - n)))
        logits, cache = mixed(params, chunk, cache, lens, jnp.full((B,), n))
        out.append(logits[:, :n])
        lens = lens + n
    for t in range(prompt_len, T):
        logits, cache = decode(params, tokens[:, t:t + 1], cache, lens)
        out.append(logits[:, None])
        lens = lens + 1
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_served_path_matches_the_reference(served, paged):
    config, arch, params, model = served
    window = config["sliding_window"]
    T, plen = 3 * window, 2 * window + 7       # prompt and decode past the window
    tokens = jax.random.randint(jax.random.key(5), (1, T), 0, config["vocab_size"])
    got = _serve(model, params, tokens, paged, plen)[0]
    want = _reference(config, arch, params, tokens[0])
    # both compute in float32 and differ only in the order of their sums
    # (grouped matmuls, per-layer scans, chunked scores against the
    # reference's dense ones): 3e-6 at this size, on logits of about 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    # the windowed layers mask: with a window past every position the same
    # model reads the same up to the window and otherwise after it
    wide = dataclasses.replace(model.cfg, layer_window=MAX_LEN)
    other = _serve(Model(wide), params, tokens, paged, plen)[0]
    assert float(jnp.max(jnp.abs(other[window + 1:] - want[window + 1:]))) > 1e-2
    np.testing.assert_allclose(np.asarray(other[:window]), np.asarray(want[:window]),
                               atol=1e-4)


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_a_request_reads_the_same_alone_and_in_a_full_batch(served, paged):
    """Five copies of one prompt among eight slots route their tokens to
    the same experts, a load that overflows a capacity of 1.25 x the mean;
    the copies' logits must not move."""
    config, _, params, model = served
    T, plen = 60, 40
    one = jax.random.randint(jax.random.key(9), (1, T), 0, config["vocab_size"])
    others = jax.random.randint(jax.random.key(10), (3, T), 0, config["vocab_size"])
    batch = jnp.concatenate([one, jnp.repeat(one, 4, axis=0), others])
    alone = _serve(model, params, one, paged, plen)[0]
    together = _serve(model, params, batch, paged, plen)
    # the same float32 sums over other batch shapes: 1e-6 apart here
    np.testing.assert_allclose(np.asarray(together[0]), np.asarray(alone),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(together[1]), np.asarray(alone),
                               atol=1e-5)
