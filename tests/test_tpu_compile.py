"""Chip-compiler rehearsals: the served path's attention kernels, and the
engine's whole step programs, compiled for a described (not attached) TPU
v5e at qwen3-0.6b's published widths.

Interpret mode accepts BlockSpecs that the TPU's Mosaic compiler refuses,
so the CPU kernel tests alone cannot show that a kernel will run on the
chip.  These tests lower each kernel with ``interpret=False`` for one
v5e chip and check that a ``tpu_custom_call`` reaches the compiled HLO;
the step programs' memory and traffic estimates show how the KV cache is
updated.  Nothing runs: they say nothing about results or speed.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and under pytest-xdist
only the worker that is given this file may do so.
"""
import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config

# qwen3-0.6b attention widths: 16 query heads over 8 KV heads, head_dim 128
B, HQ, HKV, D = 8, 16, 8, 128
S, PS = 4096, 16                       # cache length, page size
NB = S // PS                           # block-table width
P = 1 + B * NB                         # page pool (page 0 is the trash page)
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_cases():
    from repro.kernels.decode_attention import kernel as K
    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    i32 = jnp.int32
    q1 = ((B, HQ, D), DT)
    qc = lambda Q: ((B, Q, HQ, D), DT)
    kv = ((B, S, HKV, D), DT)
    pool = ((P, PS, HKV, D), DT)
    lens = ((B,), i32)
    tbl = ((B, NB), i32)
    return {
        "decode": (partial(K.decode_attention_pallas, interpret=False),
                   [q1, kv, kv, lens]),
        "decode_splitk": (partial(K.decode_attention_splitk, k_splits=4,
                                  interpret=False), [q1, kv, kv, lens]),
        "decode_paged": (partial(K.decode_attention_paged, interpret=False),
                         [q1, pool, pool, tbl, lens]),
        "decode_paged_splitk": (partial(K.decode_attention_paged_splitk,
                                        k_splits=8, interpret=False),
                                [q1, pool, pool, tbl, lens]),
        "mixed_q16": (partial(K.mixed_attention_pallas, interpret=False),
                      [qc(16), kv, kv, lens]),
        "mixed_q64": (partial(K.mixed_attention_pallas, interpret=False),
                      [qc(64), kv, kv, lens]),
        "mixed_paged_q16": (partial(K.mixed_attention_paged, interpret=False),
                            [qc(16), pool, pool, tbl, lens]),
        "mixed_paged_q64": (partial(K.mixed_attention_paged, interpret=False),
                            [qc(64), pool, pool, tbl, lens]),
        "flash_prefill": (partial(flash_attention_pallas, interpret=False),
                          [((1, 1024, HQ, D), DT), ((1, 1024, HKV, D), DT),
                           ((1, 1024, HKV, D), DT)]),
    }


@pytest.mark.parametrize("name", [
    "decode", "decode_splitk", "decode_paged", "decode_paged_splitk",
    "mixed_q16", "mixed_q64", "mixed_paged_q16", "mixed_paged_q64",
    "flash_prefill",
])
def test_attention_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    assert "tpu_custom_call" in _compile_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_full_width_mixed_step_compiles_for_v5e(paged, use_pallas, one_chip,
                                                monkeypatch):
    """The engine's own jitted mixed step (``Model.step_mixed`` under
    ``ServingEngine``) at 28 layers, bf16 — the program the chip runs each
    serving step — with XLA attention and with the Pallas kernels."""
    from repro.kernels.decode_attention import ops
    from repro.models import Model
    from repro.serving.engine import EngineConfig, ServingEngine

    # ops asks jax.default_backend(), which is the CPU here: steer it to
    # the Mosaic lowering the chip takes
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen3-0.6b"), use_pallas=use_pallas)
    model = Model(cfg)
    n, Q, max_len, aw = 8, 16, 2048, 1024
    eng = ServingEngine(model, None, EngineConfig(
        max_len=max_len, decode_batch=n, paged_kv=paged, page_size=PS,
        num_pages=1 + n * max_len // PS))
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = on_chip(model.param_specs())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    common = (i32(n, Q), i32(n), i32(n), i32(n),
              jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
    if paged:
        pool = on_chip(jax.eval_shape(
            lambda: model.empty_page_pool(eng.num_pages, PS)))
        lowered = eng._mixed_paged.lower(params, pool, i32(n, eng.max_blocks),
                                         *common, aw)
    else:
        cache = on_chip(model.cache_specs(n, max_len))
        lowered = eng._mixed.lower(params, cache, *common, aw)
    compiled = lowered.compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    mem = compiled.memory_analysis()
    # arguments (weights + KV) and temporaries fit one 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    # the compiler's traffic estimate (a while loop's body counted once)
    # reads 2.8e9-3.1e9 B with the stacked cache carried through the layer
    # loop and written in place; slicing each layer's cache out of the
    # stack and stacking a new copy back read 8.5e9-9.2e9, and a KV write
    # that gathers the whole cache element by element in every layer 3.6e11
    assert compiled.cost_analysis()["bytes accessed"] < 5e9


@pytest.mark.parametrize("program", ["chunk_scan", "mixed_step"])
@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_step_programs_hold_no_second_cache(paged, program, one_chip):
    """The engine's decode chunk and mixed step at qwen3-0.6b's published
    width and depth, 14 slots of 2048 tokens, XLA attention: the stacked KV
    cache rides the layer loop and is written in place, so the compiler's
    temporaries hold no copy of it.  They come to about 0.7 GB, mostly the
    fused projection weights; a layer loop that slices each layer's cache
    out of the stack and stacks a new copy back reserves 5.1-5.4 GB, more
    than the whole 3.29 GB cache."""
    from repro.models import Model
    from repro.serving.engine import EngineConfig, ServingEngine

    model = Model(get_config("qwen3-0.6b"))
    n, Q, max_len, steps = 14, 32, 2048, 8
    eng = ServingEngine(model, None, EngineConfig(
        max_len=max_len, decode_batch=n, paged_kv=paged, page_size=PS,
        num_pages=1 + n * max_len // PS))
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = on_chip(model.param_specs())
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    if paged:
        cache = on_chip(jax.eval_shape(
            lambda: model.empty_page_pool(eng.num_pages, PS)))
        table = (i32(n, eng.max_blocks),)
    else:
        cache = on_chip(model.cache_specs(n, max_len))
        table = ()
    if program == "chunk_scan":
        key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
        fn = eng._chunk_paged if paged else eng._chunk
        lowered = fn.lower(params, cache, *table, i32(n), i32(n), active, key,
                           steps)
    else:
        fn = eng._mixed_paged if paged else eng._mixed
        lowered = fn.lower(params, cache, *table, i32(n, Q), i32(n), i32(n),
                           i32(n), active, max_len)
    mem = lowered.compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < cache_bytes / 2
