"""Per-arch smoke tests: reduced same-family config, one forward/train step
on CPU, asserting output shapes and no NaNs (the assigned-architecture
contract).  The FULL configs are exercised only via launch/dryrun.py."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, shape_grid
from repro.models import Model

B, S = 2, 64


def _batch(cfg, key):
    if cfg.family == "encoder":
        return {
            "embeds": jax.random.normal(key, (B, S, cfg.d_model)),
            "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
            "mask": (jax.random.uniform(key, (B, S)) < 0.3).astype(jnp.float32),
        }
    if cfg.family == "vlm":
        return {
            "inputs": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
            "patches": jax.random.normal(key, (B, 16, cfg.d_model)),
            "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        }
    return {
        "inputs": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        "targets": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_loss(arch):
    cfg = get_config(arch).reduce()
    model = Model(cfg)
    key = jax.random.key(0)
    params = model.init(key)
    batch = _batch(cfg, key)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{arch} loss not finite"
    # CE of a random-init model over V classes should be near log(V)
    assert 0.5 * jnp.log(cfg.vocab_size) < metrics["ce"] < 3.0 * jnp.log(cfg.vocab_size)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    from repro.training import optimizer as opt
    from repro.training.train_step import make_train_step

    cfg = get_config(arch).reduce()
    model = Model(cfg)
    key = jax.random.key(1)
    params = model.init(key)
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=1)
    state = opt.init(params, ocfg)
    step = jax.jit(make_train_step(model, ocfg))
    batch = jax.tree.map(lambda x: x[None], _batch(cfg, key))  # A=1
    new_params, new_state, metrics = step(params, state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"]) and metrics["grad_norm"] > 0
    assert int(new_state.step) == 1
    # params actually moved
    moved = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), params, new_params),
    )
    assert moved > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill(arch):
    cfg = get_config(arch).reduce()
    model = Model(cfg)
    key = jax.random.key(2)
    params = model.init(key)
    batch = _batch(cfg, key)
    batch.pop("targets", None)
    batch.pop("mask", None)
    logits, cache = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    if cfg.supports_decode:
        assert cache is not None


def test_shape_grid_cells():
    """DESIGN.md §4's grid of 32 cells, and mellum2-12b-a2.5b's three
    (train, prefill, decode; full layers keep it off long_500k)."""
    total = sum(len(shape_grid(get_config(a))) for a in ARCH_IDS)
    assert total == 35
    assert len(shape_grid(get_config("mellum2-12b-a2.5b"))) == 3
    assert len(shape_grid(get_config("hubert-xlarge"))) == 2
    assert len(shape_grid(get_config("rwkv6-7b"))) == 4
    assert len(shape_grid(get_config("llama3-405b"))) == 3


def test_param_counts_sane():
    """Analytic param counts land in the right ballpark per arch name."""
    expect = {
        "qwen3-0.6b": (0.4e9, 1.0e9),
        "qwen3-4b": (3e9, 5e9),
        "starcoder2-15b": (12e9, 18e9),
        "llama3-405b": (360e9, 450e9),
        "mixtral-8x22b": (120e9, 150e9),
        "arctic-480b": (420e9, 520e9),
        "rwkv6-7b": (6e9, 9e9),
        "zamba2-2.7b": (2e9, 4e9),
        "llava-next-mistral-7b": (6e9, 8e9),
        "hubert-xlarge": (0.7e9, 1.3e9),
        "mellum2-12b-a2.5b": (11e9, 13e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"
