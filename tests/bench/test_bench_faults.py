"""The check catches a broken timed path: a tiny run with the program
broken underneath the harness has to come out as not correct.  The
faults a served cell can have: a decode step that returns its KV state
unchanged, and a token altered where it is produced.  (A single-chip
serving cell has no exchange between chips and no batch mean to halve.)"""
import jax.numpy as jnp
import pytest

from bench_tiny import tiny_cell
from benchmarks.chip import run
from repro.serving import engine as eng_mod


def _kv_not_written(monkeypatch):
    for name in ("_chunk_scan", "_chunk_scan_paged"):
        orig = getattr(eng_mod.ServingEngine, name)

        def stale(self, params, cache, *a, _orig=orig):
            out = _orig(self, params, cache, *a)
            return (cache,) + tuple(out[1:])
        monkeypatch.setattr(eng_mod.ServingEngine, name, stale)


def _token_altered(monkeypatch):
    def shifted(self, logits, key):
        top = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (top + 1) % logits.shape[-1]
    monkeypatch.setattr(eng_mod.ServingEngine, "_sample", shifted)


@pytest.mark.parametrize("fault", [_kv_not_written, _token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    bench, cell, config, mix = tiny_cell("qwen3-0.6b.chat")
    out = run.run_cell(bench, cell, config, mix, 23, 4.0, False)
    checks = out["line"]["checks"]
    assert out["line"]["correct"] is False
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]
