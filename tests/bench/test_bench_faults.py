"""The check catches a broken timed path: a tiny run of every cell with
the program broken underneath the harness has to come out as not
correct.  The faults a served cell can have: a decode step that returns
its KV state unchanged, and a token altered where it is produced; a cell
whose prompts share prefixes also a prefix hit that hands over the wrong
cached pages.  (A single-chip serving cell has no exchange between chips
and no batch mean to halve.)"""
import jax.numpy as jnp
import pytest

from bench_tiny import BENCH, tiny_cell
from benchmarks.chip import run
from repro.serving import engine as eng_mod
from repro.serving.paged_kv import BlockAllocator


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


def _wrong_prefix_pages(monkeypatch):
    """A block-aligned prefix hit whose first page is another cached
    block's: the match length is right, the KV under it is not."""
    orig = BlockAllocator.match_prefix

    def wrong(self, tokens):
        m, pages = orig(self, tokens)
        others = sorted(set(self._blocks.values()) - set(pages))
        if m and others:
            pages = [others[0]] + list(pages[1:])
        return m, pages
    monkeypatch.setattr(BlockAllocator, "match_prefix", wrong)


FAULTS = {"state_unchanged": _kv_not_written, "token_altered": _token_altered,
          "wrong_prefix_pages": _wrong_prefix_pages}


def _cases():
    for c in BENCH["workloads"]:
        mix = run.load_json(f"{run.HERE}/traffic/{c['traffic']}.json")
        for fault in FAULTS:
            if fault != "wrong_prefix_pages" or mix["arrivals"] == "sessions":
                yield pytest.param(c["name"], fault, id=f"{c['name']}-{fault}")


@pytest.mark.parametrize("name, fault", list(_cases()))
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    bench, cell, config, mix = tiny_cell(name)
    out = run.run_cell(bench, cell, config, mix, 23, 4.0, False)
    checks = out["line"]["checks"]
    assert out["line"]["correct"] is False
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]
