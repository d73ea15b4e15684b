"""The benchmark's data files and shared arithmetic, off the chip: every
configuration, mix and metric that ``BENCHMARK.json`` names is a file the
harness finds by name, the mixes are deterministic and inside their
clips, and ``work.py`` counts what the configurations say."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import BENCH, ROOT
from benchmarks.chip import run, traffic, work

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {c["name"]: c for c in BENCH["workloads"]}
MIXES = sorted({c["traffic"] for c in BENCH["workloads"]})


def reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def test_refuses_to_run_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks/chip/run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""           # no result line


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", sorted(c["name"] for c in BENCH["configs"]))
def test_config_file_found_by_name(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    path = os.path.join(run.HERE, "configs", name + ".json")
    assert os.path.relpath(path, ROOT) == entry["file"]
    cfg = run.load_json(path)
    assert cfg["source"] == entry["source"] and cfg["reduced"] == entry["reduced"]
    s = work.shapes_of(cfg)
    assert s.heads % s.kv_heads == 0
    assert any(c["config"] == name for c in BENCH["workloads"])


@pytest.mark.parametrize("mix", MIXES)
def test_mix_is_deterministic_and_clipped(mix):
    m = run.load_json(os.path.join(run.HERE, "traffic", mix + ".json"))
    a = traffic.generate(m, 2**31 + 7, 30, 151936, 2048)
    b = traffic.generate(m, 2**31 + 7, 30, 151936, 2048)
    c = traffic.generate(m, 12, 30, 151936, 2048)
    assert len(a) > 10
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)
    for r in a:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
        assert 0 <= r.due_s < 30 and r.prompt.min() >= 0 and r.prompt.max() < 151936
    # another seed: the same lengths in another order, other token ids
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert any(not np.array_equal(x.prompt[:16], y.prompt[:16])
               for x, y in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_every_block_asks_the_same_work(mix):
    """Any run of whole blocks from the start holds the same requests for
    every seed: a window that admits only the first part of a backlog
    still sees the whole mix."""
    m = run.load_json(os.path.join(run.HERE, "traffic", mix + ".json"))
    b = m["block"]
    reqs = [traffic.generate(m, seed, 51, 151936, 2048) for seed in (5, 2**31 + 9)]
    blocks = min(len(r) for r in reqs) // b
    assert blocks >= 2
    for k in range(1, blocks + 1):
        got = [sorted((len(r.prompt), r.max_new) for r in rs[:k * b]) for rs in reqs]
        assert got[0] == got[1]
        if m["arrivals"] == "poisson":
            assert reqs[0][k * b - 1].due_s == pytest.approx(reqs[1][k * b - 1].due_s)
    assert [r.max_new for r in reqs[0][:b]] != [r.max_new for r in reqs[1][:b]]


@pytest.mark.parametrize("name", sorted(E2E) + sorted(m["name"] for m in BENCH["per_layer"]))
def test_metric_file_found_by_name(name):
    entry = E2E.get(name) or next(m for m in BENCH["per_layer"] if m["name"] == name)
    mod = run.load_metric(name)
    assert callable(mod.read)
    assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    if name in E2E:
        return
    moved = E2E[entry["moves"]]
    for cell in entry["workloads"]:
        assert cell in CELLS and reports(moved, cell)
    if "roofline" in name or "mfu" in name:
        assert entry["unit"] == "%"


def test_layer_names_agree():
    """Metrics of one layer name it letter for letter alike: each layer is
    named by one metric file's stem or one module path, never two ways."""
    layers = {m["layer"] for m in BENCH["per_layer"]}
    stems = {m["layer"].split(" (")[0] for m in BENCH["per_layer"]}
    assert len(layers) == len(stems)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m, cell) for m in BENCH["per_layer"])
    c = CELLS[cell]
    assert c["chips"] == 1 and len(c["why"]) <= 200
    assert os.path.exists(os.path.join(run.HERE, "traffic", c["traffic"] + ".json"))


@pytest.mark.parametrize("cfg, params, kv", [
    (work.Shapes(28, 1024, 16, 8, 128, 3072, 151936), 595_984_384, 114_688),
    (work.Shapes(36, 2560, 32, 8, 128, 9728, 151936), 4_022_272_000, 147_456),
], ids=["qwen3-0.6b", "qwen3-4b-36-layers"])
def test_work_counts(cfg, params, kv):
    assert work.matmul_params(cfg) == params
    assert work.kv_bytes_per_token(cfg) == kv
    # a decode step of one slot at context 0 reads every weight once
    assert work.decode_steps_bytes(cfg, 1, [0]) == work.weight_bytes(cfg)
    assert work.token_flops(cfg, 0) == 2 * params


def test_config_shapes_match_the_files():
    for name, (layers, d) in {"qwen3-0.6b": (28, 1024), "qwen3-4b": (24, 2560)}.items():
        s = work.shapes_of(run.load_json(os.path.join(run.HERE, "configs", name + ".json")))
        assert (s.layers, s.d_model) == (layers, d)


def test_benchmark_json_is_small():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    json.dumps(BENCH)
