"""The benchmark's data files and shared arithmetic, off the chip: every
configuration, architecture, mix and metric that ``BENCHMARK.json`` names
is a file the harness finds by name, the mixes are deterministic and
inside their clips, and the work models count what the configurations
say."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench_tiny import BENCH, ROOT
from benchmarks.chip import run, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = {c["name"]: c for c in BENCH["workloads"]}
MIXES = sorted({c["traffic"] for c in BENCH["workloads"]})


def load_mix(mix: str) -> dict:
    return run.load_json(os.path.join(run.HERE, "traffic", mix + ".json"))


def load_config(name: str) -> dict:
    return run.load_json(os.path.join(run.HERE, "configs", name + ".json"))


# mixes of single requests; "sessions" mixes have tests of their own
FLAT_MIXES = [m for m in MIXES if load_mix(m)["arrivals"] != "sessions"]
SESSION_MIXES = [m for m in MIXES if load_mix(m)["arrivals"] == "sessions"]


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
    arch = run.load_arch(cfg)
    for fn in ("shapes", "overrides", "make_weights", "served_gaps", "tiny"):
        assert callable(getattr(arch, fn)), fn
    s = arch.shapes(cfg)
    assert s.layers > 0 and s.vocab > 0 and s.kv_bytes_per_token > 0
    assert 0 < 2 * s.matmul_params * s.dtype_bytes <= 2 * s.weight_bytes
    assert any(c["config"] == name for c in BENCH["workloads"])


def test_an_unknown_model_type_names_the_missing_file():
    with pytest.raises(SystemExit, match=r"archs/no_such_arch\.py"):
        run.load_arch({"name": "x", "model_type": "no_such_arch"})


@pytest.mark.parametrize("mix", FLAT_MIXES)
def test_mix_is_deterministic_and_clipped(mix):
    m = load_mix(mix)
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


@pytest.mark.parametrize("mix", FLAT_MIXES)
def test_every_block_asks_the_same_work(mix):
    """Any run of whole blocks from the start holds the same requests for
    every seed: a window that admits only the first part of a backlog
    still sees the whole mix."""
    m = load_mix(mix)
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


@pytest.mark.parametrize("mix", SESSION_MIXES)
def test_sessions_are_deterministic_and_clipped(mix):
    m = load_mix(mix)
    a = traffic.generate(m, 2**31 + 7, 51, 151936, 2048)
    b = traffic.generate(m, 2**31 + 7, 51, 151936, 2048)
    c = traffic.generate(m, 12, 51, 151936, 2048)
    assert len(a) > 10
    for x, y in zip(a, b):
        assert (x.due_s, x.max_new, x.earlier) == (y.due_s, y.max_new, y.earlier)
        assert np.array_equal(x.prompt, y.prompt)
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    for r in a:
        assert len(r.prompt) <= m["max_prompt"] and 0 <= r.earlier < len(r.prompt)
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
        assert 0 <= r.due_s < 51 and r.prompt.min() >= 0 and r.prompt.max() < 151936
    # another seed: the same requests in another order, other token ids
    key = lambda rs: sorted((len(r.prompt), r.max_new, r.earlier) for r in rs)
    assert key(a) == key(c)
    assert any(not np.array_equal(x.prompt[:16], y.prompt[:16])
               for x, y in zip(a, c))
    for parts, max_new in traffic.session_block(m):
        users, answers = parts[::2], parts[1::2] + (max_new,)
        assert all(m["prompt"]["min"] <= u <= m["prompt"]["max"] for u in users)
        assert all(m["output"]["min"] <= x <= m["output"]["max"] for x in answers)


@pytest.mark.parametrize("mix", SESSION_MIXES)
def test_every_block_of_sessions_asks_the_same_work(mix):
    """Any run of whole blocks from the start holds the same requests for
    every seed, due at the same block ends, at ``rate_rps``."""
    m = load_mix(mix)
    b = m["block"]
    reqs = [traffic.generate(m, seed, 200, 151936, 2048) for seed in (5, 2**31 + 9)]
    blocks = min(len(r) for r in reqs) // b
    assert blocks >= 2 and len(reqs[0]) == len(reqs[1])
    for k in range(1, blocks + 1):
        got = [sorted((len(r.prompt), r.max_new, r.earlier) for r in rs[:k * b])
               for rs in reqs]
        assert got[0] == got[1]
        assert reqs[0][k * b - 1].due_s == pytest.approx(reqs[1][k * b - 1].due_s)
    assert [r.max_new for r in reqs[0][:b]] != [r.max_new for r in reqs[1][:b]]
    per_block = traffic.exp_gaps(m, b).sum()
    assert b / per_block == pytest.approx(m["rate_rps"], rel=0.1)


@pytest.mark.parametrize("mix", SESSION_MIXES)
def test_sessions_continue_earlier_turns(mix):
    """A request is turn j of its session with the geometric share of a
    session of mean ``turns_mean`` turns; its prompt is the previous
    turn's prompt, an answer and more user text; the whole fits
    ``max_len``."""
    m = load_mix(mix)
    block = traffic.session_block(m)
    turns = [len(parts) // 2 + 1 for parts, _ in block]
    first = sum(1 for t in turns if t == 1)
    assert abs(first / len(block) - 1 / m["turns_mean"]) <= 1 / len(block)
    assert max(turns) >= 3
    reqs = traffic.generate(m, 2**31 + 3, 51, 151936, 2048)
    later = [r for r in reqs if r.earlier]
    assert 0 < len(later) < len(reqs)
    for r in reqs:
        parts = next(p for p, x in block if sum(p) == len(r.prompt)
                     and x == r.max_new and sum(p[:-2]) == r.earlier)
        if r.earlier:
            # the previous turn's prompt, its answer and the new user text
            assert len(r.prompt) - r.earlier == parts[-2] + parts[-1]
        assert len(r.prompt) + r.max_new <= 2048
    # no two requests continue the same history
    assert len({r.prompt[:r.earlier].tobytes() for r in later}) == len(later)
    with pytest.raises(ValueError, match="max_len"):
        traffic.generate(m, 1, 51, 151936, 1024)


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


@pytest.mark.parametrize("config, changes, params, kv", [
    ("qwen3-0.6b", {}, 595_984_384, 114_688),
    ("qwen3-4b", {"num_hidden_layers": 36}, 4_022_272_000, 147_456),
], ids=["qwen3-0.6b", "qwen3-4b-36-layers"])
def test_work_counts(config, changes, params, kv):
    cfg = dict(load_config(config), **changes)
    s = run.load_arch(cfg).shapes(cfg)
    assert s.matmul_params == params
    assert s.kv_bytes_per_token == kv
    # a decode step of one slot at context 0 reads every weight once
    assert s.decode_steps_bytes(1, [0]) == s.weight_bytes
    assert s.tokens_flops([0]) == 2 * params


def test_config_shapes_match_the_files():
    """Each configuration file holds the sizes its golden pins (the
    published widths, and the depth as cut), and its work model reads
    them."""
    here = os.path.dirname(os.path.abspath(__file__))
    for entry in BENCH["configs"]:
        cfg = load_config(entry["name"])
        sizes = run.load_json(os.path.join(here, "golden", entry["name"] + ".json"))["sizes"]
        assert {k: cfg[k] for k in sizes} == sizes
        s = run.load_arch(cfg).shapes(cfg)
        assert (s.layers, s.vocab) == (sizes["num_hidden_layers"], sizes["vocab_size"])


def test_work_model_refuses_heads_kv_heads_do_not_divide():
    cfg = dict(load_config(BENCH["configs"][0]["name"]), num_key_value_heads=3)
    with pytest.raises(ValueError, match="kv heads"):
        run.load_arch(cfg).shapes(cfg)


def test_benchmark_json_is_small():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    json.dumps(BENCH)
