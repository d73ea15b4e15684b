"""Architecture modules (``archs/<model_type>.py``): each configuration
reads what it read before its architecture's code moved there, and a new
architecture needs new files only.

``golden/<config>.json`` holds, for one configuration, the sizes its file
states, values of its work model at full size, a checksum of its weights
and its reference's gaps on fixed ids at the CPU rehearsal's size.  The
work values pin each configuration; at the rehearsal's size the
architecture's ``tiny`` sets every width, so configurations of one
architecture read the same weights and gaps there, and those two pin the
architecture's shared code (``make_weights``, the reference), not the
configuration."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench_tiny import BENCH, ROOT, tiny_config
from benchmarks.chip import run

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN = sorted(f[:-5] for f in os.listdir(os.path.join(HERE, "golden")))


def golden(name: str) -> dict:
    with open(os.path.join(HERE, "golden", name + ".json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    return run.load_json(os.path.join(run.HERE, "configs", name + ".json"))


def test_every_configuration_has_its_golden():
    assert GOLDEN == sorted(c["name"] for c in BENCH["configs"])


@pytest.mark.parametrize("name", GOLDEN)
def test_work_model_reads_its_golden(name):
    want, cfg = golden(name)["work"], config(name)
    s = run.load_arch(cfg).shapes(cfg)
    assert s.matmul_params == want["matmul_params"]
    assert s.weight_bytes == want["weight_bytes"]
    assert s.kv_bytes_per_token == want["kv_bytes_per_token"]
    tf, db = want["tokens_flops"], want["decode_steps_bytes"]
    assert s.tokens_flops(tf["contexts"]) == tf["value"]
    assert s.decode_steps_bytes(db["steps"], db["contexts"]) == db["value"]


@pytest.mark.parametrize("name", GOLDEN)
def test_weights_read_their_golden(name):
    want, cfg = golden(name)["tiny_weights"], tiny_config(config(name))
    params = run.load_arch(cfg).make_weights(cfg, want["seed"])
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == want["sha256"]


@pytest.mark.parametrize("name", GOLDEN)
def test_reference_reads_its_golden(name):
    want, cfg = golden(name)["tiny_served_gaps"], tiny_config(config(name))
    arch = run.load_arch(cfg)
    params = arch.make_weights(cfg, want["seed"])
    g, cg = arch.served_gaps(params, cfg, np.asarray(want["prompt"], np.int32),
                             np.asarray(want["served"], np.int32),
                             want["max_len"], control=True)
    np.testing.assert_allclose(g, want["gaps"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(cg, want["control_gaps"], rtol=1e-5, atol=1e-6)


RUN_COPY = """
import json, sys
sys.path[:0] = ["tests/bench"]
from bench_tiny import tiny_cell
from benchmarks.chip import run
bench, cell, config, mix = tiny_cell("copy-0.6b.chat")
out = run.run_cell(bench, cell, config, mix, 31, 3.0, False)
print(json.dumps({"correct": out["line"]["correct"],
                  "metrics": sorted(out["line"]["metrics"]),
                  "arch": run.load_arch(config).__file__}))
"""


def test_a_new_architecture_is_new_files_only(tmp_path):
    """A copy of the benchmark, given an architecture module it has not
    seen (qwen3's, renamed), a configuration that names it and a cell in
    ``BENCHMARK.json``, runs that cell with no other file changed."""
    ignore = shutil.ignore_patterns("__pycache__")
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(run.HERE, chip, ignore=ignore)
    shutil.copytree(HERE, tmp_path / "tests" / "bench", ignore=ignore)
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    shutil.copy(chip / "archs" / "qwen3.py", chip / "archs" / "qwen3copy.py")
    cfg = dict(config("qwen3-0.6b"), name="copy-0.6b", model_type="qwen3copy")
    (chip / "configs" / "copy-0.6b.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][0], name="copy-0.6b",
                                 file="benchmarks/chip/configs/copy-0.6b.json"))
    bench["workloads"].append(dict(bench["workloads"][0], name="copy-0.6b.chat",
                                   config="copy-0.6b", traffic="chat"))
    for m in bench["end_to_end"]:
        if "qwen3-0.6b.chat" in m.get("workloads", []):
            m["workloads"].append("copy-0.6b.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", RUN_COPY], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out
    assert out["arch"] == str(chip / "archs" / "qwen3copy.py")
    assert out["metrics"] == sorted(
        m["name"] for m in run.cell_metrics(bench, "copy-0.6b.chat", trace=False))
    assert all(p.read_bytes() == b for p, b in before.items())
