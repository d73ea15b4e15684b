"""``chip_smoke.py`` off the chip: it refuses a CPU-only JAX, and its
phases, checks and reference comparison run end to end at the reduced
config (the CPU rehearsal of the chip run)."""
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402


def test_refuses_to_run_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""           # no result line


def test_smoke_phases_pass_at_reduced_size(monkeypatch):
    for name, value in dict(MAX_LEN=128, BATCH=4, PREFILL_CHUNK=32, MAX_NEW=6,
                            PROMPT_LENS=(8, 60), CHECK_ROWS=2,
                            CHECK_LEN=21).items():
        monkeypatch.setattr(chip_smoke, name, value)
    assert chip_smoke.smoke(seed=0, n_requests=6, reduced=True) == []
