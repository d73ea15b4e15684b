"""CPU rehearsals of a whole run at a tiny size: everything the harness
does after its look for a chip -- weights from the seed, the fleet, the
warm-up, the timed window at the mix's load, the metric readers and the
check against the float32 reference -- and the float8 control put in the
program's place, which the same check has to judge not correct.  Every
cell of ``BENCHMARK.json`` gets one."""
import pytest

from bench_tiny import BENCH, tiny_cell
from benchmarks.chip import run

CELLS = [c["name"] for c in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_run_is_correct_and_the_control_is_not(name):
    bench, cell, config, mix = tiny_cell(name)
    out = run.run_cell(bench, cell, config, mix, 2**31 + 5, 4.0, False,
                       control=True)
    line, r, w = out["line"], out["readings"], out["window"]
    limits = config["check_limits"]
    # the control, judged in the program's place, is not correct ...
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["max_logit_gap"]["value"] > limits["max_logit_gap"]
    # ... and the program's own tokens of the same run, judged alike, are
    checks, ok = run.judge(r["program_max_logit_gap"], line["failed"],
                           r["checked_tokens"], limits)
    assert ok, checks
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert r["compiles_in_window"] == 0
    want = {m["name"] for m in run.cell_metrics(bench, name, trace=False)}
    assert set(line["metrics"]) == want
    # both tiers' cache layouts served requests and are in the check
    replicas = {w.served[rid].replica for rid in out["picked"]}
    assert len(replicas) == 2
    # what the cell's per-layer metrics read from the program's spans and
    # counters is there in an untraced run too
    for m in run.cell_metrics(bench, name, trace=True):
        if m["source"] != "device_trace":
            assert run.load_metric(m["name"]).read(w) > 0, m["name"]


def test_traced_run_reports_the_host_side_layers():
    bench, cell, config, mix = tiny_cell("qwen3-0.6b.chat")
    out = run.run_cell(bench, cell, config, mix, 17, 3.0, True)
    line = out["line"]
    assert line["correct"]
    # the CPU has no TPU plane: device metrics are left out, never 0
    for m in run.cell_metrics(bench, "qwen3-0.6b.chat", trace=True):
        if m["source"] == "device_trace":
            assert m["name"] not in line["metrics"]
        else:
            assert line["metrics"][m["name"]]["value"] > 0
    assert set(line["device"]) >= {"busy_s", "window_s", "memory_peak_bytes"}
    assert out["readings"]["traced_end_to_end"]["ttft_p50_s"] > 0
