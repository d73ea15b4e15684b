"""``trace_reduce.py`` and the metric readers on inputs with known
answers: a synthetic device trace kept beside this file, a trace recorded
here on the CPU, and a hand-made window of client-side stamps."""
import json
import os

import pytest

from bench_tiny import BENCH, tiny_config
from benchmarks.chip import record, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def synthetic():
    with open(os.path.join(HERE, "synthetic_trace.json")) as f:
        d = json.load(f)
    tr = trace_reduce.Trace(
        ops={int(k): [tuple(e) for e in v] for k, v in d["ops"].items()},
        modules={int(k): [tuple(e) for e in v] for k, v in d["modules"].items()},
        spans=[tuple(s) for s in d["spans"]])
    return trace_reduce.reduce(tr, tuple(d["window"])), d["expect"]


def test_busy_and_window(synthetic):
    got, want = synthetic
    assert got["devices"] == 1
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])


def test_program_times(synthetic):
    got, want = synthetic
    assert set(got["programs"]) == set(want["programs"])
    for name, (n, s) in want["programs"].items():
        assert got["programs"][name]["count"] == n
        assert got["programs"][name]["seconds"] == pytest.approx(s)


def test_top_ops_and_labelled_gaps(synthetic):
    got, want = synthetic
    assert [n for n, _ in got["device_ops"]][:1] == [want["device_ops"][0][0]]
    got_ops = dict(got["device_ops"])
    assert set(got_ops) == {n for n, _ in want["device_ops"]}
    for n, s in want["device_ops"]:
        assert got_ops[n] == pytest.approx(s)
    assert [(n, pytest.approx(s)) for n, s in got["idle_gaps"]] == [
        (n, s) for n, s in want["idle_gaps"]]
    assert sum(got["idle_by_span"].values()) == pytest.approx(
        want["window_s"] - want["busy_s"])


@pytest.mark.parametrize("event, name", [
    ("jit__chunk_scan(12)", "_chunk_scan"),
    ("jit__mixed_step_paged_fn(3)", "_mixed_step_paged_fn"),
    ("jit_maximum", "maximum"),
])
def test_program_name(event, name):
    assert trace_reduce.program_name(event) == name


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("tick"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)), run.SPANS)
    assert [s[0] for s in tr.spans] == ["window", "tick"]
    assert tr.ops == {}          # no TPU plane on the CPU: nothing to read
    win = tr.spans[0]
    red = trace_reduce.reduce(tr, (win[1], win[2]))
    assert red["devices"] == 0 and red["busy_s"] == 0


def _window():
    cfg = tiny_config(run.load_json(os.path.join(
        run.HERE, "configs", BENCH["configs"][0]["name"] + ".json")))
    w = record.Window(seconds=10.0, open=100.0, close=110.0,
                      shapes=run.load_arch(cfg).shapes(cfg), decode_chunk=4)
    # ten requests due a second apart; request i's first token comes
    # i/10 s after it is due, then a token every 20 ms (+1 ms per i)
    for i in range(10):
        r = record.Served(due=100.0 + i, prompt_len=32, max_new=5)
        first = r.due + i / 10
        r.stamps = [first + k * (0.020 + i * 0.001) for k in range(5)]
        r.tokens = [1] * 5
        w.served[i] = r
    # one more due at 109.5 that never got a token: it enters the TTFT tail
    w.served[10] = record.Served(due=109.5, prompt_len=32, max_new=5)
    return w


@pytest.mark.parametrize("name, want", [
    ("ttft_p50_s", 0.5),             # waits 0, .1 ... .9 and 0.5: median .5
    ("tpot_p90_ms", 28.1),           # 20 ... 29 ms: p90 by interpolation
    ("out_tok_s", 4.9),              # 49 tokens by the close, in 10 s
])
def test_end_to_end_readers(name, want):
    assert run.load_metric(name).read(_window()) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(m["name"] for m in BENCH["per_layer"]
                                        if m["source"] == "device_trace"))
def test_device_readers_say_nothing_without_a_trace(name):
    assert run.load_metric(name).read(_window()) is None


def test_op_self_time_leaves_out_nested_ops():
    ops = [("%while.7 = (s32[]) while(...)", 0, 100),
           ("%fusion.2 = bf16[8] fusion(...)", 10, 30),
           ("%fusion.3 = bf16[8] fusion(...)", 40, 60),
           ("%copy.4 = bf16[8] copy(...)", 120, 130)]
    got = trace_reduce.self_times(ops)
    assert got == pytest.approx({"%while.7": 60e-9, "%fusion.2": 20e-9,
                                 "%fusion.3": 20e-9, "%copy.4": 10e-9})
