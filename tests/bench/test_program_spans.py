"""``program_spans.py`` on inputs with known answers: a synthetic trace
with the program's spans nested in the harness's (written here as an
``.xplane.pb`` from ``synthetic_program_trace.json``), hand-made tracer
events and client stamps, and a traced run of a tiny cell on the CPU."""
import json
import os

import pytest

from bench_tiny import tiny_cell
from benchmarks.chip import program_spans, record, run, span_run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PS_PER_MS = 10**9


@pytest.fixture(scope="module")
def synthetic():
    with open(os.path.join(HERE, "synthetic_program_trace.json")) as f:
        return json.load(f)


def _plane(pid: int, name: str, lines: dict, stat: str = "") -> str:
    """One XPlane in text form; ``lines`` maps a line's name to its events
    as (name, start ms, end ms[, metadata name]).  The metadata name is
    the ``stat`` of the event's metadata for even-numbered events and of
    the event itself for odd ones: a reader finds it either way."""
    meta, out = {}, []
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        body = []
        for i, e in enumerate(evs):
            on_meta = len(e) > 3 and i % 2 == 0
            mid = meta.setdefault(e[0], (len(meta) + 1,
                                         e[3] if on_meta else None))[0]
            st = (f' stats {{ metadata_id: 1 str_value: "{e[3]}" }}'
                  if len(e) > 3 and not on_meta else "")
            body.append(f"events {{ metadata_id: {mid} "
                        f"offset_ps: {round(e[1] * PS_PER_MS)} "
                        f"duration_ps: {round((e[2] - e[1]) * PS_PER_MS)}{st} }}")
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                   + " ".join(body) + " }")
    for n, (i, v) in meta.items():
        st = f' stats {{ metadata_id: 1 str_value: "{v}" }}' if v else ""
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}"'
                   f'{st} }} }}')
    if stat:
        out.append(f'stat_metadata {{ key: 1 value {{ id: 1 name: "{stat}" }} }}')
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(out) + " }"


def _write_xplane(path: str, d: dict, with_program_spans: bool) -> str:
    from jax.profiler import ProfileData

    host = d["harness_spans"] + (d["program_spans"] if with_program_spans else [])
    text = "\n".join([
        _plane(1, "/device:TPU:0", {"XLA Ops": d["ops"],
                                    "XLA Modules": d["modules"]}, "tf_op"),
        _plane(2, "/host:CPU", {"python": host})])
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return path


@pytest.fixture(scope="module")
def traces(synthetic, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("xplane")
    return {k: _write_xplane(str(tmp / f"{k}.xplane.pb"), synthetic, k == "with")
            for k in ("with", "without")}


def _window(d):
    return tuple(x * 1e6 for x in d["window"])


def test_reduce_reads_the_same_with_program_spans(synthetic, traces):
    got = {k: trace_reduce.reduce(trace_reduce.load(p, run.SPANS),
                                  _window(synthetic))
           for k, p in traces.items()}
    assert got["with"] == got["without"]
    want = synthetic["expect"]
    assert got["with"]["busy_s"] == pytest.approx(want["busy_s"])
    assert got["with"]["idle_by_span"] == pytest.approx(want["idle_by_span"])


def test_program_spans_load_with_their_nesting(synthetic, traces):
    spans = program_spans.load(traces["with"])
    assert len(spans) == len(synthetic["program_spans"])
    assert [s.start for s in spans] == sorted(s.start for s in spans)
    depth = {}
    for s in spans:
        depth.setdefault(s.name, s.depth)
    for name, want in synthetic["expect"]["depths"].items():
        assert depth[name] == want, name
    assert program_spans.load(traces["without"]) == []


def test_idle_gaps_labelled_by_the_deepest_program_span(synthetic, traces):
    tr = trace_reduce.load(traces["with"], run.SPANS)
    spans = program_spans.load(traces["with"])
    got = program_spans.idle_by_program_span(tr, spans, _window(synthetic))
    want = synthetic["expect"]
    assert got["by_span"] == pytest.approx(want["idle_by_program_span"])
    assert [(n, pytest.approx(s)) for n, s in got["idle_gaps"]] == [
        (n, s) for n, s in want["idle_gaps"]]
    assert got["tick_idle_s"] == pytest.approx(want["tick_idle_s"])
    assert got["tick_idle_by_deepest"] == pytest.approx(
        want["tick_idle_by_deepest"])
    assert got["tick_idle_unnamed_share"] == pytest.approx(
        want["tick_idle_unnamed_share"])
    # every idle second is labelled once
    assert sum(got["by_span"].values()) == pytest.approx(
        sum(want["idle_by_span"].values()))


def test_pump_idle_time(synthetic, traces):
    for k, want in (("with", synthetic["expect"]["pump_idle_s"]),
                    ("without", None)):
        tr = trace_reduce.load(traces[k], run.SPANS)
        got = program_spans.pump_idle_s(tr, program_spans.load(traces[k]),
                                        _window(synthetic))
        assert got == (pytest.approx(want) if want is not None else None)


def test_device_time_by_named_scope(synthetic, traces):
    scoped, stat = program_spans.load_scoped_ops(traces["with"])
    assert stat == "tf_op"
    tr = trace_reduce.load(traces["with"], run.SPANS)
    got = program_spans.device_by_scope(scoped, tr.modules, _window(synthetic))
    want = synthetic["expect"]["device_by_scope"]
    assert set(got) == set(want)
    for prog, scopes in want.items():
        assert got[prog] == pytest.approx(scopes)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_chunk_scan)/jit(main)/while/body/attn/kv_write/scatter", "kv_write"),
    ("jit(_mixed_step_fn)/jit(main)/attn/dot_general", "attn"),
    ("jit(_chunk_scan)/lm_head/dot_general", "lm_head"),
    ("jit(maximum)/max", "other"),
    ("", "other"),
])
def test_op_scope(op_name, scope):
    assert program_spans.op_scope(op_name) == scope


def test_label_gap_prefers_the_deepest_of_equal_overlaps():
    spans = program_spans.nest([("fleet.tick", 0, 10), ("engine.pump", 2, 8),
                                ("pump.admit", 2, 4), ("pump.decode", 4, 8)])
    starts = [s.start for s in spans]
    assert program_spans.label_gap((2.5, 3.5), spans, starts) == "pump.admit"
    assert program_spans.label_gap((3, 5), spans, starts) == "engine.pump"
    assert program_spans.label_gap((7, 9), spans, starts) == "fleet.tick"
    assert program_spans.label_gap((11, 12), spans, starts) == "other"
    # a gap mostly past the tick (the fleet waiting for arrivals) is no
    # phase's, though the tick's tail overlaps it
    assert program_spans.label_gap((9, 14), spans, starts) == "other"


def _served(due, first):
    return record.Served(due=due, prompt_len=8, max_new=4,
                         stamps=[first, first + 0.1] if first else [])


def test_ttft_split_adds_up_to_the_time_to_first_token():
    served = {1: _served(10.0, 12.5), 2: _served(11.0, 14.0),
              3: _served(12.0, 30.0),          # first token after the close
              4: _served(13.0, None),          # no token yet
              5: _served(13.5, 15.0)}          # never admitted (hedge twin)
    events = [
        {"name": "req.queued", "rid": 1, "w": 11.0, "t": 3.0},
        {"name": "req.queued", "rid": 2, "w": 11.5, "t": 3.0},
        {"name": "req.queued", "rid": 3, "w": 12.5, "t": 4.0},
        {"name": "req.queued", "rid": 5, "w": 14.0, "t": 4.0},
        {"name": "req.dispatched", "rid": 1, "w": 11.1, "t": 3.0},
        {"name": "req.admitted", "rid": 1, "w": 11.2, "t": 3.0},
        {"name": "req.admitted", "rid": 2, "w": 12.5, "t": 3.0},
        {"name": "req.admitted", "rid": 2, "w": 13.5, "t": 4.0},  # requeued
        {"name": "req.admitted", "rid": 3, "w": 13.0, "t": 4.0},
    ]
    got = program_spans.ttft_split(events, served, close=20.0)
    assert got["n"] == 2
    assert got["intake_wait_ms"] == pytest.approx((1.0 + 0.5) / 2 * 1e3)
    assert got["slot_wait_ms"] == pytest.approx((0.2 + 1.0) / 2 * 1e3)
    assert got["ingest_ms"] == pytest.approx((1.3 + 1.5) / 2 * 1e3)
    # the three parts add up to the same requests' mean due-to-first-token
    assert (got["intake_wait_ms"] + got["slot_wait_ms"] + got["ingest_ms"]
            == pytest.approx((2.5 + 3.0) / 2 * 1e3))
    assert got["ttft_ms"] == pytest.approx(2750.0)
    # a program that stamps no req.admitted gives no split, and no error
    no_admit = [e for e in events if e["name"] != "req.admitted"]
    assert program_spans.ttft_split(no_admit, served, close=20.0) is None


def test_tick_ctl_ms_takes_the_pumps_out_of_each_tick():
    events = [
        {"name": "fleet.tick", "w": 1.0, "wall_s": 2.0, "parent": None},
        {"name": "engine.pump", "w": 1.1, "wall_s": 0.9, "parent": "fleet.tick"},
        {"name": "engine.pump", "w": 2.0, "wall_s": 0.95, "parent": "fleet.tick"},
        {"name": "fleet.tick", "w": 3.0, "wall_s": 1.0, "parent": None},
        {"name": "engine.pump", "w": 3.0, "wall_s": 0.97, "parent": "fleet.tick"},
        {"name": "fleet.tick", "w": 9.0, "wall_s": 3.0, "parent": None},  # past close
    ]
    got = program_spans.tick_ctl_ms(events, 0.5, 10.0)
    assert got == pytest.approx(((2.0 - 1.85) + (1.0 - 0.97)) / 2 * 1e3)
    assert program_spans.tick_ctl_ms([], 0.5, 10.0) is None


def test_traced_tiny_chat_run_reads_the_program_spans():
    bench, cell, config, mix = tiny_cell("qwen3-0.6b.chat")
    out = span_run.traced_window(cell, config, mix, 2**31 + 11, 3.0, bench)
    sp = out["ttft_split"]
    assert sp["n"] > 0
    assert sp["intake_wait_ms"] >= 0 and sp["slot_wait_ms"] >= 0
    assert sp["ingest_ms"] > 0
    assert (sp["intake_wait_ms"] + sp["slot_wait_ms"] + sp["ingest_ms"]
            == pytest.approx(sp["ttft_ms"]))
    assert out["tick_ctl_ms"] > 0
    assert out["program_spans_in_trace"] > 0     # the host plane holds them
    assert out["window"]["spans"] > 0
    assert out["window"]["tracer_events_lost"] == 0
    assert out["window"]["compiles_in_window"] == 0
    assert out["pump_idle_share"] is None        # no device plane on the CPU
    assert out["accepted"]["pump_ms.online"] > 0
    json.dumps(out, default=str)
