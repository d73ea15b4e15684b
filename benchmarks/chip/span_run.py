#!/usr/bin/env python3
"""One traced window of a cell, read through the program's own spans.

    python3 benchmarks/chip/span_run.py --workload <cell> --seed <n> \
        --seconds <s> [--out chiprun_out/<file>.json]

It serves the cell as ``run.py --trace 1`` does (the same weights, fleet,
warm-up, traffic and profiled window, built by ``run.py``'s own
functions), keeps the profiler trace and the fleet's flight recorder,
and prints one JSON line of readings:

* ``accepted`` -- the cell's per-layer metrics as ``run.py`` reads them;
* ``ttft_split`` -- intake wait, slot wait and ingest of the requests
  with a first token in the window (``program_spans.ttft_split``);
* ``tick_ctl_ms``, ``pump_idle_share`` (% of the traced window),
  ``idle_by_program_span`` and ``device_by_scope``;
* ``span_cost_us`` -- the tracer's cost per span and per event with the
  profiler off, measured in this process before the fleet is built;
* ``device_by_scope`` -- with the stat that carried the ops' metadata
  names; the compile cache is keyed on metadata here, so the programs
  carry this tree's named scopes;
* ``window`` -- events and spans recorded in the window, events lost to
  the ring, compiles in the window.

It checks no output against the reference; ``run.py`` does that.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import (  # noqa: E402
    program_spans, run, trace_reduce, traffic)


def span_cost_us(n: int = 20000) -> dict:
    """Microseconds per span (begin and end, annotation included) and per
    event on an enabled tracer, and per span on a disabled one, with no
    profiler session running."""
    from repro.obs import Tracer

    out = {}
    for label, tr, kind in (("span", Tracer(capacity=n), "span"),
                            ("event", Tracer(capacity=n), "event"),
                            ("disabled_span", Tracer.disabled(), "span")):
        t0 = time.perf_counter()
        if kind == "span":
            for _ in range(n):
                with tr.begin("pump.admit", cat="engine", sampled=True):
                    pass
        else:
            for i in range(n):
                tr.event("req.admitted", cat="req", rid=i)
        out[label] = (time.perf_counter() - t0) / n * 1e6
    return out


def op_stats_sample(path: str, n: int = 50) -> dict:
    """The stat names the first ``n`` TPU ops of the trace carry on their
    events and on their event metadata, with one value of each: where the
    ops' metadata names can be read."""
    from jax.profiler import ProfileData

    seen = {"event": {}, "metadata": {}}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for i, e in enumerate(line.events):
                if i >= n:
                    break
                for k, v in e.stats:
                    seen["event"].setdefault(k, str(v)[:160])
    for ops in program_spans.op_metadata(path).values():
        for st in list(ops.values())[:n]:
            for k, v in st.items():
                seen["metadata"].setdefault(k, str(v)[:160])
    return seen


def traced_window(cell: dict, config: dict, mix: dict, seed: int,
                  seconds: float, bench: dict) -> dict:
    import jax

    counter = run.CompileCounter()
    dev = jax.devices()[0]
    w = run.new_window(config, seconds)
    reqs = traffic.generate(mix, seed, seconds, w.shapes.vocab,
                            config["serving"]["max_len"])
    _, rt, client = run.set_up(config, mix, seed, reqs)

    tdir = tempfile.mkdtemp(prefix="chipbench_spans_")
    jax.profiler.start_trace(tdir)
    c0 = counter.n
    loop = run.drive(rt, client, reqs, w,
                     lambda name: jax.profiler.TraceAnnotation(name), {})
    compiles = counter.n - c0
    w.trace_end = loop["loop_end"]
    jax.profiler.stop_trace()
    ev = run.read_events(rt, w)
    events = rt.tracer.to_list()

    t_red = time.perf_counter()
    path = trace_reduce.find_xplane(tdir)
    tr = trace_reduce.load(path, run.SPANS)
    (_, lo, hi) = [sp for sp in tr.spans if sp[0] == "window"][-1]
    w.trace = trace_reduce.reduce(tr, (lo, hi))
    spans = program_spans.load(path)
    idle = program_spans.idle_by_program_span(tr, spans, (lo, hi))
    pump_idle = program_spans.pump_idle_s(tr, spans, (lo, hi))
    stats_seen = op_stats_sample(path)
    scoped, stat = program_spans.load_scoped_ops(path)
    by_scope = program_spans.device_by_scope(scoped, tr.modules, (lo, hi))
    shutil.rmtree(tdir, ignore_errors=True)

    accepted = {}
    for m in run.cell_metrics(bench, cell["name"], trace=True):
        v = run.load_metric(m["name"]).read(w)
        if v is not None:
            accepted[m["name"]] = v
    return {
        "cell": cell["name"], "seed": seed,
        "device": {"kind": dev.device_kind, "platform": dev.platform},
        "accepted": accepted,
        "ttft_p50_s": run.load_metric("ttft_p50_s").read(w),
        "ttft_split": program_spans.ttft_split(events, w.served, w.close),
        "tick_ctl_ms": program_spans.tick_ctl_ms(events, w.open, w.close),
        "tick_host_ms": run.load_metric("tick_host_ms").read(w),
        "pump_idle_share": (pump_idle / w.trace["window_s"] * 100.0
                            if pump_idle is not None and w.trace["window_s"]
                            else None),
        "idle_share": (1.0 - w.trace["busy_s"] / w.trace["window_s"]) * 100.0
        if w.trace["window_s"] else None,
        "idle_by_span": w.trace["idle_by_span"],
        "idle_by_program_span": idle,
        "device_by_scope": {"stat": stat, "seconds": by_scope,
                            "op_stats_seen": stats_seen},
        "program_spans_in_trace": len([sp for sp in spans
                                       if sp.end > lo and sp.start < hi]),
        "window": {**program_spans.span_counts(events, w.open, w.trace_end),
                   "tracer_events_lost": ev["tracer_events_lost"],
                   "compiles_in_window": compiles,
                   "ticks": len(w.in_window_ticks()),
                   "requests_due": len(w.served)},
        "breakdown_idle_gaps": w.trace["idle_gaps"],
        "reduce_s": time.perf_counter() - t_red,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find(bench["workloads"], args.workload, "workload")
    config = run.load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = run.load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    import jax

    if jax.devices()[0].platform != "tpu":
        run.say(f"span_run.py: cell {cell['name']} needs a TPU; JAX found "
                f"platform {jax.devices()[0].platform!r}", err=True)
        return 2
    run.use_compile_cache()
    # an executable read back from the compile cache carries the metadata
    # of the program first compiled under its key, and the key leaves
    # metadata out: key on it too, so the ops carry this tree's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    cost = span_cost_us()
    out = traced_window(cell, config, mix, args.seed, args.seconds, bench)
    out["span_cost_us"] = cost
    line = json.dumps(out, default=str)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
