#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` (the checkout's
root); it names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``).  In one process the harness

  1. stops unless JAX's devices are TPUs, as many as the cell asks for;
  2. keeps JAX's compile cache in the checkout (``.jax_cache``), or where
     ``JAX_COMPILATION_CACHE_DIR`` says;
  3. makes the weights on the device from ``--seed`` and builds the fleet
     through ``FleetRuntime`` (two one-replica tiers, as configured);
     what belongs to the architecture (its work model, the program's
     model fields, its weights, its reference) comes from the module
     ``archs/<model_type>.py`` that the configuration's ``model_type``
     names;
  4. warms up with the runtime's own ``warmup()``;
  5. releases each request through ``FleetClient.submit`` when it is due on
     the wall clock, ticks the fleet back to back, and stamps every token
     as the client receives it, for exactly ``--seconds``;
  6. checks a sample of the finished requests against the architecture's
     float32 reference forward and prints the result as its last line.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` takes a
profiler trace of the window and reports its per-layer metrics
(``metrics/<name>.py``).  Earlier lines carry readings, not results.

``--control 1`` puts the control in the program's place for the check:
each checked request's served tokens are replaced by the tokens that the
float8 control of the architecture's reference (``served_gaps``) puts
first at the same positions, and the same ``correct`` is decided on them.
It has to read false.  A benchmark run leaves it at 0.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import record, traffic  # noqa: E402

SPANS = ("window", "submit", "tick", "wait_for_arrival")
# served tokens compared with the reference in each run: at least this
# many, over at most CHECK_REQUESTS requests (the longest finished one
# always among them)
CHECK_TOKENS = 320
CHECK_REQUESTS = 8


def say(*parts, err: bool = False) -> None:
    print(*parts, flush=True, file=sys.stderr if err else sys.stdout)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


@functools.cache
def _load_module(path: str, prefix: str):
    """The module at ``path``, loaded once per process."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    """The reader module ``metrics/<name>.py``; a metric ``<q>.<cells>``
    with no file of its own (one quantity reported under another name for
    another kind of cell) is read by ``metrics/<q>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, "metrics", name.rsplit(".", 1)[0] + ".py")
    return _load_module(path, "chipbench_metric_")


def load_arch(config: dict):
    """The architecture module ``archs/<model_type>.py`` of a
    configuration: its work model, model fields, weights and reference
    (the interface is stated in ``work.py``)."""
    mt = config["model_type"]
    path = os.path.join(HERE, "archs", f"{mt}.py")
    if not os.path.exists(path):
        raise SystemExit(f"configuration {config.get('name')!r} names "
                         f"model_type {mt!r}, and there is no architecture "
                         f"module {os.path.relpath(path, ROOT)}")
    return _load_module(path, "chipbench_arch_")


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries of ``BENCHMARK.json`` that this cell reports:
    end-to-end with ``trace`` off, per-layer with it on."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    it is set (JAX reads it itself), else one fixed directory in the
    checkout, so that a second run on the same disk hits.  Every program
    is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles that JAX reports, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class ClientSink:
    """The harness's streaming sink on the runtime: stamps every token and
    completion on the wall clock when the runtime hands it over."""

    def __init__(self, w: record.Window):
        self.w = w
        self.tick = -1              # harness tick index in progress

    def on_tokens(self, rid, toks, replica, t) -> None:
        now = time.perf_counter()
        s = self.w.served.get(rid)
        if s is None:
            return
        self.w.deliveries.append(record.Delivery(
            self.tick, rid, len(s.tokens), len(toks)))
        s.tokens += [int(x) for x in toks]
        s.stamps += [now] * len(toks)
        s.replica = replica

    def on_complete(self, rid, toks, rec) -> None:
        s = self.w.served.get(rid)
        if s is not None:
            s.done = time.perf_counter()

    def on_drop(self, rid, t, reason="") -> None:
        s = self.w.served.get(rid)
        if s is not None:
            s.dropped = reason or "dropped"


# -- configuration -----------------------------------------------------------
def make_tiers(config: dict, seed: int):
    from repro.fleet.runtime import TierSpec

    sv = config["serving"]
    if sv.get("attention", "xla") != "xla":
        raise ValueError(f"attention {sv['attention']!r}: only 'xla' is built")
    common = dict(
        arch=sv["arch"], reduced=False, param_seed=seed,
        model_overrides=load_arch(config).overrides(config),
        max_len=sv["max_len"], decode_batch=sv["decode_batch"],
        decode_chunk=sv["decode_chunk"], queue_limit=sv["queue_limit"],
        prefill_chunk=sv["prefill_chunk"],
        capacity_prefill_chunk=sv["capacity_prefill_chunk"],
        base_capacity=sv["replicas_per_tier"],
        initial_replicas=sv["replicas_per_tier"],
        min_replicas=sv["replicas_per_tier"],
    )
    tiers = []
    for t in sv["tiers"]:
        extra = {}
        if t["paged_kv"]:
            extra = dict(paged_kv=True, page_size=sv["page_size"],
                         num_pages=1 + sv["decode_batch"] * sv["max_len"]
                         // sv["page_size"])
        tiers.append(TierSpec(name=t["name"], cost_per_hour=t["cost_per_hour"],
                              nominal_t_max=t["nominal_t_max"],
                              latency_s=t["latency_s"], **common, **extra))
    return tiers


def build_fleet(config: dict, seed: int, params):
    """A ``FleetRuntime`` serving ``params``: the weights are placed where
    the runtime looks for a tier's model, so it never makes its own."""
    import jax

    import dataclasses

    from repro.configs import get_config
    from repro.fleet.runtime import FleetConfig, FleetRuntime
    from repro.models import Model

    tiers = make_tiers(config, seed)
    spec = tiers[0]
    overrides = dict(spec.model_overrides)
    cfg = dataclasses.replace(get_config(spec.arch), **overrides)
    model = Model(cfg)
    want = jax.eval_shape(model.init, jax.random.key(0))
    have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise RuntimeError("the program's parameter layout differs from the "
                           "benchmark's weights (archs/"
                           f"{config['model_type']}.py make_weights)")
    rt = FleetRuntime(tiers, [], FleetConfig(seed=seed))
    rt._model_cache[(spec.arch, spec.param_seed, spec.reduced,
                     tuple(sorted(overrides.items())))] = (model, params)
    for t in tiers:
        if rt._engine_for(t).params is not params:
            raise RuntimeError(f"tier {t.name} did not take the benchmark's weights")
    return rt


# -- the measured window -----------------------------------------------------
WARM_REQUESTS = 4


def new_window(config: dict, seconds: float) -> record.Window:
    """An empty window record with the architecture's work model and, on a
    TPU, the chip's published peaks."""
    import jax

    from benchmarks.chip import peaks

    w = record.Window(seconds=float(seconds), open=0.0, close=0.0,
                      shapes=load_arch(config).shapes(config),
                      decode_chunk=config["serving"]["decode_chunk"])
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        pk = peaks.peak_for(dev.device_kind)
        w.peak_flops, w.peak_bytes_s = pk.flops, pk.hbm_bytes_s
    return w


def set_up(config: dict, mix: dict, seed: int, reqs=()):
    """The weights made from the seed, the fleet serving them, warmed up
    for the mix and holding what the window's sessions served before it
    (``serve_earlier``): (params, runtime, client)."""
    import jax

    from repro.fleet.client import FleetClient

    arch = load_arch(config)
    params = arch.make_weights(config, seed)
    jax.block_until_ready(params)
    rt = build_fleet(config, seed, params)
    client = FleetClient(rt)
    warm_up(rt, client, mix, seed, arch.shapes(config).vocab, config["serving"])
    serve_earlier(client, reqs)
    return params, rt, client


def warm_up(rt, client, mix: dict, seed: int, vocab: int, sv: dict) -> None:
    """The runtime's own ``warmup()`` (every step program the tiers can
    run), then a few requests of the mix's median prompt served to the
    end, so that the control loop's first ticks, which compile the host
    side's small programs, run before the window."""
    from repro.serving.api import InferenceRequest

    rt.warmup()
    rng = np.random.default_rng([seed, 0x3A7])
    plen = int(mix["prompt"]["median"])
    for _ in range(WARM_REQUESTS):
        client.submit(InferenceRequest(
            prompt=rng.integers(0, vocab, size=plen, dtype=np.int32),
            max_new=2 * sv["decode_chunk"]))
    client.drain()


def serve_earlier(client, reqs) -> None:
    """Serve, in due order, the previous turn's prompt of every request
    that continues a session (``Request.earlier``), one token each, and
    drain: the cache then holds what those turns left in it."""
    from repro.serving.api import InferenceRequest

    earlier = [r for r in reqs if r.earlier]
    for r in earlier:
        client.submit(InferenceRequest(prompt=r.prompt[:r.earlier], max_new=1))
    if earlier:
        client.drain()


def program_counters(rt) -> Dict[str, int]:
    """Counters the program keeps, summed over its tiers: prompt tokens
    its engines served from cached pages, requests its dispatcher placed,
    and those it placed by cached prefix."""
    d = rt.dispatcher
    engines = {id(r.session.eng): r.session.eng
               for reps in rt.replicas.values() for r in reps if r.session}
    return {"reused_tokens": sum(e.telemetry.reused_tokens
                                 for e in engines.values()),
            "dispatched": sum(d.dispatched_per_tier.values()),
            "affinity_placements": d.affinity_placements}


def drive(rt, client, reqs, w: record.Window, span,
          prompts: Dict[int, np.ndarray]) -> dict:
    """Release ``reqs`` when due, tick back to back, for ``w.seconds``;
    ``w.counters`` gets what the program's counters gained meanwhile."""
    from repro.serving.api import InferenceRequest

    sink = ClientSink(w)
    rt.attach_sink(sink)
    late, i, n = [], 0, len(reqs)
    c0 = program_counters(rt)
    with span("window"):
        w.open = time.perf_counter()
        w.close = w.open + w.seconds
        while True:
            now = time.perf_counter()
            if now >= w.close:
                break
            if i < n and w.open + reqs[i].due_s <= now:
                with span("submit"):
                    while i < n and w.open + reqs[i].due_s <= now:
                        r = reqs[i]
                        h = client.submit(InferenceRequest(prompt=r.prompt,
                                                           max_new=r.max_new))
                        due = w.open + r.due_s
                        w.served[h.rid] = record.Served(due, len(r.prompt),
                                                        r.max_new)
                        prompts[h.rid] = r.prompt
                        late.append(time.perf_counter() - due)
                        i += 1
            if rt.busy:
                sink.tick = len(w.ticks)
                t_virtual, t0 = rt.t, time.perf_counter()
                with span("tick"):
                    client.tick()
                w.ticks.append(record.Tick(t_virtual, t0, time.perf_counter()))
            else:
                nxt = w.open + reqs[i].due_s if i < n else w.close
                with span("wait_for_arrival"):
                    time.sleep(max(0.0, min(nxt, w.close) - time.perf_counter()))
        end = time.perf_counter()
    w.counters = {k: v - c0[k] for k, v in program_counters(rt).items()}
    return {"late_s": late, "loop_end": end}


def read_events(rt, w: record.Window) -> dict:
    """Pump spans and dispatch records of the window from the tracer."""
    ticks_t = {tk.t for tk in w.ticks}
    compiles = 0
    for ev in rt.tracer.events:
        if ev.get("t") not in ticks_t:
            continue
        name = ev["name"]
        if name == "engine.pump":
            w.pumps.append(record.Pump(ev["t"], ev["wall_s"], ev["occupancy"]))
        elif name == "req.dispatched":
            w.dispatched_t.setdefault(ev["rid"], ev["t"])
        elif name == "req.admitted" and ev["rid"] in w.served:
            w.admitted_prompt_tokens += w.served[ev["rid"]].prompt_len
        elif name == "engine.compile":
            compiles += ev.get("new_traces", 1)
    return {"engine_compile_events": compiles,
            "tracer_events_lost": max(0, rt.tracer.emitted - rt.tracer.capacity)}


# -- the check against the reference -----------------------------------------
def pick_checked(w: record.Window, seed: int) -> list:
    """A sample, drawn from the seed, of the requests finished in the
    window: the longest first, then one from each replica not yet in, then
    at random until ``CHECK_TOKENS`` served tokens or ``CHECK_REQUESTS``."""
    done = sorted(rid for rid, s in w.served.items()
                  if s.done is not None and s.done <= w.close
                  and len(s.tokens) == s.max_new)
    if not done:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    order = list(rng.permutation(done))
    longest = max(done, key=lambda r: (w.served[r].prompt_len
                                       + len(w.served[r].tokens), -r))
    picked = [longest]
    for rid in order:
        if w.served[rid].replica not in {w.served[p].replica for p in picked}:
            picked.append(rid)
    for rid in order:
        if (sum(len(w.served[p].tokens) for p in picked) >= CHECK_TOKENS
                or len(picked) >= CHECK_REQUESTS):
            break
        if rid not in picked:
            picked.append(rid)
    return [int(r) for r in picked]


def check(params, config: dict, w: record.Window, prompts: Dict[int, np.ndarray],
          picked: list, control: bool = False) -> dict:
    """The widest gap by which a served token's logit lies below the float32
    reference's best, over the picked requests; with ``control`` also the
    gap of the tokens the float8 control puts first."""
    arch = load_arch(config)
    gaps, cgaps, n = [], [], 0
    for rid in picked:
        served = np.asarray(w.served[rid].tokens, np.int32)
        g, cg = arch.served_gaps(params, config, prompts[rid], served,
                                 config["serving"]["max_len"], control=control)
        gaps.append(float(np.max(g)))
        if cg is not None:
            cgaps.append(float(np.max(cg)))
        n += len(served)
    return {"max_logit_gap": max(gaps) if gaps else None,
            "control_max_logit_gap": max(cgaps) if cgaps else None,
            "checked_requests": len(picked), "checked_tokens": n}


def judge(gap, failed: int, checked_tokens: int, limits: dict):
    """The numbers compared, each beside its limit, and ``correct``."""
    checks = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "checked_tokens": {"value": checked_tokens,
                           "limit": limits["min_checked_tokens"]},
    }
    correct = (gap is not None and gap <= limits["max_logit_gap"]
               and failed == 0
               and checked_tokens >= limits["min_checked_tokens"])
    return checks, bool(correct)


# -- one run -----------------------------------------------------------------
def run_cell(bench: dict, cell: dict, config: dict, mix: dict, seed: int,
             seconds: float, trace: bool, *, control: bool = False) -> dict:
    """Everything a run does after the look for a chip; returns the result
    line as a dict (and the readings printed before it).  ``control`` judges
    the float8 control's tokens in place of the served ones (the program's
    own gap goes to the readings)."""
    import jax

    from benchmarks.chip import trace_reduce

    counter = CompileCounter()
    dev = jax.devices()[0]
    w = new_window(config, seconds)
    reqs = traffic.generate(mix, seed, seconds, w.shapes.vocab,
                            config["serving"]["max_len"])
    params, rt, client = set_up(config, mix, seed, reqs)
    prompts = {}

    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace else (
        lambda name: contextlib.nullcontext())
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(tdir)
    c0 = counter.n
    setup_s = w.setup_s = time.perf_counter() - T_START
    loop = drive(rt, client, reqs, w, span, prompts)
    compiles = counter.n - c0
    w.trace_end = loop["loop_end"]
    if trace:
        jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peak_bytes = max((st.get("peak_bytes_in_use", 0) for st in stats), default=0)

    ev = read_events(rt, w)
    readings = {
        "requests_due": len(w.served),
        "generator_late_s": {"median": float(np.median(loop["late_s"])) if loop["late_s"] else None,
                             "max": float(np.max(loop["late_s"])) if loop["late_s"] else None},
        "ticks": len(w.in_window_ticks()),
        "window_overrun_s": loop["loop_end"] - w.close,
        "compiles_in_window": compiles,
        "engine_compile_events_in_window": ev["engine_compile_events"],
        "tracer_events_lost": ev["tracer_events_lost"],
        "mode_trace": [(t, int(m)) for t, m in rt.mode_trace
                       if w.ticks and t >= w.ticks[0].t],
        "served_by_replica": {},
        "memory_peak_bytes": peak_bytes,
        "setup_s": setup_s,
        "persistent_cache_hits": counter.hits,
        "program_counters": w.counters,
        "admitted_prompt_tokens": w.admitted_prompt_tokens,
    }
    for sr in w.served.values():
        if sr.replica:
            readings["served_by_replica"][sr.replica] = \
                readings["served_by_replica"].get(sr.replica, 0) + 1

    breakdown = None
    if trace:
        t_red = time.perf_counter()
        tr = trace_reduce.load(trace_reduce.find_xplane(tdir), SPANS)
        shutil.rmtree(tdir, ignore_errors=True)
        win = [sp for sp in tr.spans if sp[0] == "window"]
        if win:
            w.trace = trace_reduce.reduce(tr, (win[-1][1], win[-1][2]))
            breakdown = {"device_ops": [[n, v] for n, v in w.trace["device_ops"]],
                         "idle_gaps": [[n, v] for n, v in w.trace["idle_gaps"]]}
            readings["trace"] = {k: w.trace[k] for k in
                                 ("devices", "window_s", "busy_s", "programs",
                                  "idle_by_span")}
        readings["trace_reduce_s"] = time.perf_counter() - t_red
        e2e = {}
        for m in cell_metrics(bench, cell["name"], trace=False):
            v = load_metric(m["name"]).read(w)
            if v is not None:
                e2e[m["name"]] = v
        readings["traced_end_to_end"] = e2e

    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        v = load_metric(m["name"]).read(w)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # free the program's state before the reference runs: its peak is read
    del client, rt
    gc.collect()
    t_chk = time.perf_counter()
    picked = pick_checked(w, seed)
    got = check(params, config, w, prompts, picked, control=control)
    readings["check_s"] = time.perf_counter() - t_chk
    readings.update({k: got[k] for k in ("checked_requests", "checked_tokens")})
    gap = got["max_logit_gap"]
    if control:
        readings["program_max_logit_gap"] = gap
        gap = got["control_max_logit_gap"]
    failed = sum(1 for sr in w.served.values() if sr.dropped)
    checks, correct = judge(gap, failed, got["checked_tokens"],
                            config["check_limits"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    if trace and w.trace:
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
    line = {"correct": correct, "attempted": len(w.served),
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return {"line": line, "readings": readings, "window": w, "picked": picked}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(bench["workloads"], args.workload, "workload")
    config = load_json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        say(f"run.py: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devs)} device(s) on platform {devs[0].platform!r}",
            err=True)
        return 2
    cache = use_compile_cache()
    say(f"[bench] {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} control {args.control}; "
        f"device {devs[0].device_kind} x{len(devs)}, "
        f"jax {jax.__version__}, compile cache {cache}")
    out = run_cell(bench, cell, config, mix, args.seed, args.seconds,
                   bool(args.trace), control=bool(args.control))
    say("[bench] readings " + json.dumps(out["readings"], default=str))
    for name, c in out["line"]["checks"].items():
        say(f"[bench] check {name} {c['value']} limit {c['limit']}", err=True)
    print(json.dumps(out["line"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
