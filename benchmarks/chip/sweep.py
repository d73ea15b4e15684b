#!/usr/bin/env python3
"""Find a cell's knee once: one process, one set-up, a ladder of offered
rates over the cell's open-loop mix.  Not part of a benchmark run.

    python3 benchmarks/chip/sweep.py --workload qwen3-0.6b.chat \
        --rates 1,2,4,8 --seconds 30 --seed 7 [--out sweep.json]

For each rung the earlier turns of its sessions are served (as a run's
set-up does) and the harness's own window loop runs at that rate; then
the fleet is ticked until idle before the next rung.  A rung reports the
requests due and finished, the outstanding requests at each quarter of
the window (a backlog that grows across the window is past the knee),
the window's TTFT and TPOT tails, and its shares of prompt tokens served
from cache and of requests placed by cached prefix.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import record, run, traffic  # noqa: E402


def outstanding(w: record.Window, t: float) -> int:
    return sum(1 for s in w.served.values()
               if s.due <= t and (s.done is None or s.done > t))


def rung(rt, client, config, mix, rate, seed, seconds) -> dict:
    w = run.new_window(config, seconds)
    reqs = traffic.generate(dict(mix, rate_rps=rate), seed, seconds,
                            w.shapes.vocab, config["serving"]["max_len"])
    run.serve_earlier(client, reqs)
    run.drive(rt, client, reqs, w, lambda name: contextlib.nullcontext(), {})
    run.read_events(rt, w)
    q = [outstanding(w, w.open + f * seconds) for f in (0.25, 0.5, 0.75, 1.0)]
    out = {"rate_rps": rate, "due": len(w.served),
           "finished": sum(1 for x in w.served.values()
                           if x.done is not None and x.done <= w.close),
           "outstanding_at_quarters": q,
           "ticks": len(w.in_window_ticks())}
    for name in ("ttft_p50_s", "tpot_p90_ms", "out_tok_s", "tick_host_ms",
                 "pump_ms", "prefix_reused_share", "affinity_share"):
        out[name] = run.load_metric(name).read(w)
    t0 = time.perf_counter()
    while rt.busy:
        client.tick()
    out["drain_s"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = run.find(bench["workloads"], args.workload, "workload")
    config = run.load_json(os.path.join(run.HERE, "configs",
                                        cell["config"] + ".json"))
    mix = run.load_json(os.path.join(run.HERE, "traffic",
                                     cell["traffic"] + ".json"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    run.use_compile_cache()
    _, rt, client = run.set_up(config, mix, args.seed)
    rungs = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        rungs.append(rung(rt, client, config, mix, rate, args.seed + i,
                          args.seconds))
        print("[sweep] " + json.dumps(rungs[-1]), flush=True)
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in jax.local_devices())
    print(f"[sweep] memory_peak_bytes {peak}", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seconds": args.seconds,
                       "rungs": rungs}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
