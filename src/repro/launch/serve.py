"""Serving launcher: the paper's control loop wired to REAL model replicas.

Deployment units are (arch × tier × mode) triplets; their T_i/L_i profiles
come either from the paper's Table 1 (--paper-dus) or from roofline-derived
service rates of the dry-run artifacts (--roofline-dus).  A ServingEngine
(reduced config, or the published one with --full-width) executes real
decode steps for the traffic the router sends, while the simulator
supplies demand, capacity events, and autoscaling.

    PYTHONPATH=src python -m repro.launch.serve --duration 600 \
        --demand 400 --outage 200:400 --arch qwen3-0.6b
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Optional

import numpy as np


def default_results_dir() -> str:
    """Dry-run artifact root: ``--results-dir`` flag > ``REPRO_RESULTS_DIR``
    env > the repo-checkout-relative default (which only exists for
    in-tree runs — installed checkouts must override)."""
    env = os.environ.get("REPRO_RESULTS_DIR")
    if env:
        return os.path.join(env, "dryrun")
    return os.path.join(
        os.path.dirname(__file__), "..", "..", "..", "results", "dryrun"
    )


def roofline_dus(arch: str, results_dir: Optional[str] = None):
    """Build DU profiles from dry-run roofline JSONs (beyond-paper path)."""
    from repro.configs import TIERS, get_config
    from repro.core.deployment import profile_from_roofline

    results_dir = results_dir or default_results_dir()
    path = os.path.join(results_dir, f"{arch}__decode_32k__single.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        cell = json.load(f)
    if not cell.get("ok"):
        return None
    bound = max(
        cell["roofline"]["compute_s"],
        cell["roofline"]["memory_s"],
        cell["roofline"]["collective_s"],
    )
    cfg = get_config(arch)
    dus = []
    # heterogeneous fleet: same arch on different tiers; service time scales
    # with the tier's bottleneck resource vs v5e's
    base = TIERS["tpu-v5e"]
    for tier_name in ("tpu-v5e", "tpu-v4", "tpu-v6e"):
        tier = TIERS[tier_name]
        dom = cell["roofline"]["dominant"]
        scale = {
            "compute": base.peak_flops / tier.peak_flops,
            "memory": base.hbm_bw / tier.hbm_bw,
            "collective": base.ici_bw / tier.ici_bw,
        }[dom]
        dus.append(
            profile_from_roofline(
                cfg, tier,
                step_seconds=bound * scale,
                batch=128, chips=256,
            )
        )
    return dus


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--duration", type=float, default=600.0)
    ap.add_argument("--demand", type=float, default=400.0)
    ap.add_argument("--outage", default="", help="start:end seconds for pool-0 outage")
    ap.add_argument("--paper-dus", action="store_true",
                    help="use the paper's SD21 Table-1 profiles")
    ap.add_argument("--execute-samples", type=int, default=4,
                    help="real decode steps executed per 60s of sim time")
    ap.add_argument("--continuous", action="store_true",
                    help="run the sample decode through DecodeSlots "
                         "continuous batching instead of a fixed batch")
    ap.add_argument("--results-dir", default="",
                    help="dry-run artifact root for --roofline DUs "
                         "(default: $REPRO_RESULTS_DIR or the in-tree "
                         "results/ directory)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the control loop over LIVE ServingEngine "
                         "replicas (fleet runtime) instead of the analytic "
                         "simulator")
    ap.add_argument("--requests", type=int, default=100,
                    help="--fleet: number of requests in the trace")
    ap.add_argument("--trace-out", default="",
                    help="--fleet: write the flight-recorder event trace "
                         "(JSONL) here after the run")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the arch's published config (full depth and "
                         "width, bf16) instead of the reduced smoke model")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress informational output")
    args = ap.parse_args(argv)

    def say(*parts):
        if not args.quiet:
            print(*parts)

    if args.fleet:
        from repro.fleet.client import FleetClient
        from repro.fleet.runtime import build_demo_fleet

        outage = None
        if args.outage:
            s, e = (float(x) for x in args.outage.split(":"))
            outage = (s, e)
        rt = build_demo_fleet(arch=args.arch, n_requests=args.requests,
                              rate=max(args.demand / 100.0, 1.0),
                              outage=outage, reduced=not args.full_width)
        # the streaming client API: every trace request becomes a live
        # RequestHandle (status / tokens() / cancel()), and TTFT is
        # observed at the first emitted token instead of inferred later
        client = FleetClient(rt)
        handles = client.adopt_workload()
        client.drain()
        report = rt.report()
        say("fleet summary:",
            {k: round(v, 4) for k, v in report.summary().items()})
        say("mode trace:", [(round(t, 1), m) for t, m in report.mode_trace])
        done = [h.record for h in handles if h.record is not None]
        if done:
            stream_p99 = float(np.percentile([r.ttft_s for r in done], 99.0))
            compl_p99 = float(np.percentile([r.latency_s for r in done], 99.0))
            say(f"p99 TTFT: {stream_p99:.2f}s at the first streamed token "
                f"(a completion-only client would observe {compl_p99:.2f}s)")
        if args.trace_out:
            n_ev = client.export_trace(args.trace_out)
            say(f"trace: {n_ev} events -> {args.trace_out}")
        return report

    from repro.configs.sd21 import paper_deployment_units
    from repro.core.capacity import CapacityPool, synthetic_outage
    from repro.core.simulator import ClusterSimulator, SimConfig, steady

    dus = None
    if not args.paper_dus:
        rdir = (os.path.join(args.results_dir, "dryrun")
                if args.results_dir else None)
        dus = roofline_dus(args.arch, results_dir=rdir)
        if dus is None:
            print("no dry-run artifact for roofline DUs; falling back to --paper-dus")
    if dus is None:
        dus = list(paper_deployment_units())

    pools = [CapacityPool(base_capacity=20, provision_delay_s=15) for _ in dus]
    if args.outage:
        s, e = (float(x) for x in args.outage.split(":"))
        pools[0].events.append(synthetic_outage(s, e))

    sim = ClusterSimulator(dus, pools, steady(args.demand),
                           SimConfig(duration_s=args.duration))
    log = sim.run()
    s = log.summary()
    print("deployment units:")
    for d in dus:
        print(f"  {d.name}: T_max={d.t_max:.1f} rps  L={d.latency_s:.3f}s  "
              f"${d.cost_per_hour:.2f}/hr  c_i={d.cost_per_inference:.5f}")
    print("summary:", {k: round(v, 4) for k, v in s.items()})

    # execute REAL decode steps for a sample of routed requests — the same
    # fused scan path whose measured tokens/s backs the DU t_max profiles
    if args.execute_samples > 0:
        import time

        import jax

        from repro.configs import get_config
        from repro.models import Model
        from repro.serving import EngineConfig, ServingEngine

        cfg = get_config(args.arch)
        if not args.full_width:
            cfg = cfg.reduce()
        model = Model(cfg)
        params = model.init(jax.random.key(0))
        eng = ServingEngine(model, params, EngineConfig(max_len=64, decode_batch=4))
        rng = np.random.default_rng(0)
        if args.continuous:
            from repro.serving.api import EngineClient, InferenceRequest

            client = EngineClient(eng)
            t0 = time.perf_counter()
            handles = [
                client.submit(InferenceRequest(
                    prompt=rng.integers(0, cfg.vocab_size, (1, 16)),
                    max_new=args.execute_samples))
                for _ in range(4)
            ]
            streamed = list(handles[0].tokens())   # drives pumps while live
            client.drain()
            dt = time.perf_counter() - t0
            n = sum(h.result().size for h in handles)
            print(f"continuous batching (streaming client): {n} tokens over "
                  f"{len(handles)} requests in {dt:.3f}s ({n / dt:.1f} tok/s); "
                  f"first handle streamed {streamed} "
                  f"(TTFT {handles[0].record.ttft_s * 1e3:.1f}ms)")
        else:
            prompt = {
                "inputs": jax.numpy.asarray(
                    rng.integers(0, cfg.vocab_size, (4, 16))
                )
            }
            toks = eng.generate(prompt, steps=args.execute_samples, prompt_len=16)
            t0 = time.perf_counter()
            toks = eng.generate(prompt, steps=args.execute_samples, prompt_len=16)
            dt = time.perf_counter() - t0
            print(f"executed {toks.size} real decode tokens on replica engine "
                  f"({cfg.name}, {toks.size / dt:.1f} tok/s warm); "
                  f"sample: {toks[0].tolist()}")
    return log


if __name__ == "__main__":
    main()
