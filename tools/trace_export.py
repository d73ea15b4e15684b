#!/usr/bin/env python
"""Convert a flight-recorder JSONL trace into a Chrome-trace / Perfetto
timeline.

Input: the JSONL event stream ``repro.obs.Tracer.dump_jsonl`` writes (one
flat JSON event per line, timestamps in control-loop seconds).  Output:
Chrome Trace Event Format JSON (``{"traceEvents": [...]}``) loadable in
``chrome://tracing`` or https://ui.perfetto.dev.

Layout:

* one PROCESS (pid) per replica, named after it — each request's serve
  interval on that replica is an ``X`` (complete) event on its own thread
  (tid = rid), so concurrent requests nest side by side per replica;
* within each serve interval, ``prefill`` (queued/dispatched -> first
  token) and ``decode`` (first token -> completion) sub-slices;
* pid 0 is the fleet control plane: mode switches, scale decisions,
  replica lifecycle, preemptions, KV flushes/restores as instant events
  (``i``) on per-category threads, plus mode as a counter track;
* engine pump phase walls (admit/dispatch/sync) become counter events on
  the replica that reported them, as do per-pump speculative-decode
  drafted/accepted token counts (``engine.speculate``).

A request that migrated (kill -> requeue -> re-dispatch) renders as one
serve slice per replica visited — the gap between them is exactly the
requeue-to-redispatch latency, visible on the timeline.

    python tools/trace_export.py fleet.jsonl -o fleet_chrome.json
    python tools/trace_export.py fleet.jsonl --stats

``--stats`` prints coverage: the fraction of completed requests whose
timeline carries at least one serve slice (the drills assert >= 0.99).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

from repro.obs.trace import load_jsonl, request_chains  # noqa: E402

# control-plane event name -> tid within the fleet process (pid 0);
# grouping by concern keeps the Perfetto control track readable
_CTL_TRACKS = {
    "ctl.mode_switch": 1,
    "ctl.scale": 2,
    "ctl.replica_fail": 3,
    "ctl.preempt_notice": 3,
    "ctl.preempt_deadline": 3,
    "ctl.wedge_death": 3,
    "ctl.crash_backoff": 3,
    "ctl.kv_flush": 4,
    "ctl.kv_restore": 4,
    "ctl.speculation": 5,
    "ctl.capacity_trade": 6,
}
_CTL_TRACK_NAMES = {1: "mode", 2: "autoscale", 3: "failures", 4: "kv",
                    5: "speculation", 6: "capacity trading"}
FLEET_PID = 0


def _us(t: float) -> float:
    """Control-loop seconds -> Chrome trace microseconds."""
    return float(t) * 1e6


def _args_of(ev: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in ev.items() if k not in ("t", "w", "name", "cat")}


def _serve_slices(chain: List[Dict[str, Any]]
                  ) -> List[Tuple[str, float, Optional[float], float]]:
    """One request's (replica, start, first_token_t|None, end) serve
    intervals, one per replica visited.  A slice opens at dispatch and
    closes at requeue/terminal (or the chain's last timestamp if the
    trace ends mid-flight)."""
    slices: List[Tuple[str, float, Optional[float], float]] = []
    open_rep: Optional[str] = None
    t0 = first_t = None
    t_last = chain[-1]["t"] if chain else 0.0

    def close(t_end: float) -> None:
        nonlocal open_rep, t0, first_t
        if open_rep is not None:
            slices.append((open_rep, t0, first_t, max(t_end, t0)))
        open_rep, t0, first_t = None, None, None

    for ev in chain:
        name = ev["name"]
        if name in ("req.dispatched", "req.hedged"):
            if open_rep is None or name == "req.dispatched":
                close(ev["t"])
                open_rep, t0 = str(ev.get("replica", "?")), ev["t"]
        elif name == "req.first_token":
            if first_t is None:
                first_t = ev["t"]
        elif name == "req.requeued":
            close(ev["t"])
        elif name in ("req.completed", "req.cancelled", "req.failed"):
            close(ev["t"])
    close(t_last)
    return slices


def convert(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Build the Chrome-trace dict from a flight-recorder event list."""
    out: List[Dict[str, Any]] = []
    pids: Dict[str, int] = {}

    def pid_of(replica: str) -> int:
        if replica not in pids:
            pids[replica] = len(pids) + 1       # pid 0 is the fleet track
            out.append({"ph": "M", "pid": pids[replica], "name": "process_name",
                        "args": {"name": f"replica {replica}"}})
        return pids[replica]

    out.append({"ph": "M", "pid": FLEET_PID, "name": "process_name",
                "args": {"name": "fleet control plane"}})
    for tid, tname in _CTL_TRACK_NAMES.items():
        out.append({"ph": "M", "pid": FLEET_PID, "tid": tid,
                    "name": "thread_name", "args": {"name": tname}})

    # request serve slices, nested prefill/decode per replica visit
    chains = request_chains(events)
    for rid, chain in sorted(chains.items()):
        # per-model attribution: req.* events carry the arch the request
        # targeted ("" = model-agnostic) — surface it on the serve slice
        model = next((e["model"] for e in chain if e.get("model")), "")
        for rep, t0, first_t, t1 in _serve_slices(chain):
            pid = pid_of(rep)
            base = {"pid": pid, "tid": rid, "cat": "req"}
            args = {"replica": rep}
            if model:
                args["model"] = model
            out.append({**base, "ph": "X", "name": f"serve r{rid}",
                        "ts": _us(t0), "dur": max(_us(t1) - _us(t0), 1.0),
                        "args": args})
            split = first_t if first_t is not None and t0 <= first_t <= t1 else None
            if split is not None:
                if split > t0:
                    out.append({**base, "ph": "X", "name": "prefill",
                                "ts": _us(t0), "dur": _us(split) - _us(t0)})
                if t1 > split:
                    out.append({**base, "ph": "X", "name": "decode",
                                "ts": _us(split), "dur": _us(t1) - _us(split)})

    mode = None
    for ev in events:
        name, cat = ev["name"], ev.get("cat", "")
        if cat == "ctl" and name in _CTL_TRACKS:
            out.append({"ph": "i", "pid": FLEET_PID, "tid": _CTL_TRACKS[name],
                        "name": name, "ts": _us(ev["t"]), "s": "p",
                        "args": _args_of(ev)})
            if name == "ctl.mode_switch" and ev.get("mode") != mode:
                mode = ev.get("mode")
                out.append({"ph": "C", "pid": FLEET_PID, "name": "mode",
                            "ts": _us(ev["t"]), "args": {"mode": mode}})
        elif cat == "ctl" and name.startswith("replica."):
            rep = str(ev.get("replica", "?"))
            out.append({"ph": "i", "pid": pid_of(rep), "tid": 0,
                        "name": name, "ts": _us(ev["t"]), "s": "t",
                        "args": _args_of(ev)})
        elif cat == "engine" and name == "engine.pump":
            rep = str(ev.get("replica", "?"))
            out.append({"ph": "C", "pid": pid_of(rep), "name": "pump phases",
                        "ts": _us(ev["t"]),
                        "args": {k: ev.get(k, 0.0)
                                 for k in ("admit_s", "dispatch_s", "sync_s")}})
        elif cat == "engine" and name == "engine.speculate":
            # speculation rides the replica that reported it: a counter
            # track of drafted vs accepted tokens per pump, so acceptance
            # collapse is visible on the timeline next to the pump phases
            rep = str(ev.get("replica", "?"))
            out.append({"ph": "C", "pid": pid_of(rep), "name": "speculation",
                        "ts": _us(ev["t"]),
                        "args": {k: ev.get(k, 0)
                                 for k in ("drafted", "accepted")}})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def coverage(events: List[Dict[str, Any]]) -> Tuple[float, int, int]:
    """(fraction, with_slices, completed): completed requests whose chain
    produced at least one serve slice on some replica."""
    chains = request_chains(events)
    completed = [rid for rid, chain in chains.items()
                 if any(e["name"] == "req.completed" for e in chain)]
    if not completed:
        return 1.0, 0, 0
    ok = sum(1 for rid in completed if _serve_slices(chains[rid]))
    return ok / len(completed), ok, len(completed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="JSONL trace from Tracer.dump_jsonl")
    ap.add_argument("-o", "--out", default="",
                    help="output path (default: <trace>.chrome.json)")
    ap.add_argument("--stats", action="store_true",
                    help="print event counts and request coverage")
    args = ap.parse_args(argv)

    events = load_jsonl(args.trace)
    doc = convert(events)
    out_path = args.out or args.trace + ".chrome.json"
    with open(out_path, "w") as f:
        json.dump(doc, f)
    frac, ok, total = coverage(events)
    print(f"{len(events)} events -> {len(doc['traceEvents'])} trace events "
          f"-> {out_path}")
    if args.stats:
        print(f"coverage: {ok}/{total} completed requests have serve slices "
              f"({frac:.1%})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
