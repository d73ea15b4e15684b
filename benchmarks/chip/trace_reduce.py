"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time, device time per jitted program, the top
device operations, and the longest idle gaps labelled by the harness span
the host was in.

Device planes are those named ``/device:TPU:<n>``; on each, the ``XLA
Ops`` line holds one event per operation and the ``XLA Modules`` line one
per execution of a jitted program (named ``jit_<function>(<id>)``).  Host
spans are ``jax.profiler.TraceAnnotation`` events on the host plane.
Times are the trace's nanoseconds, host and device on one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


class Trace(NamedTuple):
    ops: Dict[int, List[Tuple[str, float, float]]]       # device -> ops
    modules: Dict[int, List[Tuple[str, float, float]]]   # device -> programs
    spans: List[Tuple[str, float, float]]                # host annotations


_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module_event: str) -> str:
    """``jit__chunk_scan(42)`` -> ``_chunk_scan``."""
    return _MODULE.match(module_event).group(1)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, span_names: Sequence[str]) -> Trace:
    """Read the device ops and programs of every TPU plane, and the host
    annotations named in ``span_names``."""
    from jax.profiler import ProfileData

    ops: Dict[int, list] = defaultdict(list)
    modules: Dict[int, list] = defaultdict(list)
    spans = []
    wanted = set(span_names)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                dst = (ops if line.name == "XLA Ops" else modules)[int(m.group(1))]
                for e in line.events:
                    dst.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name in wanted:
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return Trace(dict(ops), dict(modules), sorted(spans, key=lambda s: s[1]))


def op_name(event: str) -> str:
    """An operation's short name: the HLO instruction's name without the
    instruction text the trace carries (``%while.72 = (...) while(...)``
    -> ``%while.72``)."""
    return event.split(" = ", 1)[0][:80]


def self_times(ops: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds per operation name, each operation's time less the time of
    the operations nested in it (a loop's body ops run inside the loop's
    own event on the same line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []                     # [name, start, end, child_ns]
    for n, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= a:
            top = stack.pop()
            out[top[0]] += (top[2] - top[1] - top[3]) * 1e-9
        if stack and b <= stack[-1][2]:
            stack[-1][3] += b - a
        stack.append([op_name(n), a, b, 0.0])
    for top in stack:
        out[top[0]] += (top[2] - top[1] - top[3]) * 1e-9
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]],
              starts: Sequence[float]) -> str:
    """The host span that overlaps the gap most, or ``other``.  ``spans``
    are sorted by start, ``starts`` are their starts; the harness's spans
    do not nest, so the search starts at the last span begun before the
    gap."""
    best, most = "other", 0.0
    for name, a, b in spans[max(0, bisect.bisect_right(starts, gap[0]) - 1):]:
        if b <= gap[0]:
            continue
        if a >= gap[1]:
            break
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > most:
            best, most = name, ov
    return best


def reduce(trace: Trace, window: Interval, top: int = 10) -> dict:
    """Device numbers inside ``window`` (ns), averaged over the devices
    that ran anything: busy seconds, seconds and count per program, the
    ``top`` operations by self time and the ``top`` longest idle gaps."""
    lo, hi = window
    devices = sorted(d for d, ops in trace.ops.items()
                     if any(b > lo and a < hi for _, a, b in ops))
    busy_s, op_s = 0.0, defaultdict(float)
    prog_s, prog_n = defaultdict(float), defaultdict(int)
    all_gaps = []
    # the spans inside the window say what the host was doing; the span
    # that marks the window itself says nothing
    spans = [s for s in trace.spans
             if s[2] > lo and s[1] < hi and (s[1], s[2]) != (lo, hi)]
    starts = [s[1] for s in spans]
    for d in devices:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in trace.ops[d]
               if b > lo and a < hi]
        busy = union((a, b) for _, a, b in ops)
        busy_s += sum(b - a for a, b in busy) * 1e-9
        for n, v in self_times(ops).items():
            op_s[n] += v
        for n, a, b in trace.modules.get(d, []):
            if b > lo and a < hi:
                name = program_name(n)
                prog_s[name] += (min(b, hi) - max(a, lo)) * 1e-9
                prog_n[name] += 1
        all_gaps += [(label_gap(g, spans, starts), (g[1] - g[0]) * 1e-9)
                     for g in gaps(busy, lo, hi)]
    nd = max(1, len(devices))
    by_label = defaultdict(float)
    for label, s in all_gaps:
        by_label[label] += s / nd
    return {
        "devices": len(devices),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s / nd,
        "programs": {n: {"count": prog_n[n] / nd, "seconds": prog_s[n] / nd}
                     for n in sorted(prog_s)},
        "device_ops": sorted(((n, s / nd) for n, s in op_s.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(all_gaps, key=lambda x: -x[1])[:top],
        "idle_by_span": dict(sorted(by_label.items(), key=lambda x: -x[1])),
    }
