"""Readings from the program's own spans: the flight recorder's wall-clock
events (``repro.obs.Tracer``) and the same spans on the host plane of a
profiler trace, where the tracer's ``TraceAnnotation``s put them on the
device ops' clock.

From the trace (``.xplane.pb``):

* ``load`` -- the program's spans (names starting ``fleet.``, ``pump.``,
  ``engine.``) with their nesting depth;
* ``idle_by_program_span`` -- each idle gap of the device labelled by the
  deepest program span that overlaps it most (``label_gap``), summed per
  label, and the share of the idle time inside the harness's ``tick``
  spans that no phase of the tick names;
* ``pump_idle_s`` -- device idle time that falls inside ``engine.pump``;
* ``device_by_scope`` -- each step program's op self-time grouped by the
  model's ``jax.named_scope`` in the op's metadata.

From the tracer's events and the client's stamps (``record.Served``):

* ``ttft_split`` -- due time to first token at the client, cut at the
  ``req.queued`` and ``req.admitted`` wall stamps into intake wait, slot
  wait and ingest (the three add up to the time to first token);
* ``tick_ctl_ms`` -- ``fleet.tick`` wall time less its ``engine.pump``
  children.

Times from the trace are its nanoseconds; times from the tracer are
``time.perf_counter`` seconds, the clock the harness stamps on.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from benchmarks.chip import trace_reduce

PREFIXES = ("fleet.", "pump.", "engine.")
# the model's named scopes (models/transformer.py, models/attention.py)
SCOPES = ("attn", "kv_write", "mlp", "fuse_weights", "lm_head")
# device-op stats that may carry the op's metadata name
# ("jit(f)/.../attn/kv_write/scatter"), in the order they are tried
SCOPE_STATS = ("tf_op", "name", "long_name")
STEP_PROGRAMS = ("_chunk_scan", "_chunk_scan_paged", "_mixed_step_fn",
                 "_mixed_step_paged_fn")


class Span(NamedTuple):
    name: str
    start: float            # ns
    end: float
    depth: int              # 0 for a span no other program span holds


def is_program_span(name: str) -> bool:
    return name.startswith(PREFIXES)


def nest(spans: Iterable[Tuple[str, float, float]]) -> List[Span]:
    """Spans sorted by start, each with the number of spans holding it."""
    out, stack = [], []
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1] <= a:
            stack.pop()
        out.append(Span(name, a, b, len(stack)))
        stack.append(b)
    return out


def load(path: str) -> List[Span]:
    """The program's spans on the host plane of the trace at ``path``."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if is_program_span(e.name):
                    found.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return nest(found)


def label_gap(gap: Tuple[float, float], spans: Sequence[Span],
              starts: Sequence[float]) -> str:
    """The program span that overlaps the gap most, the deepest of those
    that overlap it equally (a phase that holds the whole gap beats the
    tick around it); ``other`` where more of the gap lies outside every
    program span than inside that one.  ``spans`` are sorted by start and
    ``starts`` are their starts."""
    best, key, held = "other", (0.0, -1), []
    for sp in spans[:bisect.bisect_left(starts, gap[1])]:
        ov = min(sp.end, gap[1]) - max(sp.start, gap[0])
        if ov > 0:
            held.append((max(sp.start, gap[0]), min(sp.end, gap[1])))
            if (ov, sp.depth) > key:
                best, key = sp.name, (ov, sp.depth)
    outside = gap[1] - gap[0] - sum(b - a for a, b in trace_reduce.union(held))
    return "other" if outside > key[0] else best


def _device_gaps(trace: trace_reduce.Trace, window) -> Dict[int, list]:
    lo, hi = window
    out = {}
    for d, ops in trace.ops.items():
        live = [(max(a, lo), min(b, hi)) for _, a, b in ops if b > lo and a < hi]
        if live:
            out[d] = trace_reduce.gaps(trace_reduce.union(live), lo, hi)
    return out


def _overlap(gaps: Sequence[Tuple[float, float]],
             intervals: Sequence[Tuple[float, float]]) -> float:
    """ns shared by two lists of disjoint intervals."""
    total, j = 0.0, 0
    for a, b in gaps:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < b:
            total += min(b, intervals[k][1]) - max(a, intervals[k][0])
            k += 1
    return total


def deepest_spans(gap: Tuple[float, float], spans: Sequence[Span],
                  starts: Sequence[float]) -> Dict[str, float]:
    """ns of the gap by the deepest program span open at each instant
    (``other`` where none is)."""
    over = [sp for sp in spans[:bisect.bisect_left(starts, gap[1])]
            if sp.end > gap[0]]
    cuts = sorted({gap[0], gap[1]} | {x for sp in over for x in (sp.start, sp.end)
                                      if gap[0] < x < gap[1]})
    out: Dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        held = [sp for sp in over if sp.start <= mid < sp.end]
        out[max(held, key=lambda sp: sp.depth).name if held else "other"] += b - a
    return out


def idle_by_program_span(trace: trace_reduce.Trace, spans: Sequence[Span],
                         window, top: int = 10) -> dict:
    """Idle seconds per gap label (averaged over the devices that ran
    anything), the ``top`` longest gaps with their labels, and the idle
    time inside the harness's ``tick`` spans split by the deepest program
    span open at each instant, with the share of it that no phase of the
    tick names (``fleet.tick``'s self time, or no program span)."""
    lo, hi = window
    inside = [s for s in spans if s.end > lo and s.start < hi]
    starts = [s.start for s in inside]
    ticks = trace_reduce.union((a, b) for n, a, b in trace.spans if n == "tick")
    by_label, listed = defaultdict(float), []
    in_tick: Dict[str, float] = defaultdict(float)
    dev = _device_gaps(trace, window)
    for gl in dev.values():
        for g in gl:
            label = label_gap(g, inside, starts)
            s = (g[1] - g[0]) * 1e-9
            by_label[label] += s
            listed.append((label, s))
            for a, b in ticks:
                if a < g[1] and b > g[0]:
                    part = (max(a, g[0]), min(b, g[1]))
                    for name, ns in deepest_spans(part, inside, starts).items():
                        in_tick[name] += ns * 1e-9
    nd = max(1, len(dev))
    total = sum(in_tick.values())
    unnamed = in_tick.get("fleet.tick", 0.0) + in_tick.get("other", 0.0)
    return {
        "by_span": {k: v / nd for k, v in
                    sorted(by_label.items(), key=lambda x: -x[1])},
        "idle_gaps": sorted(listed, key=lambda x: -x[1])[:top],
        "tick_idle_s": total / nd,
        "tick_idle_by_deepest": {k: v / nd for k, v in
                                 sorted(in_tick.items(), key=lambda x: -x[1])},
        "tick_idle_unnamed_share": unnamed / total if total else None,
    }


def pump_idle_s(trace: trace_reduce.Trace, spans: Sequence[Span],
                window) -> Optional[float]:
    """Device idle seconds inside ``engine.pump`` spans, averaged over the
    devices that ran anything; None when the trace holds no device ops or
    no pump span."""
    pumps = trace_reduce.union((s.start, s.end) for s in spans
                               if s.name == "engine.pump")
    dev = _device_gaps(trace, window)
    if not dev or not pumps:
        return None
    return sum(_overlap(g, pumps) for g in dev.values()) * 1e-9 / len(dev)


# -- device time by the model's named scopes ---------------------------------
def op_scope(op_name: str) -> str:
    """The innermost of the model's named scopes in an op's metadata name,
    or ``other``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return "other"


def _xspace_type():
    """A message type for the parts of an ``XSpace`` (the profiler's
    ``xplane.proto``) that ``ProfileData`` does not expose: each event
    metadata's stats, where an op's metadata name lives.  Fields keep the
    numbers of ``xplane.proto``; the rest is skipped as unknown."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xspace.proto", package="chipbench", syntax="proto3")

    def message(name, *fields):
        """Fields as (name, number, scalar type or message name); a name
        ending in ``[]`` is repeated."""
        m = fd.message_type.add(name=name)
        for fname, num, typ in fields:
            many = fname.endswith("[]")
            f = m.field.add(name=fname.rstrip("[]"), number=num,
                            label=T.LABEL_REPEATED if many else T.LABEL_OPTIONAL)
            if isinstance(typ, str):
                f.type, f.type_name = T.TYPE_MESSAGE, f".chipbench.{typ}"
            else:
                f.type = typ

    message("Stat", ("metadata_id", 1, T.TYPE_INT64),
            ("str_value", 5, T.TYPE_STRING), ("ref_value", 7, T.TYPE_UINT64))
    message("EventMeta", ("name", 2, T.TYPE_STRING), ("stats[]", 5, "Stat"))
    message("EventMetaEntry", ("key", 1, T.TYPE_INT64),
            ("value", 2, "EventMeta"))
    message("StatMeta", ("name", 2, T.TYPE_STRING))
    message("StatMetaEntry", ("key", 1, T.TYPE_INT64), ("value", 2, "StatMeta"))
    message("Plane", ("name", 2, T.TYPE_STRING),
            ("event_metadata[]", 4, "EventMetaEntry"),
            ("stat_metadata[]", 5, "StatMetaEntry"))
    message("Space", ("planes[]", 1, "Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.Space"))


def op_metadata(path: str) -> Dict[int, Dict[str, Dict[str, str]]]:
    """Per TPU plane: each op's event name -> {stat name: value} of its
    event metadata (string stats, and references to interned strings)."""
    space = _xspace_type()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: Dict[int, Dict[str, Dict[str, str]]] = {}
    for plane in space.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        out[int(m.group(1))] = {
            e.value.name: {names.get(st.metadata_id, ""):
                           st.str_value or names.get(st.ref_value, "")
                           for st in e.value.stats}
            for e in plane.event_metadata}
    return out


def load_scoped_ops(path: str, stats: Sequence[str] = SCOPE_STATS
                    ) -> Tuple[Dict[int, List[Tuple[str, float, float]]],
                               Optional[str]]:
    """Every TPU op as (scope, start, end), and the stat the scopes came
    from: the first of ``stats`` whose values, on the op's event or on its
    event metadata, name a scope anywhere in the trace (None, with every
    scope ``other``, when none does)."""
    from jax.profiler import ProfileData

    meta = op_metadata(path)
    raw: Dict[int, list] = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        d = int(m.group(1))
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                st = dict(meta.get(d, {}).get(e.name, {}))
                st.update((k, str(v)) for k, v in e.stats if k in stats)
                raw[d].append((st, e.start_ns, e.start_ns + e.duration_ns))
    used = next((k for k in stats if any(
        op_scope(st.get(k, "")) != "other"
        for ops in raw.values() for st, _, _ in ops)), None)
    return ({d: [(op_scope(st.get(used, "")) if used else "other", a, b)
                 for st, a, b in ops] for d, ops in raw.items()}, used)


def device_by_scope(scoped: Dict[int, List[Tuple[str, float, float]]],
                    modules: Dict[int, List[Tuple[str, float, float]]],
                    window, programs: Sequence[str] = STEP_PROGRAMS
                    ) -> Dict[str, Dict[str, float]]:
    """Seconds of op self-time per named scope inside each step program's
    executions, averaged over devices: {program: {scope: seconds}}."""
    lo, hi = window
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for d, ops in scoped.items():
        execs = sorted((a, b, trace_reduce.program_name(n))
                       for n, a, b in modules.get(d, []) if b > lo and a < hi)
        starts = [a for a, _, _ in execs]
        ops = sorted(((s, max(a, lo), min(b, hi)) for s, a, b in ops
                      if b > lo and a < hi), key=lambda o: (o[1], -o[2]))
        for scope, a, b, self_ns in _self_times(ops):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and a < execs[i][1] and execs[i][2] in programs:
                out[execs[i][2]][scope] += self_ns * 1e-9
    nd = max(1, len(scoped))
    return {p: {s: v / nd for s, v in sorted(sc.items(), key=lambda x: -x[1])}
            for p, sc in sorted(out.items())}


def _self_times(ops):
    """(scope, start, end, self ns) of each op, less the ops nested in it
    (``ops`` sorted by start, longest first)."""
    out, stack = [], []
    for scope, a, b in ops:
        while stack and stack[-1][2] <= a:
            out.append(tuple(stack.pop()))
        if stack and b <= stack[-1][2]:
            stack[-1][3] -= b - a
        stack.append([scope, a, b, b - a])
    out += [tuple(x) for x in stack]
    return out


# -- readings from the tracer's events ---------------------------------------
def first_stamps(events: Iterable[dict], name: str) -> Dict[int, float]:
    """rid -> wall stamp ``w`` of the first ``name`` event naming it."""
    out: Dict[int, float] = {}
    for ev in events:
        if ev.get("name") == name and "rid" in ev and "w" in ev:
            out.setdefault(int(ev["rid"]), float(ev["w"]))
    return out


def ttft_split(events: Sequence[dict], served: dict, close: float
               ) -> Optional[Dict[str, float]]:
    """Means over the requests with a first token at the client by
    ``close`` and both program stamps: intake wait (due to ``req.queued``),
    slot wait (``req.queued`` to ``req.admitted``) and ingest
    (``req.admitted`` to the first token at the client), in ms, beside
    their sum's mean (``ttft_ms``) and the count.  None when no request
    has all three stamps (a program without ``req.admitted``)."""
    queued = first_stamps(events, "req.queued")
    admitted = first_stamps(events, "req.admitted")
    parts = []
    for rid, s in served.items():
        if not s.stamps or s.stamps[0] > close:
            continue
        if rid in queued and rid in admitted:
            parts.append((queued[rid] - s.due, admitted[rid] - queued[rid],
                          s.stamps[0] - admitted[rid]))
    if not parts:
        return None
    m = np.mean(np.asarray(parts), axis=0) * 1e3
    return {"n": len(parts), "intake_wait_ms": float(m[0]),
            "slot_wait_ms": float(m[1]), "ingest_ms": float(m[2]),
            "ttft_ms": float(m.sum())}


def tick_ctl_ms(events: Sequence[dict], open_: float, close: float
                ) -> Optional[float]:
    """Mean over the ``fleet.tick`` spans inside [open_, close] of their
    wall time less that of the ``engine.pump`` spans they hold, in ms."""
    ticks = sorted((e["w"], e["w"] + e["wall_s"]) for e in events
                   if e.get("name") == "fleet.tick" and "wall_s" in e
                   and e["w"] >= open_ and e["w"] + e["wall_s"] <= close)
    if not ticks:
        return None
    pump = [0.0] * len(ticks)
    starts = [a for a, _ in ticks]
    for e in events:
        if e.get("name") == "engine.pump" and e.get("parent") == "fleet.tick":
            i = bisect.bisect_right(starts, e["w"]) - 1
            if i >= 0 and e["w"] + e["wall_s"] <= ticks[i][1]:
                pump[i] += e["wall_s"]
    return float(np.mean([(b - a - p) * 1e3
                          for (a, b), p in zip(ticks, pump)]))


def span_counts(events: Sequence[dict], open_: float, close: float
                ) -> Dict[str, int]:
    """Events and spans (events with ``wall_s``) recorded in the window."""
    inside = [e for e in events if open_ <= e.get("w", -1.0) <= close]
    return {"events": len(inside),
            "spans": sum(1 for e in inside if "wall_s" in e and "dur" in e)}
