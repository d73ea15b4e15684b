"""What one measured window leaves behind, in the form the metric readers
(``metrics/<name>.py``) take it.

The harness fills a ``Window`` from its own clock (due times, tick spans,
every token as the client received it), from the program's tracer events
(``engine.pump``, ``req.dispatched``, ``req.admitted``), from the
program's counters at the window's open and at the loop's end and, in a
traced run, from the reduced device trace.  Times are
``time.perf_counter`` seconds.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass
class Served:
    """One request as the client saw it."""

    due: float                      # when it was due (wall clock)
    prompt_len: int
    max_new: int
    stamps: List[float] = field(default_factory=list)   # one per token
    tokens: List[int] = field(default_factory=list)
    replica: str = ""
    done: Optional[float] = None    # completion handed to the client
    dropped: str = ""               # reason, when the fleet dropped it


@dataclass
class Delivery:
    """Tokens one replica handed over for one request in one tick."""

    tick: int
    rid: int
    first: int                      # index of the first token delivered
    n: int


@dataclass
class Tick:
    t: float                        # the runtime's control-loop time
    start: float
    end: float


@dataclass
class Pump:
    t: float                        # control-loop time of its tick
    wall_s: float
    occupancy: float


@dataclass
class Window:
    seconds: float
    open: float
    close: float                    # open + seconds
    shapes: Any                     # the architecture's work model (work.py)
    decode_chunk: int
    setup_s: float = 0.0            # process start to the window's open
    peak_flops: float = 0.0
    peak_bytes_s: float = 0.0
    served: Dict[int, Served] = field(default_factory=dict)
    deliveries: List[Delivery] = field(default_factory=list)
    ticks: List[Tick] = field(default_factory=list)
    pumps: List[Pump] = field(default_factory=list)
    dispatched_t: Dict[int, float] = field(default_factory=dict)
    admitted_prompt_tokens: int = 0  # prompts given a slot in the ticks
    counters: Dict[str, int] = field(default_factory=dict)  # run.program_counters
    trace: Optional[dict] = None    # trace_reduce.reduce(), traced runs only
    trace_end: float = 0.0          # end of the traced span (the loop's end)

    # -- helpers shared by the readers --------------------------------------
    def tick_end(self, t: float) -> Optional[float]:
        for tk in self.ticks:
            if tk.t == t:
                return tk.end
        return None

    def in_window_ticks(self) -> List[Tick]:
        return [tk for tk in self.ticks if tk.end <= self.close]

    def program(self, names: Tuple[str, ...]) -> Tuple[float, float]:
        """(executions, device seconds) of the named jitted programs in the
        trace; (0, 0) when none ran or no trace was taken."""
        if not self.trace:
            return 0.0, 0.0
        progs = self.trace["programs"]
        return (sum(progs[n]["count"] for n in names if n in progs),
                sum(progs[n]["seconds"] for n in names if n in progs))

    def decode_contexts(self, upto: float) -> List[int]:
        """Live context of each token a decode chunk scan produced in the
        ticks that ended by ``upto``: of what a replica delivered for a
        request in one tick, the last ``decode_chunk`` tokens come from the
        chunk scan that closes the pump (the rest from the mixed steps)."""
        ends = {i: tk.end for i, tk in enumerate(self.ticks)}
        out = []
        for d in self.deliveries:
            if ends.get(d.tick, upto + 1) > upto:
                continue
            m = min(self.decode_chunk, d.n)
            plen = self.served[d.rid].prompt_len
            out += [plen + k for k in range(d.first + d.n - m, d.first + d.n)
                    if k >= 1]
        return out

    def processed_contexts(self, upto: float) -> List[int]:
        """Context of every token the model ran in ticks that ended by
        ``upto``: the prompt of each request whose first token came in them,
        and every later token delivered in them."""
        ends = {i: tk.end for i, tk in enumerate(self.ticks)}
        out = []
        for d in self.deliveries:
            if ends.get(d.tick, upto + 1) > upto:
                continue
            plen = self.served[d.rid].prompt_len
            if d.first == 0:
                out += range(1, plen + 1)
            out += [plen + k for k in range(max(d.first, 1), d.first + d.n)]
        return out
