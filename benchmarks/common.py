"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

import jax


Row = Tuple[str, float, str]   # (name, us_per_call, derived)


def time_us(fn: Callable, *args, iters: int = 20, warmup: int = 3) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def fmt_rows(rows: List[Row]) -> str:
    return "\n".join(f"{n},{us:.1f},{d}" for n, us, d in rows)
