"""The one traffic generator: turns a mix's parameters
(``traffic/<mix>.json``) and ``--seed`` into requests.

The requests come in blocks of ``block``.  Every block holds the same
requests: ``block`` stratified quantiles of the prompt and output length
distributions, paired by a fixed shuffle, and (open loop) the same
``block`` gaps between arrivals.  The seed draws only the order within
each block and the token ids.  So two seeds ask the same work of the
system, and so does every run of whole blocks from the start: a window
that serves only the first part of a long backlog still sees the mix.

Mix parameters:
  arrivals   "poisson": open loop, ``rate_rps`` requests a second, gaps
             exponential, as many whole blocks as the window holds;
             "at_start": ``count`` requests all due at the window's start
             (an offline batch).
  prompt, output  lognormal lengths: ``median``, ``sigma``, clipped to
             [``min``, ``max``].
  block      requests a block.
Token ids are uniform over the vocabulary, so no two prompts share a
prefix.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, NamedTuple

import numpy as np


class Request(NamedTuple):
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # (prompt_len,) int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` stratified gaps of a Poisson stream at ``rate_rps``."""
    return -np.log1p(-_quantiles(n)) / mix["rate_rps"]


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Request]:
    """The requests due in a window of ``seconds``, in due order."""
    b = int(mix["block"])
    if mix["arrivals"] == "at_start":
        n = int(mix["count"])
        b = min(b, n)
    elif mix["arrivals"] == "poisson":
        gaps = exp_gaps(mix, b)
        n = b * max(1, int(seconds // gaps.sum()))
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    prompts = lognormal_lengths(mix["prompt"], b)
    outputs = lognormal_lengths(mix["output"], b)[
        np.random.default_rng(0xB10C).permutation(b)]
    if int(np.max(prompts + outputs)) > max_len:
        raise ValueError(f"mix allows {int(np.max(prompts + outputs))} "
                         f"tokens, above max_len {max_len}")
    rng = np.random.default_rng([seed, 0x7A11C])
    blocks = -(-n // b)
    order = np.concatenate([rng.permutation(b) for _ in range(blocks)])[:n]
    if mix["arrivals"] == "at_start":
        due = np.zeros(n)
    else:
        due = np.cumsum(np.concatenate(
            [rng.permutation(gaps) for _ in range(blocks)]))
    out = []
    for i in range(n):
        if due[i] >= seconds:
            break
        ids = rng.integers(0, vocab, size=int(prompts[order[i]]),
                           dtype=np.int32)
        out.append(Request(float(due[i]), ids, int(outputs[order[i]])))
    return out
