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
             (an offline batch);
             "sessions": arrivals as "poisson", each request a turn of a
             chat session, below.
  prompt, output  lognormal lengths: ``median``, ``sigma``, clipped to
             [``min``, ``max``].
  block      requests a block.
Token ids are uniform over the vocabulary, so no two prompts of
"poisson" or "at_start" share a prefix.

"sessions": every request is turn ``j`` of its own chat session, whose
earlier turns came before the window.  Sessions have a geometric number
of turns with mean ``turns_mean`` (after each answer a user goes on with
probability 1 - 1/``turns_mean``), so a request is turn ``j`` with
probability (1/m)(1 - 1/m)**(j-1), m = ``turns_mean``; the block holds
these shares as stratified quantiles.  Turn ``j``'s prompt is every
earlier turn's user text (``prompt`` lengths) and answer (``output``
lengths), then its own user text; its ``max_new`` is one more
``output`` length.  A request whose prompt would pass ``max_prompt``
tokens is the latest turn of its session that fits.  ``Request.earlier``
is the length of the previous turn's prompt, which the harness serves in
set-up, so the window starts with the cache a steady stream of sessions
leaves: each session's previous prompt published, subject to the pages
the cache has.  The answers inside a history are seeded tokens, not
served ones; the program publishes prompt pages only, so a served answer
would not be in its cache either.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import List, NamedTuple, Tuple

import numpy as np


class Request(NamedTuple):
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # (prompt_len,) int32 token ids
    max_new: int
    earlier: int = 0      # prompt[:earlier]: the previous turn's prompt


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_gaps(mix: dict, n: int) -> np.ndarray:
    """``n`` stratified gaps of a Poisson stream at ``rate_rps``."""
    return -np.log1p(-_quantiles(n)) / mix["rate_rps"]


def session_block(mix: dict) -> List[Tuple[Tuple[int, ...], int]]:
    """The requests every block of a "sessions" mix holds: (the lengths of
    a prompt's parts -- user text, answer, ..., user text --, max_new).
    Turns, lengths and their pairing are stratified quantiles and fixed
    shuffles; the seed plays no part."""
    b = int(mix["block"])
    q = 1.0 - 1.0 / float(mix["turns_mean"])
    turns = 1 + np.floor(np.log1p(-_quantiles(b)) / math.log(q) + 1e-9
                         ).astype(np.int64)
    r = int(turns.sum())
    users = lognormal_lengths(mix["prompt"], r)[
        np.random.default_rng(0x5E55).permutation(r)]
    answers = lognormal_lengths(mix["output"], r)[
        np.random.default_rng(0xB10C).permutation(r)]
    out, k = [], 0
    for t in turns:
        parts = [int(users[k])]
        for j in range(1, t):
            more = [int(answers[k + j - 1]), int(users[k + j])]
            if sum(parts) + sum(more) > int(mix["max_prompt"]):
                break
            parts += more
        if parts[0] > int(mix["max_prompt"]):
            raise ValueError("a session's first prompt passes max_prompt")
        out.append((tuple(parts), int(answers[k + len(parts) // 2])))
        k += int(t)
    return out


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             max_len: int) -> List[Request]:
    """The requests due in a window of ``seconds``, in due order."""
    b = int(mix["block"])
    if mix["arrivals"] == "at_start":
        n = int(mix["count"])
        b = min(b, n)
    elif mix["arrivals"] in ("poisson", "sessions"):
        gaps = exp_gaps(mix, b)
        n = b * max(1, int(seconds // gaps.sum()))
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    if mix["arrivals"] == "sessions":
        items = session_block(mix)
    else:
        prompts = lognormal_lengths(mix["prompt"], b)
        outputs = lognormal_lengths(mix["output"], b)[
            np.random.default_rng(0xB10C).permutation(b)]
        items = [((int(p),), int(o)) for p, o in zip(prompts, outputs)]
    longest = max(sum(parts) + o for parts, o in items)
    if longest > max_len:
        raise ValueError(f"mix allows {longest} tokens, above max_len {max_len}")
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
        parts, max_new = items[order[i]]
        ids = rng.integers(0, vocab, size=sum(parts), dtype=np.int32)
        out.append(Request(float(due[i]), ids, max_new, sum(parts[:-2])))
    return out
