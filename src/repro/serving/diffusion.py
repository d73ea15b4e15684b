"""Diffusion-style job engine: the paper's sd21 deployment units, served.

The source paper's Table-1 workload is Stable Diffusion 2.1 — seconds-long,
highly batchable, non-streaming *jobs*, not token streams.  This module
serves that request class behind the SAME surface the fleet already
speaks: ``DiffusionEngine.new_session()`` returns a
``DiffusionSession`` that duck-types ``QueueSession``'s
``CacheBackend``/pump interface (``submit`` / ``pump`` / ``cancel`` /
``fits`` / ``load`` / ``inflight_rids``), so ``Replica``, the dispatcher,
the fleet runtime, and the streaming ``RequestHandle`` API all work
unchanged.

The "model" is a deterministic latent denoiser, not a UNet: each job owns
one (D, D) latent seeded from its prompt tokens, and every pump advances
all active jobs ``steps_per_pump`` denoising steps in ONE jitted
``lax.scan`` dispatch (per-slot step masking, so a slot's trajectory
depends only on its own latent + conditioning — admission order and batch
composition never change a job's output).  A finished job emits its
result as one non-streaming burst of ``max_new`` digest tokens, a
deterministic quantization of the final latent — byte-identical across
replicas, retries, and batch shapes, which is what lets the fleet's
requeue-and-retry machinery apply to jobs unchanged.

What jobs do NOT have: KV caches, prefix reuse, frontiers (a half-denoised
latent is cheaper to restart than to externalize at these step counts),
mixed-batch prefill, or speculation.  ``DiffusionSession`` reports all of
those capabilities absent and the fleet routes around them.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs.trace import Tracer
from repro.serving.engine import PumpReport, traced_pump


@dataclass
class DiffusionConfig:
    """Shape of one sd21-style job engine (per-tier, like ``EngineConfig``)."""

    batch: int = 8                 # concurrent job slots per replica
    denoise_steps: int = 20        # total denoising steps per job
    steps_per_pump: int = 5        # steps advanced per pump — a job spans
                                   # ceil(denoise_steps/steps_per_pump) pumps,
                                   # which is what makes it "seconds-long" in
                                   # fleet ticks rather than instant
    latent_dim: int = 16           # latent is (latent_dim, latent_dim)
    max_len: int = 4096            # prompt + digest-token bound (API compat)
    seed: int = 0


class DiffusionEngine:
    """Tier-shared compiled denoiser; replicas get isolated sessions."""

    is_job_engine = True

    def __init__(self, cfg: DiffusionConfig):
        self.cfg = cfg
        self.paged = False
        self.mixed = False
        D = cfg.latent_dim
        key = jax.random.key(cfg.seed)
        k1, k2 = jax.random.split(key)
        # fixed mixing weights: the stand-in denoiser's "parameters"
        self.w_mix = jax.random.normal(k1, (D, D)) / math.sqrt(D)
        self.w_cond = jax.random.normal(k2, (D,)) / math.sqrt(D)
        self._steps = jax.jit(self._denoise_scan, static_argnums=(3,),
                              donate_argnums=(0,))
        self._place = jax.jit(self._place_fn, donate_argnums=(0, 1))

    def new_session(self) -> "DiffusionSession":
        return DiffusionSession(self)

    # -- jitted bodies --------------------------------------------------------
    def _denoise_scan(self, lat, cond, rem, steps: int):
        """Advance every slot with remaining steps by up to ``steps``
        denoising iterations.  ``lat``: (B, D, D); ``cond``: (B, D);
        ``rem``: (B,) i32 remaining steps.  Slots at rem=0 are frozen, so a
        slot admitted mid-flight never overshoots its step budget and its
        trajectory is independent of its batchmates."""

        def step(carry, _):
            lat, rem = carry
            upd = rem > 0
            eps = jnp.tanh(
                lat @ self.w_mix
                + cond[:, None, :] * self.w_cond[None, None, :]
            )
            lat = jnp.where(upd[:, None, None], lat - 0.1 * eps, lat)
            rem = jnp.maximum(rem - upd.astype(jnp.int32), 0)
            return (lat, rem), ()

        (lat, rem), _ = lax.scan(step, (lat, rem), None, length=steps)
        return lat, rem

    def _place_fn(self, lat, cond, l0, c0, s):
        lat = lax.dynamic_update_slice(lat, l0[None], (s, 0, 0))
        cond = lax.dynamic_update_slice(cond, c0[None], (s, 0))
        return lat, cond

    # -- deterministic job setup / readout ------------------------------------
    def seed_job(self, prompt: np.ndarray) -> Tuple[jax.Array, jax.Array]:
        """(initial latent (D, D), conditioning (D,)) for a prompt — a pure
        function of the prompt tokens and the engine seed, so the digest a
        job produces is replica- and retry-independent."""
        key = jax.random.key(self.cfg.seed)
        for t in np.asarray(prompt).ravel():
            key = jax.random.fold_in(key, int(t) & 0x7FFFFFFF)
        D = self.cfg.latent_dim
        lat0 = jax.random.normal(jax.random.fold_in(key, 0), (D, D))
        cond = jax.random.normal(jax.random.fold_in(key, 1), (D,))
        return lat0, cond

    def digest(self, lat_row: np.ndarray, max_new: int) -> np.ndarray:
        """Quantize a finished latent into ``max_new`` int tokens — the
        job's non-streaming "output".  Tiles when max_new exceeds the
        latent size; deterministic given the latent."""
        flat = np.asarray(lat_row, np.float64).ravel()
        reps = -(-max_new // flat.size)
        flat = np.tile(flat, reps)[:max_new]
        return (np.floor(np.abs(flat) * 1e6).astype(np.int64)) % 65536

    def warm(self) -> None:
        """Compile the denoise scan and placement outside measured pumps."""
        sess = self.new_session()
        sess.submit(-1, np.zeros((1, 4), np.int64), 2)
        while not sess.idle:
            sess.pump()


class DiffusionSession:
    """One replica's job slots: the ``QueueSession`` duck type for jobs.

    Satisfies ``serving.backends.CacheBackend`` with every capability
    reported absent: no pages, no prefixes, no frontiers — a killed job
    simply requeues and re-denoises from its deterministic seed.
    """

    def __init__(self, engine: DiffusionEngine):
        self.eng = engine
        cfg = engine.cfg
        B, D = cfg.batch, cfg.latent_dim
        self.paged = False
        self.scan_state = False
        self.mixed = False
        self.allocator = None
        # live-knob surface the fleet pokes on every session type; both are
        # inert here (jobs have no prefill budget and nothing to speculate)
        self.token_budget = 1
        self.spec_k = 0
        self.spec_accept_ewma: Optional[float] = None
        self.lat = jnp.zeros((B, D, D), jnp.float32)
        self.cond = jnp.zeros((B, D), jnp.float32)
        self._rid = np.full((B,), -1, np.int64)       # slot -> rid (-1 free)
        self._rem = np.zeros((B,), np.int64)          # host mirror of steps left
        self._max_new = {}                            # rid -> digest length
        self.queue: List[Tuple[int, np.ndarray, int]] = []
        self.results: Dict[int, np.ndarray] = {}
        self._instant: List[int] = []
        self._slo: Dict[int, Tuple[int, int, float, int]] = {}
        self._seq = 0
        # flight recorder, handed over by the owning replica (as on
        # QueueSession)
        self.tracer: Tracer = Tracer.disabled()
        self.trace_tags: Dict[str, object] = {}

    # -- request intake -------------------------------------------------------
    def submit(self, rid: int, inp: np.ndarray, max_new: int, *,
               slo_class: str = "job", priority: int = 0,
               deadline_s: Optional[float] = None,
               recompute: bool = False, frontier=None,
               speculate: bool = True) -> None:
        """Queue a job.  ``frontier``/``recompute``/``speculate`` are
        accepted for interface parity and ignored — jobs restart from
        their deterministic seed on retry."""
        del recompute, frontier, speculate
        if rid in self.results or rid in self._max_new or any(
                q[0] == rid for q in self.queue):
            raise ValueError(f"request id {rid} already in session")
        inp = np.asarray(inp)
        max_new = int(max_new)
        if max_new <= 0:
            self.results[rid] = np.asarray([], np.int64)
            self._instant.append(rid)
            return
        if inp.shape[1] + max_new > self.eng.cfg.max_len:
            raise ValueError(
                f"request {rid}: prompt_len={inp.shape[1]} + "
                f"max_new={max_new} exceeds max_len={self.eng.cfg.max_len}"
            )
        from repro.serving.api import slo_order_key

        deadline_at = (time.monotonic() + deadline_s
                       if deadline_s is not None else math.inf)
        self._slo[rid] = slo_order_key(slo_class, priority, deadline_at,
                                       self._seq)
        self._seq += 1
        self.queue.append((rid, inp, max_new))

    def cancel(self, rid: int) -> bool:
        if rid in self.results:
            return False
        before = len(self.queue)
        self.queue = [q for q in self.queue if q[0] != rid]
        hit = len(self.queue) < before
        for s in np.nonzero(self._rid == rid)[0]:
            self._rid[s] = -1
            self._rem[s] = 0
            hit = True
        self._max_new.pop(rid, None)
        self._slo.pop(rid, None)
        return hit

    # -- CacheBackend surface -------------------------------------------------
    def fits(self, prompt_len: int, max_new: int) -> bool:
        return prompt_len + max_new <= self.eng.cfg.max_len

    def prefix_match_len(self, prompt) -> int:
        return 0

    @property
    def supports_frontiers(self) -> bool:
        return False

    def extract_frontier(self, rid: int):
        return None

    def extract_frontiers(self) -> List:
        return []

    def decoding_lens(self) -> Dict[int, int]:
        return {}

    # -- introspection --------------------------------------------------------
    @property
    def idle(self) -> bool:
        return (not self.queue and not self._instant
                and not np.any(self._rid >= 0))

    @property
    def load(self) -> int:
        return len(self.queue) + int(np.sum(self._rid >= 0))

    def inflight_rids(self) -> List[int]:
        active = [int(r) for r in self._rid if r >= 0]
        return active + [rid for rid, _, _ in self.queue]

    # -- the loop body --------------------------------------------------------
    def _pop_next(self) -> Tuple[int, np.ndarray, int]:
        best = min(range(len(self.queue)),
                   key=lambda i: self._slo[self.queue[i][0]])
        return self.queue.pop(best)

    def pump(self) -> PumpReport:
        """One job cycle: admit into free slots, then ONE jitted dispatch
        advancing every active job ``steps_per_pump`` denoising steps.
        Jobs whose step budget hits zero complete, emitting their whole
        digest in this report (non-streaming).  Traced like the token
        engine's pump: ``engine.pump`` around ``pump.admit``,
        ``pump.decode`` (the denoising dispatch) and ``pump.decode_sync``."""
        return traced_pump(self.tracer, self.trace_tags, self._pump_jobs)

    def _phase(self, name: str):
        return self.tracer.begin(name, cat="engine", sampled=True)

    def _pump_jobs(self) -> PumpReport:
        eng, cfg = self.eng, self.eng.cfg
        report = PumpReport()
        with self._phase("pump.admit") as sp:
            for rid in self._instant:
                report.completed[rid] = self.results[rid]
            self._instant = []
            for s in np.nonzero(self._rid < 0)[0]:
                if not self.queue:
                    break
                rid, inp, max_new = self._pop_next()
                lat0, cond = eng.seed_job(inp)
                self.lat, self.cond = eng._place(
                    self.lat, self.cond, lat0, cond, jnp.int32(int(s))
                )
                self._rid[s] = rid
                self._rem[s] = cfg.denoise_steps
                self._max_new[rid] = max_new
                report.admitted.append(rid)
        report.admit_s = sp.wall_s
        for rid in report.admitted:
            self.tracer.event("req.admitted", cat="req", rid=rid,
                              **self.trace_tags)

        active = self._rid >= 0
        report.occupancy = float(np.mean(active))
        if not np.any(active):
            return report

        with self._phase("pump.decode") as sp:
            self.lat, rem = eng._steps(
                self.lat, self.cond, jnp.asarray(self._rem, jnp.int32),
                cfg.steps_per_pump,
            )
        report.dispatch_s = sp.wall_s
        with self._phase("pump.decode_sync") as sp:
            self._rem = np.asarray(rem, np.int64)
            done = np.nonzero(active & (self._rem == 0))[0]
            if done.size:
                lat_host = np.asarray(self.lat[jnp.asarray(done)])
                for j, s in enumerate(done):
                    rid = int(self._rid[s])
                    toks = eng.digest(lat_host[j], self._max_new[rid])
                    self.results[rid] = toks
                    report.completed[rid] = toks
                    report.tokens[rid] = [int(v) for v in toks]
                    report.emitted[rid] = int(toks.size)
                    report.useful_tokens += int(toks.size)
                    self._rid[s] = -1
                    self._max_new.pop(rid, None)
                    self._slo.pop(rid, None)
        report.sync_s = sp.wall_s
        return report


__all__ = ["DiffusionConfig", "DiffusionEngine", "DiffusionSession"]
