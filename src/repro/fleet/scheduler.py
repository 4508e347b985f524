"""Continuous-batching scheduler: variable-length requests -> fixed-shape
decode slots -> fused wave dispatches or token-granular slot splicing.

Serving traffic arrives as requests of arbitrary prompt length and token
budget; the compiled fast path (the PR-2 fused ``lax.scan`` decode, now
adaptive and mesh-shardable) wants **fixed shapes**.  The
:class:`ContinuousBatcher` bridges the two:

* requests queue per **prompt bucket**; prompts right-pad to the bucket
  length and prefill runs **pad-masked** (``prompt_lens``): the models'
  attention carries a pad-mask input, so a padded prompt attends only to
  its real tokens and generates bit-identically to the same prompt served
  unpadded (bucket granularity now costs only wasted compute, never wrong
  conditioning).  Pad-masking needs a full-attention stack — ring caches
  and recurrent/ssm state would absorb the pad tail — so other families
  keep the PR-3 repeat-pad wave behavior;
* **wave mode** (the default, and the bit-exactness oracle): each wave
  admits up to ``n_slots`` requests FIFO from the oldest bucket, backfills
  remaining slots with the next FIFO requests from *other* buckets whose
  prompts fit (their outputs are kept and counted — idle slots no longer
  cycle already-admitted prompts), and runs ONE fused adaptive dispatch of
  ``new_token_bucket`` steps with per-slot positions and per-slot token
  budgets (a slot that exhausts its budget retires in place);
* **token mode** (``BatcherConfig.token_granular``): slots retire and admit
  *mid-flight*.  Decode runs one compiled per-step program
  (``serve.engine.token_step``) over the slot batch with per-slot cache
  positions; when a slot finishes, the next FIFO request is prefilled into
  that slot's cache region (``serve.engine.prefill_one`` +
  ``splice_slot``) and spliced into the running batch at the next step
  boundary — no recompiles, no desync of the other slots.  Same prompts,
  same seeds => per-request tokens bit-identical to the wave oracle
  (greedy AND temperature sampling; tested);
* **EOS retirement** (``BatcherConfig.eos_id``): a slot frees the moment
  its request samples the EOS token — in token mode the slot retires and
  refills at the next step boundary; in wave mode the engine's per-slot
  done-flags freeze the slot on device and the host truncates the output
  row at the first EOS (the EOS token is kept as the last token;
  ``Completion.finish == "eos"``);
* **per-request RNG streams**: every request samples from its own stream
  keyed on (request seed, token index) — ``serve.engine.slot_sample`` —
  so temperature sampling is splice-invariant and token mode stays
  bit-identical to the wave oracle at any temperature
  (``Request.seed``, or a deterministic rid-derived seed);
* **arrival mode** (:meth:`ContinuousBatcher.run_arrivals`): requests are
  submitted when their trace timestamps come due (``ArrivalSource`` /
  :func:`poisson_arrivals`), queueing delay is measured submit->admission,
  empty slots re-admit as arrivals land, and the loop sleeps instead of
  dispatching zero-active steps;
* **async admission** (``BatcherConfig.async_admission``, token mode): a
  freed slot's next prefill is *dispatched* immediately but spliced at
  the next step boundary, so the prefill computes concurrently with the
  in-flight decode instead of stalling the step loop;
* every compiled program is keyed on shape classes exactly as before (one
  prefill per prompt bucket, one decode program for the shared
  ``max_cache_len``); policy re-tunes and ``PolicyReader`` syncs change
  traced int32 values only — later waves, spliced admissions and adopted
  policies all reuse the same programs.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig, ParallelConfig
from repro.quant.ax import prepare_params, prepared_projections
from repro.serve import ServeConfig, generate
from repro.serve.engine import prefill_one, splice_slot_jit, token_step
from repro.train.fault import StragglerWatchdog

from . import chaos

__all__ = ["Request", "Completion", "BatcherConfig", "ContinuousBatcher",
           "ArrivalSource", "poisson_arrivals"]

# host-side observability (repro.obs; see docs/observability.md).  TTFT in
# wave mode equals e2e at *wave-landing* granularity: the whole wave is one
# fused dispatch, so even a slot whose EOS done-flag froze it early only
# materializes its tokens when the wave's scan returns — the equality is a
# measurement artifact of the dispatch shape, not a claim that the first
# token took as long as the last.  Token mode reports the real first-token
# latency, measured when the admission's first token reaches the host.
# All instrumentation sits outside traced code.
_REG = obs.default_registry()
_OCCUPANCY = _REG.gauge(
    "repro_batcher_occupancy",
    "useful-token fraction of all decode-slot token positions (by mode)")
_QUEUE_DEPTH = _REG.gauge(
    "repro_queue_depth", "waiting requests per prompt bucket")
_ADMISSIONS = _REG.counter(
    "repro_admissions_total", "requests admitted into decode slots (by mode)")
_BACKFILLS = _REG.counter(
    "repro_backfills_total",
    "wave-mode idle slots backfilled from other buckets' FIFO heads")
_SPLICES = _REG.counter(
    "repro_splices_total",
    "token-mode mid-flight admissions spliced into a live batch")
_TTFT = _REG.histogram(
    "repro_request_ttft_seconds",
    "submit -> first token (wave mode: == e2e at wave-LANDING granularity "
    "— EOS may free the slot's compute earlier but tokens only materialize "
    "when the fused wave returns)",
    buckets=obs.TTFT_BUCKETS)
_E2E = _REG.histogram(
    "repro_request_e2e_seconds", "submit -> request retirement (by mode)",
    buckets=obs.E2E_BUCKETS)
_STEP_WALL = _REG.histogram(
    "repro_token_step_seconds",
    "host wall per token-granular decode step: dispatch -> its tokens on "
    "the host",
    buckets=obs.DISPATCH_BUCKETS)
_TOKENS_PER_S = _REG.gauge(
    "repro_decode_tokens_per_second",
    "real (non-pad, non-filler) tokens per wall second over the last drain")
_POST_WARMUP_RETRACES = _REG.gauge(
    "repro_decode_retraces_post_warmup",
    "token_step program installs after the first decode step of a drain — "
    "the live zero-recompile invariant (asserted 0; splices and policy "
    "updates must never retrace)")
_AX_PREPARED = _REG.gauge(
    "repro_ax_prepared_projections",
    "approximated projections the batcher's steps read from weights "
    "prepared at load (quant.ax.prepare_params); 0 = every call quantizes "
    "its weight")
_SHED = _REG.counter(
    "repro_requests_shed_total",
    "admissions refused because the bounded queue was full (load-shedding)")
_TIMEOUTS = _REG.counter(
    "repro_request_timeouts_total",
    "requests retired past their deadline_s (by where: queued / decoding)")
_STRAGGLERS = _REG.counter(
    "repro_step_stragglers_total",
    "decode steps/waves flagged slow by the straggler watchdog")


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray          # (L,) int32 prompt
    max_new: int
    # optional SLO: seconds from submit after which the request is retired
    # as a `timeout` completion instead of (or mid-) decoding.  None = no
    # deadline (the default keeps every existing call site byte-identical).
    deadline_s: Optional[float] = None
    # per-request sampling seed (serve.engine.slot_sample).  None derives a
    # deterministic seed from (BatcherConfig.seed, rid) — stable across
    # arrival order, slot placement and serving mode, so wave and token
    # mode sample identical streams.
    seed: Optional[int] = None


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray          # (<= max_new,) int32 generated
    wave: int                   # wave index (wave mode) / retire step (token)
    prompt_len: int
    bucket: int
    status: str = "ok"          # "ok" | "timeout" (partial/empty tokens)
    # why decoding stopped: "length" (budget exhausted), "eos" (sampled the
    # EOS token — kept as the last token), "timeout" (deadline)
    finish: str = "length"
    # correlation id assigned at submit — unique across splices/backfills
    # and across drains even when rids recur (qor attribution + trace key)
    corr: Optional[str] = None
    # per-request QoR attribution summary (obs.qor.ErrorAttributor.finish):
    # per-target/per-tile ew-MAE, error shares, top-k contributors.  Token
    # mode with an adaptive controller only; None in wave mode (the wave
    # oracle stays uninstrumented) and when telemetry is off.
    qor: Optional[dict] = None


@dataclasses.dataclass
class BatcherConfig:
    n_slots: int = 8                       # fixed decode batch (mesh-divisible)
    prompt_buckets: Sequence[int] = (16, 32, 64)
    new_token_bucket: int = 16             # fused scan length per wave
    observe_every: int = 1                 # telemetry decimation inside the scan
    temperature: float = 0.0
    seed: int = 0
    token_granular: bool = False           # mid-flight slot splicing
    # admission control: refuse (shed) submits once this many requests wait;
    # None = unbounded (the pre-hardening behavior)
    max_queue: Optional[int] = None
    straggler_factor: float = 3.0          # per-step watchdog (train/fault)
    # EOS-triggered early retirement: a slot frees the moment it samples
    # this token (None = budget-only retirement, the pre-PR9 behavior).
    # Requires the pad-mask (full-attention) stack, like token mode.
    eos_id: Optional[int] = None
    # token mode: dispatch a freed slot's next prefill immediately but
    # splice it at the NEXT step boundary, overlapping the prefill with the
    # in-flight decode (False = dispatch-and-splice synchronously, the
    # oracle-faithful default)
    async_admission: bool = False


class ContinuousBatcher:
    """Admission + execution over the fused adaptive decode (wave mode) or
    the per-step token-granular decode (``BatcherConfig.token_granular``).

    ``adaptive`` is either the fleet's re-tuning
    :class:`~repro.runtime.AdaptiveController` (the single store writer) or a
    replica-side :class:`~repro.fleet.store.PolicyReader` (synced before each
    wave / admission); ``None`` serves the static policy (single-host only:
    the engine's sharded path is the adaptive one, so ``mesh`` requires
    ``adaptive``).  ``mesh`` shards the decode slots over the mesh batch
    axes.
    """

    def __init__(self, params, cfg: ModelConfig, bcfg: Optional[BatcherConfig] = None,
                 adaptive=None, mesh=None, par: Optional[ParallelConfig] = None):
        assert mesh is None or adaptive is not None, (
            "ContinuousBatcher: mesh= requires an adaptive controller/reader "
            "(the sharded decode program is the adaptive scan)")
        # serving never changes the weights: quantize each approximated one
        # and build its limbs once, so every step reads them in place
        self.params = prepare_params(params, cfg)
        _AX_PREPARED.set(prepared_projections(self.params))
        self.cfg = cfg
        self.bcfg = bcfg or BatcherConfig()
        # pad-mask prefill (and with it per-slot positions, budgets, and
        # idle-slot backfill) needs a full-attention stack: ring caches and
        # recurrent/ssm state would absorb the pad tail.  Other families
        # keep the PR-3 wave behavior (repeat-pad conditioning, idle slots
        # cycling admitted prompts).
        self.padmask = (cfg.family != "encdec" and all(
            k in ("global", "dense_ffn") for k in cfg.layer_kinds()))
        if self.bcfg.token_granular:
            assert self.padmask, (
                f"token-granular mode needs pad-mask prefill (full-attention "
                f"stack); {cfg.name} has kinds "
                f"{sorted(set(cfg.layer_kinds()))}")
            # temperature > 0 is fine since PR 9: sampling draws from
            # per-request RNG streams (serve.engine.slot_sample), so token
            # mode stays bit-identical to the wave oracle at any temperature
        if self.bcfg.eos_id is not None:
            assert self.padmask, (
                f"eos_id retirement needs the per-slot (pad-mask) decode "
                f"path; {cfg.name} has kinds {sorted(set(cfg.layer_kinds()))}")
        self.adaptive = adaptive
        self.mesh = mesh
        self.par = par
        self.queues: Dict[int, collections.deque] = {
            b: collections.deque() for b in sorted(self.bcfg.prompt_buckets)
        }
        self.wave = 0
        self._arrival = 0
        self._order: Dict[int, int] = {}     # rid -> arrival index (FIFO across buckets)
        self.stats = dict(waves=0, requests=0, real_tokens=0, padded_tokens=0,
                          filler_tokens=0, backfilled=0, splices=0,
                          decode_steps=0, decode_retraces_post_warmup=0,
                          shed=0, timeouts=0, stragglers=0, eos_retired=0)
        self.mode = "token" if self.bcfg.token_granular else "wave"
        # per-step (token mode) / per-wave straggler watchdog — the same
        # trailing-median detector the train loop supervises with
        self.watchdog = StragglerWatchdog(factor=self.bcfg.straggler_factor)
        self._submit_t: Dict[int, float] = {}    # rid -> submit perf_counter
        # per-request latency log (rid, bucket, prompt_len, max_new, ttft,
        # e2e seconds) — the source benchmarks/serving_table.py reduces to
        # TTFT/e2e p50/p99 per mode
        self.request_log: List[dict] = []
        # QoR attribution (obs.qor): correlation ids assigned at submit —
        # "<rid>#<arrival>" stays unique across splices/backfills and across
        # drains even when rids recur — and exposure accounting over the
        # token loop's step telemetry.  Wave mode carries the corr id on its
        # completions but never attributes (the oracle stays uninstrumented).
        self._corr: Dict[int, str] = {}          # pending rid -> corr id
        self.qor = obs.ErrorAttributor()
        # optional SLO engine (obs.slo, attach_slo): fed every request's
        # ttft/e2e sample as it retires
        self.slo = None

    def attach_slo(self, engine) -> None:
        """Attach an :class:`repro.obs.slo.SLOEngine` to the latency stream
        (sources ``"ttft"`` and ``"e2e"``)."""
        self.slo = engine

    def _update_queue_gauges(self) -> None:
        for b, q in self.queues.items():
            _QUEUE_DEPTH.set(len(q), bucket=str(b))

    def _record_latency(self, req: "Request", ttft: Optional[float],
                        e2e: float, observe_ttft: bool = True,
                        queue_delay: Optional[float] = None,
                        finish: str = "length") -> None:
        if ttft is not None and observe_ttft:
            _TTFT.observe(ttft, mode=self.mode)
        _E2E.observe(e2e, mode=self.mode)
        if self.slo is not None:
            with obs.span("slo_observe", cat="runtime", rid=req.rid):
                if ttft is not None:
                    self.slo.observe_latency("ttft", ttft)
                self.slo.observe_latency("e2e", e2e)
        self.request_log.append(dict(
            rid=req.rid, bucket=self.bucket_of(len(req.tokens)),
            prompt_len=len(req.tokens), max_new=req.max_new,
            ttft=ttft, e2e=e2e, queue_delay=queue_delay,
            seed=self._request_seed(req), finish=finish))

    def _request_seed(self, req: "Request") -> int:
        """The request's sampling-stream seed: explicit ``Request.seed``, or
        a deterministic (BatcherConfig.seed, rid) derivation — a function of
        the request identity alone, so the stream is invariant to arrival
        order, slot placement and serving mode."""
        if req.seed is not None:
            return int(req.seed) & 0x7FFFFFFF
        return (self.bcfg.seed * 1_000_003 + req.rid * 2_654_435_761) \
            & 0x7FFFFFFF

    # -- admission -----------------------------------------------------
    def bucket_of(self, prompt_len: int) -> int:
        for b in sorted(self.queues):
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds largest bucket "
            f"{max(self.queues)}")

    def submit(self, req: Request) -> bool:
        """Queue a request.  Returns False (and counts a shed) when the
        bounded admission queue (``BatcherConfig.max_queue``) is full —
        load-shedding at the door beats queueing work that will only time
        out inside."""
        if (self.bcfg.max_queue is not None
                and self.pending() >= self.bcfg.max_queue):
            self.stats["shed"] += 1
            _SHED.inc(1)
            obs.instant("shed", cat="scheduler", rid=req.rid,
                        pending=self.pending())
            return False
        assert req.max_new >= 1, req
        assert req.max_new <= self.bcfg.new_token_bucket, (
            f"request {req.rid}: max_new {req.max_new} > token bucket "
            f"{self.bcfg.new_token_bucket}")
        assert req.rid not in self._order, f"duplicate pending rid {req.rid}"
        req.tokens = np.asarray(req.tokens, np.int32).reshape(-1)
        self.queues[self.bucket_of(len(req.tokens))].append(req)
        self._order[req.rid] = self._arrival
        corr = f"{req.rid}#{self._arrival}"
        self._corr[req.rid] = corr
        self._arrival += 1
        self._submit_t[req.rid] = time.perf_counter()
        obs.async_begin("request", req.rid, prompt_len=len(req.tokens),
                        max_new=req.max_new, corr=corr)
        if self.bcfg.token_granular:
            # exposure accounting opens at submit so even a request that
            # times out queued (or retires within its admission step) still
            # closes with a summary (fleet-basis fallback)
            self.qor.begin(corr, req.rid)
        self._update_queue_gauges()
        return True

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values())

    # -- deadlines -----------------------------------------------------
    def _deadline_passed(self, req: Request) -> bool:
        if req.deadline_s is None:
            return False
        t0 = self._submit_t.get(req.rid)
        return t0 is not None and time.perf_counter() - t0 > req.deadline_s

    def _timeout(self, req: Request, tokens, where: str) -> Completion:
        """Retire ``req`` past its deadline: a ``timeout`` completion with
        whatever tokens were generated so far (empty when still queued)."""
        self.stats["timeouts"] += 1
        _TIMEOUTS.inc(1, where=where)
        e2e = time.perf_counter() - self._submit_t.pop(
            req.rid, time.perf_counter())
        self._record_latency(req, None, e2e, observe_ttft=False,
                             finish="timeout")
        corr = self._corr.pop(req.rid, None)
        qor = self.qor.finish(corr) if corr is not None else None
        obs.instant("timeout", cat="scheduler", rid=req.rid, where=where)
        obs.async_end("request", req.rid, status="timeout")
        return Completion(req.rid, np.asarray(tokens, np.int32),
                          self.wave if self.mode == "wave"
                          else self.stats["decode_steps"],
                          len(req.tokens), self.bucket_of(len(req.tokens)),
                          status="timeout", corr=corr, qor=qor,
                          finish="timeout")

    def _expire_queued(self) -> List[Completion]:
        """Sweep the admission queues for requests whose deadline passed
        while waiting; retires them as empty ``timeout`` completions."""
        out = []
        for q in self.queues.values():
            expired = [r for r in q if self._deadline_passed(r)]
            if expired:
                dead = {r.rid for r in expired}
                keep = [r for r in q if r.rid not in dead]
                for r in expired:
                    del self._order[r.rid]
                    out.append(self._timeout(r, np.zeros(0, np.int32),
                                             where="queued"))
                q.clear()
                q.extend(keep)
        if out:
            self._update_queue_gauges()
        return out

    def max_cache_len(self) -> int:
        """One decode-cache length shared by every bucket: the decode
        program (and in token mode the step program) compiles once."""
        return max(self.queues) + self.bcfg.new_token_bucket + 1

    # -- FIFO helpers --------------------------------------------------
    def _pick_bucket(self, max_prompt_len: Optional[int] = None) -> Optional[int]:
        """Bucket whose HEAD is the globally oldest waiting request (FIFO
        fairness across buckets; within a bucket the deque is already FIFO).
        ``max_prompt_len`` skips buckets whose head doesn't fit."""
        best, best_order = None, None
        for b, q in self.queues.items():
            if not q:
                continue
            if max_prompt_len is not None and len(q[0].tokens) > max_prompt_len:
                continue
            if best_order is None or self._order[q[0].rid] < best_order:
                best, best_order = b, self._order[q[0].rid]
        return best

    def _pop_oldest(self, max_prompt_len: Optional[int] = None) -> Optional[Request]:
        """Pop the globally oldest request (optionally only if its prompt
        fits ``max_prompt_len``)."""
        b = self._pick_bucket(max_prompt_len)
        if b is None:
            return None
        req = self.queues[b].popleft()
        del self._order[req.rid]             # retired rids leave the FIFO map
        return req                           # (long-running server: no leak)

    def _pad(self, tokens: np.ndarray, bucket: int) -> np.ndarray:
        pad = bucket - len(tokens)
        if pad <= 0:
            return tokens[:bucket]
        return np.concatenate([tokens, np.full(pad, tokens[-1], np.int32)])

    # -- wave execution (the bit-exactness oracle) ---------------------
    def step(self) -> List[Completion]:
        """Run one wave; returns the completions it retired (empty when the
        queues are drained).  Requests whose deadline lapsed while queued
        retire first as ``timeout`` completions (never dispatched)."""
        faults = chaos.fire("sched.step", wave=self.wave, mode=self.mode)
        if any(f.kind == "crash_replica" for f in faults):
            raise chaos.InjectedFault("sched.step: replica killed")
        chaos.maybe_stall(faults, default=0.05)
        timed_out = self._expire_queued()
        bucket = self._pick_bucket()
        if bucket is None:
            return timed_out
        t_wave = time.perf_counter()
        bc = self.bcfg
        q = self.queues[bucket]
        admitted = []
        while q and len(admitted) < bc.n_slots:
            req = q.popleft()
            del self._order[req.rid]
            admitted.append(req)
        # backfill idle slots with the next FIFO requests from other buckets
        # whose prompts fit this wave's bucket — outputs are kept (the old
        # behavior cycled already-admitted prompts and discarded the copies).
        # Correct only under pad-mask prefill (a backfilled short prompt
        # must not condition on its pad tail).
        n_backfilled = 0
        while self.padmask and len(admitted) < bc.n_slots:
            req = self._pop_oldest(max_prompt_len=bucket)
            if req is None:
                break
            admitted.append(req)
            n_backfilled += 1
        # remaining idle slots cycle the admitted prompts (fixed shape) with
        # a 1-token budget: they retire after the prefill sample and stay
        # inert for the whole wave
        slots = [admitted[i % len(admitted)] for i in range(bc.n_slots)]
        filler = bc.n_slots - len(admitted)

        if self.adaptive is not None and hasattr(self.adaptive, "poll"):
            self.adaptive.poll()             # replica: adopt newer store policy

        # queue delay: submit -> this wave's admission pop (measured for
        # admitted + backfilled requests; filler slots are copies)
        qdelay = {r.rid: t_wave - self._submit_t.get(r.rid, t_wave)
                  for r in admitted}
        batch = np.stack([self._pad(r.tokens, bucket) for r in slots])
        lens = np.asarray([len(r.tokens) for r in slots], np.int32)
        budgets = np.asarray(
            [r.max_new if i < len(admitted) else 1
             for i, r in enumerate(slots)], np.int32)
        scfg = ServeConfig(max_new_tokens=bc.new_token_bucket,
                           temperature=bc.temperature, seed=bc.seed,
                           fused=True, observe_every=bc.observe_every,
                           eos_id=bc.eos_id if self.padmask else None)
        padmask_kw = (dict(prompt_lens=lens, slot_new_tokens=budgets,
                           max_cache_len=self.max_cache_len())
                      if self.padmask else {})
        if self.padmask and bc.temperature > 0:
            # per-request RNG streams: sampling depends on (seed, token
            # index) only, so these waves match token mode bit-exactly
            padmask_kw["slot_seeds"] = np.asarray(
                [self._request_seed(r) for r in slots], np.int32)
        self._update_queue_gauges()
        with obs.span("wave", cat="scheduler", wave=self.wave, bucket=bucket,
                      admitted=len(admitted), backfilled=n_backfilled):
            out = np.asarray(generate(
                self.params, {"tokens": jnp.asarray(batch)}, self.cfg, scfg,
                par=self.par, adaptive=self.adaptive, mesh=self.mesh,
                **padmask_kw))
        t_done = time.perf_counter()

        done = []
        for i, req in enumerate(admitted):
            toks = out[i, :req.max_new]
            finish = "length"
            if bc.eos_id is not None and self.padmask:
                hits = np.nonzero(toks == bc.eos_id)[0]
                if hits.size:                     # truncate at the first EOS
                    toks = toks[:int(hits[0]) + 1]   # (EOS kept as last token)
                    finish = "eos"
                    self.stats["eos_retired"] += 1
            done.append(Completion(req.rid, toks, self.wave,
                                   len(req.tokens), bucket,
                                   corr=self._corr.pop(req.rid, None),
                                   finish=finish))
            self.stats["real_tokens"] += int(len(toks))
            self.stats["padded_tokens"] += int(
                bucket - len(req.tokens) + bc.new_token_bucket - len(toks))
            e2e = t_done - self._submit_t.pop(req.rid, t_done)
            # wave TTFT == e2e at wave-LANDING granularity: the fused scan
            # returns all slots together, so even an EOS-frozen slot's
            # tokens materialize only now (see the histogram help text)
            self._record_latency(req, e2e, e2e,
                                 queue_delay=qdelay.get(req.rid),
                                 finish=finish)
            obs.async_end("request", req.rid, wave=self.wave)
        self.stats["backfilled"] += n_backfilled
        self.stats["filler_tokens"] += filler * (bucket + bc.new_token_bucket)
        self.stats["requests"] += len(admitted)
        self.stats["waves"] += 1
        self.stats["decode_steps"] += bc.new_token_bucket - 1
        _ADMISSIONS.inc(len(admitted), mode=self.mode)
        _BACKFILLS.inc(n_backfilled)
        _OCCUPANCY.set(self.occupancy(), mode=self.mode)
        if self.watchdog.observe(t_done - t_wave):
            self.stats["stragglers"] += 1
            _STRAGGLERS.inc(1, mode=self.mode)
            obs.instant("straggler", cat="scheduler", wave=self.wave,
                        wall=t_done - t_wave)
        self.wave += 1
        return timed_out + done

    # -- token-granular execution --------------------------------------
    def _admit_pop(self):
        """Pop the next admissible FIFO request; requests whose deadline
        lapsed while queued are retired as empty ``timeout`` completions
        instead of being prefilled (the prefill would be wasted work)."""
        expired: List[Completion] = []
        req = self._pop_oldest()
        while req is not None and self._deadline_passed(req):
            expired.append(self._timeout(req, np.zeros(0, np.int32),
                                         where="queued"))
            req = self._pop_oldest()
        return req, expired

    def _admit_dispatch(self, slot: int, key):
        """Pop the next FIFO request and *dispatch* its prefill (JAX async
        dispatch — returns immediately, the prefill computes on device).
        Returns ``(pending-admission dict | None, expired timeouts)``; the
        splice + first-token sync happen in :meth:`_admit_complete`, either
        immediately (sync admission) or at the next step boundary (async
        admission — the prefill then overlaps the in-flight decode)."""
        req, expired = self._admit_pop()
        if req is None:
            return None, expired
        if self.adaptive is not None and hasattr(self.adaptive, "poll"):
            with obs.span("policy_poll", cat="runtime", rid=req.rid):
                self.adaptive.poll()
        L = len(req.tokens)
        bucket = self.bucket_of(L)
        padded = self._pad(req.tokens, bucket)
        t_dispatch = time.perf_counter()
        if self.bcfg.temperature > 0:
            # per-request RNG stream: the first token is index 0 of the
            # request's stream, bit-matching the wave oracle's seeded draw
            seed_kw = dict(seed=self._request_seed(req))
        else:
            seed_kw = dict(key=key)
        with obs.span("admit_dispatch", cat="scheduler", rid=req.rid,
                      slot=slot, bucket=bucket):
            first, fresh = prefill_one(
                self.params, padded[None], L, self.cfg, self.par,
                max_cache_len=self.max_cache_len(),
                temperature=self.bcfg.temperature, **seed_kw)
        queue_delay = t_dispatch - self._submit_t.get(req.rid, t_dispatch)
        return dict(req=req, slot=slot, first=first, fresh=fresh,
                    queue_delay=queue_delay), expired

    def _admit_complete(self, pend: dict, state: list, pos: np.ndarray,
                        tok: np.ndarray, nt: np.ndarray, seeds: np.ndarray,
                        cache, splice: bool):
        """Splice a dispatched prefill into its slot's cache region and sync
        the first token to the host; fills the slot state.  A request that
        retires within its admission (``max_new == 1``, or its first token
        IS the EOS) frees the slot again in place."""
        req, slot = pend["req"], pend["slot"]
        done: List[Completion] = []
        with obs.span("admit", cat="scheduler", rid=req.rid, slot=slot):
            cache = splice_slot_jit(cache, pend["fresh"], slot)
            first = int(np.asarray(pend["first"])[0])   # sync: token on host
        obs.instant("splice", cat="scheduler", rid=req.rid, slot=slot)
        ttft = time.perf_counter() - self._submit_t.get(
            req.rid, time.perf_counter())
        _TTFT.observe(ttft, mode=self.mode)
        state[slot] = dict(req=req, remaining=req.max_new - 1, toks=[first],
                           ttft=ttft, queue_delay=pend["queue_delay"])
        pos[slot] = len(req.tokens)
        tok[slot] = first
        nt[slot] = 1                          # token 0 sampled at prefill
        seeds[slot] = self._request_seed(req)
        self.stats["requests"] += 1
        self.stats["real_tokens"] += 1
        self.stats["padded_tokens"] += self.bucket_of(len(req.tokens)) - len(
            req.tokens)
        _ADMISSIONS.inc(1, mode=self.mode)
        self._update_queue_gauges()
        eos_hit = (self.bcfg.eos_id is not None
                   and first == self.bcfg.eos_id)
        if state[slot]["remaining"] == 0 or eos_hit:
            if eos_hit:
                self.stats["eos_retired"] += 1
            done.extend(self._retire(
                slot, state, finish="eos" if eos_hit else "length"))
        elif splice:
            self.stats["splices"] += 1
            _SPLICES.inc(1)
        return cache, done

    def _retire(self, slot: int, state: list, status: str = "ok",
                finish: Optional[str] = None) -> List[Completion]:
        st = state[slot]
        state[slot] = None
        req = st["req"]
        if finish is None:
            finish = "timeout" if status == "timeout" else "length"
        if status == "timeout":              # mid-decode deadline: keep the
            self.stats["timeouts"] += 1      # partial tokens, mark the cut
            _TIMEOUTS.inc(1, where="decoding")
            obs.instant("timeout", cat="scheduler", rid=req.rid,
                        where="decoding")
        e2e = time.perf_counter() - self._submit_t.pop(
            req.rid, time.perf_counter())
        # TTFT was already observed at the admission splice
        self._record_latency(req, st.get("ttft"), e2e, observe_ttft=False,
                             queue_delay=st.get("queue_delay"), finish=finish)
        corr = self._corr.pop(req.rid, None)
        qor = self.qor.finish(corr) if corr is not None else None
        obs.instant("retire", cat="scheduler", rid=req.rid, slot=slot)
        end_kw = dict(step=self.stats["decode_steps"], status=status)
        if qor is not None and qor["top"]:
            # the top contributor rides on the request's async trace span so
            # timeline views show *where* each request's error concentrated
            end_kw.update(qor_top=qor["top"][0]["where"],
                          qor_share=round(qor["top"][0]["share"], 4),
                          qor_basis=qor["basis"])
        obs.async_end("request", req.rid, **end_kw)
        return [Completion(req.rid, np.asarray(st["toks"], np.int32),
                           self.stats["decode_steps"], len(req.tokens),
                           self.bucket_of(len(req.tokens)), status=status,
                           corr=corr, qor=qor, finish=finish)]

    def _run_token_granular(self, source: Optional["ArrivalSource"] = None
                            ) -> List[Completion]:
        """Drain the queues with mid-flight admission: one compiled step
        program, slots retire and refill at step boundaries.  With an
        ``source`` arrival trace, requests are submitted as their
        timestamps come due, freed slots re-admit as arrivals land, and the
        loop sleeps instead of dispatching zero-active steps."""
        from repro.models import init_cache

        bc = self.bcfg
        B = bc.n_slots
        cache = init_cache(self.cfg, B, self.max_cache_len())
        key = jax.random.PRNGKey(bc.seed)
        state: list = [None] * B
        pending_admits: list = [None] * B    # async: dispatched, not spliced
        pos = np.zeros(B, np.int32)
        tok = np.zeros(B, np.int32)
        nt = np.zeros(B, np.int32)           # per-slot emitted-token counts
        seeds = np.zeros(B, np.int32)        # per-slot request seeds
        done: List[Completion] = []
        k_obs = max(1, int(bc.observe_every))
        pending = pending_step = None        # telemetry the controller has
                                             # yet to observe, and its step
        eos = bc.eos_id
        seeded = bc.temperature > 0          # per-request RNG streams

        t_drain = time.perf_counter()
        tokens_at_start = self.stats["real_tokens"]
        steps_this_drain = 0

        # phase spans carry the step they follow: the boundary after step
        # k (decode_steps - 1 once it has run) holds its retire sweep,
        # arrivals and admissions, and the next step's preparation
        def boundary():
            return self.stats["decode_steps"] - 1

        def poll_arrivals():
            if source is None:
                return
            with obs.span("poll_arrivals", cat="scheduler", step=boundary()):
                for r in source.poll(time.perf_counter() - t_drain):
                    self.submit(r)           # may shed (bounded queue)

        def fill_slots():
            # dispatch admissions into every empty slot; sync mode splices
            # and reads the first token immediately (oracle-faithful),
            # async leaves the prefill in flight until the next boundary.
            # A sync admission that retires in place frees the slot again,
            # hence the inner loop.
            nonlocal cache
            with obs.span("fill_slots", cat="scheduler", step=boundary()):
                for s in range(B):
                    while state[s] is None and pending_admits[s] is None:
                        pend, expired = self._admit_dispatch(s, key)
                        done.extend(expired)
                        if pend is None:
                            break
                        if bc.async_admission:
                            pending_admits[s] = pend
                        else:
                            cache, d = self._admit_complete(
                                pend, state, pos, tok, nt, seeds, cache,
                                splice=steps_this_drain > 0)
                            done.extend(d)

        def complete_admits():
            # async admissions splice at the step boundary: their prefills
            # computed concurrently with the decode step(s) dispatched
            # since; the first-token read below is the only sync point
            nonlocal cache
            for s in range(B):
                if pending_admits[s] is not None:
                    pend, pending_admits[s] = pending_admits[s], None
                    cache, d = self._admit_complete(
                        pend, state, pos, tok, nt, seeds, cache,
                        splice=steps_this_drain > 0)
                    done.extend(d)

        poll_arrivals()
        fill_slots()
        # zero-recompile invariant: the step program compiles once on the
        # first decode step of a cold process; everything after — splices,
        # retirements, policy adoptions — must reuse it.  Snapshot the
        # token_step install count after step 0 and assert no further
        # installs land during the drain (the live gauge CI gates).
        warmup_installs = None
        while True:
            if any(p is not None for p in pending_admits):
                complete_admits()
                fill_slots()                 # in-place retires free slots
            active_np = np.asarray([st is not None for st in state])
            if not active_np.any():
                if any(p is not None for p in pending_admits):
                    continue
                if source is not None and not source.exhausted():
                    # idle under arrivals: sleep until the next request is
                    # due — never dispatch a zero-active decode step
                    nd = source.next_due()
                    now = time.perf_counter() - t_drain
                    if nd is not None and nd > now:
                        time.sleep(min(nd - now, 0.05))
                    poll_arrivals()
                    fill_slots()
                    continue
                if self.pending():
                    fill_slots()
                    continue
                break
            step = self.stats["decode_steps"]
            with obs.span("step_prepare", cat="scheduler", step=step):
                faults = chaos.fire("sched.step", step=step, mode=self.mode)
                if any(f.kind == "crash_replica" for f in faults):
                    raise chaos.InjectedFault("sched.step: replica killed")
                chaos.maybe_stall(faults, default=0.05)
                # the corr ids live in THIS step — captured before the
                # retire/splice sweep below, so telemetry produced by the
                # step is charged to exactly the requests decoding in it
                live_corrs = [self._corr[st["req"].rid]
                              for st in state if st is not None]
                key, sub = jax.random.split(key)
                gate = (step % k_obs == 0)
            t_step = time.perf_counter()
            with obs.span("token_step", cat="scheduler", step=step,
                          active=int(active_np.sum())):
                out = token_step(
                    self.params, cache, jnp.asarray(tok), sub,
                    jnp.asarray(pos), jnp.asarray(active_np), self.cfg,
                    self.par, temperature=bc.temperature,
                    adaptive=self.adaptive, mesh=self.mesh, gate=gate,
                    eos_id=eos,
                    seeds=jnp.asarray(seeds) if seeded else None,
                    nt=jnp.asarray(nt) if seeded else None)
            step_wall = time.perf_counter() - t_step
            if self.watchdog.observe(step_wall):
                self.stats["stragglers"] += 1
                _STRAGGLERS.inc(1, mode=self.mode)
                obs.instant("straggler", cat="scheduler", step=step,
                            wall=step_wall)
            if warmup_installs is None:
                warmup_installs = obs.retrace_total("token_step")
            if self.adaptive is not None:
                tok_d, cache, telem = out
                if pending is not None:      # one-step-stale observe keeps
                    with obs.span("controller_observe", cat="runtime",
                                  step=pending_step):
                        self.adaptive.observe(pending)
                    pending = None           # the dispatch pipeline warm
                if gate:
                    # host transfer NOW (the tok sync below drains the same
                    # dispatch, so this adds no stall) — attribution must
                    # charge this step's live corr set before any of them
                    # retires in the sweep below; the controller still
                    # observes one step stale, exactly as before
                    with obs.span("telemetry_read", cat="scheduler",
                                  step=step):
                        host_telem = jax.device_get(telem)
                    pending, pending_step = host_telem, step
            else:
                tok_d, cache = out
            with obs.span("token_read", cat="scheduler", step=step):
                tok = np.array(tok_d)    # writable copy (splices update rows)
            _STEP_WALL.observe(time.perf_counter() - t_step)
            if self.adaptive is not None and gate:
                with obs.span("qor_observe", cat="runtime", step=step):
                    self.qor.observe_step(host_telem, live_corrs)
            pos = pos + active_np
            nt = nt + active_np
            n_active = int(active_np.sum())
            self.stats["real_tokens"] += n_active
            self.stats["filler_tokens"] += B - n_active
            self.stats["decode_steps"] += 1
            steps_this_drain += 1
            with obs.span("retire_sweep", cat="scheduler", step=step):
                for s in range(B):           # retire at the step boundary
                    st = state[s]
                    if st is None:
                        continue
                    st["toks"].append(int(tok[s]))
                    st["remaining"] -= 1
                    eos_hit = eos is not None and int(tok[s]) == eos
                    timed_out = (st["remaining"] > 0 and not eos_hit
                                 and self._deadline_passed(st["req"]))
                    if st["remaining"] == 0 or eos_hit or timed_out:
                        if eos_hit:
                            self.stats["eos_retired"] += 1
                        finish = ("eos" if eos_hit else
                                  ("timeout" if timed_out else "length"))
                        done.extend(self._retire(
                            s, state,
                            status="timeout" if timed_out else "ok",
                            finish=finish))
            poll_arrivals()
            fill_slots()                     # splice/dispatch replacements
        if pending is not None and self.adaptive is not None:
            with obs.span("controller_observe", cat="runtime",
                          step=pending_step):
                self.adaptive.observe(pending)
        post = (0 if warmup_installs is None
                else int(obs.retrace_total("token_step") - warmup_installs))
        self.stats["decode_retraces_post_warmup"] = post
        _POST_WARMUP_RETRACES.set(post)
        assert post == 0, (
            f"token-granular drain retraced the step program {post}x after "
            f"warmup — splices/policy updates must only change traced values")
        _OCCUPANCY.set(self.occupancy(), mode=self.mode)
        wall = time.perf_counter() - t_drain
        if wall > 0:
            _TOKENS_PER_S.set(
                (self.stats["real_tokens"] - tokens_at_start) / wall,
                mode=self.mode)
        return done

    def run(self) -> List[Completion]:
        """Drain the queues; returns all completions in retirement order."""
        if self.bcfg.token_granular:
            return self._run_token_granular()
        out: List[Completion] = []
        while self.pending():
            out.extend(self.step())
        return out

    def run_arrivals(self, source: "ArrivalSource") -> List[Completion]:
        """Serve an arrival trace: requests are submitted as their
        timestamps come due (relative to the call), queueing delay is
        measured per request, and the engine sleeps rather than spinning
        when no request is due.  Token-granular mode admits mid-flight as
        arrivals land; wave mode launches a wave over whatever has arrived
        and re-polls between waves (late arrivals wait for the next wave —
        exactly the head-of-line blocking the serving table measures)."""
        if self.bcfg.token_granular:
            return self._run_token_granular(source=source)
        out: List[Completion] = []
        t0 = time.perf_counter()
        while True:
            for r in source.poll(time.perf_counter() - t0):
                self.submit(r)
            if not self.pending():
                if source.exhausted():
                    break
                nd = source.next_due()
                now = time.perf_counter() - t0
                if nd is not None and nd > now:
                    time.sleep(min(nd - now, 0.05))
                continue
            out.extend(self.step())
        return out

    def occupancy(self) -> float:
        s = self.stats
        useful = s["real_tokens"]
        total = useful + s["padded_tokens"] + s["filler_tokens"]
        return useful / total if total else 1.0

    def latency_summary(self) -> dict:
        """TTFT / e2e percentiles (seconds) over ``request_log``.

        The ``*_p50``/``*_p99`` keys are exact order statistics from the
        per-request records (unchanged interface).  Each also carries a
        bucket-resolution twin: ``*_bucketed`` is what the corresponding
        registry histogram (tuned ``TTFT_BUCKETS``/``E2E_BUCKETS`` family)
        reports for the same samples via linear interpolation, and
        ``*_resolution`` the covering bucket's width — so gates and humans
        comparing exact percentiles against histogram reads see a stated
        resolution instead of an exact-vs-bucket-floor mismatch.

        Wave-mode caveat: TTFT records equal e2e records there because the
        fused wave materializes all tokens when it returns — TTFT is
        measured at wave-*landing* granularity, not a claim that the first
        token took the whole wave to compute (token-granular mode measures
        true first-token latency).  ``queue_delay_*`` percentiles cover
        requests served from an arrival trace (records carry a non-None
        ``queue_delay``); absent under direct ``run()`` drains.  Empty
        log -> empty dict."""
        if not self.request_log:
            return {}
        e2e = np.asarray([r["e2e"] for r in self.request_log])
        ttft = np.asarray([r["ttft"] for r in self.request_log
                           if r["ttft"] is not None])
        out = dict(requests=len(self.request_log),
                   e2e_p50=float(np.percentile(e2e, 50)),
                   e2e_p99=float(np.percentile(e2e, 99)))
        for q, name in ((0.50, "e2e_p50"), (0.99, "e2e_p99")):
            v, res = obs.bucket_percentile(e2e, obs.E2E_BUCKETS, q)
            out[name + "_bucketed"] = v
            out[name + "_resolution"] = res
        if ttft.size:
            out.update(ttft_p50=float(np.percentile(ttft, 50)),
                       ttft_p99=float(np.percentile(ttft, 99)))
            for q, name in ((0.50, "ttft_p50"), (0.99, "ttft_p99")):
                v, res = obs.bucket_percentile(ttft, obs.TTFT_BUCKETS, q)
                out[name + "_bucketed"] = v
                out[name + "_resolution"] = res
        qd = np.asarray([r["queue_delay"] for r in self.request_log
                         if r.get("queue_delay") is not None])
        if qd.size:
            out.update(queue_delay_p50=float(np.percentile(qd, 50)),
                       queue_delay_p99=float(np.percentile(qd, 99)))
        return out

    def describe(self) -> str:
        s = self.stats
        return (f"batcher[{self.mode}] waves={s['waves']} "
                f"steps={s['decode_steps']} "
                f"requests={s['requests']} splices={s['splices']} "
                f"backfilled={s['backfilled']} "
                f"retraces={s['decode_retraces_post_warmup']} "
                f"shed={s['shed']} timeouts={s['timeouts']} "
                f"stragglers={s['stragglers']} "
                f"slot_util={self.occupancy():.2f} "
                f"(real={s['real_tokens']} padded={s['padded_tokens']} "
                f"filler={s['filler_tokens']})")


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------

class ArrivalSource:
    """A timestamped request trace: sorted ``(t_offset_seconds, Request)``
    pairs fed to ``ContinuousBatcher.run_arrivals``.  ``poll(now)`` yields
    every request whose timestamp has come due (consuming it); ``next_due``
    is the next pending timestamp (None when drained).  Timestamps are
    offsets from the start of the serve, so the same trace replays
    identically across runs and modes."""

    def __init__(self, items: Sequence[Tuple[float, Request]]):
        self._items = sorted(items, key=lambda it: it[0])
        self._i = 0

    def __len__(self) -> int:
        return len(self._items)

    def exhausted(self) -> bool:
        return self._i >= len(self._items)

    def next_due(self) -> Optional[float]:
        if self.exhausted():
            return None
        return float(self._items[self._i][0])

    def poll(self, now: float) -> List[Request]:
        due: List[Request] = []
        while not self.exhausted() and self._items[self._i][0] <= now:
            due.append(self._items[self._i][1])
            self._i += 1
        return due


def poisson_arrivals(requests: Sequence[Request], rate_rps: float,
                     seed: int = 0) -> ArrivalSource:
    """Stamp ``requests`` with a Poisson process at ``rate_rps`` requests/s:
    inter-arrival gaps are iid Exponential(1/rate), timestamps their cumsum.
    Deterministic in ``seed`` (numpy Generator) — the serving benchmarks
    replay the *same* trace against wave and token-granular engines so the
    goodput/TTFT comparison isolates the scheduler, not the trace."""
    assert rate_rps > 0, "poisson_arrivals: rate must be positive"
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=len(requests))
    return ArrivalSource(list(zip(np.cumsum(gaps).tolist(), requests)))
