"""Batched serving loop: prefill once, then greedy/temperature decode steps
against the sharded KV cache.

The non-adaptive hot path is **fully fused on device**: the whole token loop
(decode step + sampling + cache update) runs as one ``lax.scan``, so serving
``T`` tokens costs one dispatch instead of ``T`` host round-trips.  The
Python step loop is kept (``ServeConfig.fused=False``, or automatically when
a ``param_hook`` needs to mutate params mid-generation) and produces
bit-identical token sequences — the scan body performs the exact same ops in
the same order, including the RNG splits.

With an :class:`~repro.runtime.AdaptiveController` attached, the decode step
is compiled **once** with the SWAPPER config as a traced input and telemetry
summaries as extra outputs; each step the controller folds the telemetry in,
scores distribution drift, and re-tunes the policy in place — the jit cache
stays warm throughout (zero recompilations; see runtime/).  Telemetry is
decimated by ``ServeConfig.observe_every``: the observe gate enters the
compiled step as a traced boolean, so off-steps skip the summary compute
(``lax.cond``) *and* the host-side device_get without retracing anything.

**Adaptive decode is also fused** (``ServeConfig.fused=True``, no
``param_hook``): the whole adaptive token loop runs as one ``lax.scan`` with
the per-step telemetry records threaded through the scan carry — each gated
step scatter-adds its fixed-shape record into slot ``i // observe_every`` of
a ``ceil(T/k)``-slot carry buffer (off-steps contribute ``lax.cond`` zeros),
so adaptive serving pays **one dispatch per generation** and the host folds
the slot records into the controller afterwards.  The policy is therefore
frozen within a generation; re-tunes land between generations (the stepwise
loop remains for mid-generation adaptation and ``param_hook``).

With ``mesh=...`` the fused adaptive scan additionally runs under
``shard_map`` over the mesh batch axes: every shard decodes its batch slice
and the telemetry records are ``psum``/``pmax``/all-gathered **in-graph**
(``fleet.collect``) before leaving the trace, so one controller sees the
fleet-global operand distribution.

When the controller (or ``fleet.PolicyReader``) reports ``tile_rows > 0``,
decode runs **per-row-tile**: the policy enters as (tile_rows, 1, 3) config
grids instead of scalar triples, every projection additionally emits a
per-tile telemetry record (same scan-carry slots, same gate), and published
``SwapPolicy.tile_grids`` land in the compiled step as new traced int32
values — tile-granular adaptation with zero recompiles, exactly like the
scalar path (see docs/architecture.md).

**Decode positions are per-slot** (PR 5): every decode path carries an
int32 ``(B,)`` position vector instead of one scalar index, and per-slot
done-flags derived from ``slot_new_tokens`` gate sampling (a finished
slot's token freezes), cache writes (dropped — the slot's cache region
stays inert until a fresh request is spliced in), and the telemetry
scatter-add (all-retired steps contribute nothing).

**EOS retires slots early** (PR 9): with ``ServeConfig.eos_id`` set, the
done-flag is ``(i < budget) & (tok != eos)`` — the carry token freezes at
the EOS value, so the derivation needs no extra carry state and a slot
stops sampling, stops writing its cache region, and stops advancing its
position the step after it emits EOS, in every decode path (fused scan,
adaptive scan-carry scan, stepwise oracle, and :func:`token_step`).  The
telemetry observe gate stays budget-driven (``i < bmax``) — it must be
identical on every shard and across paths, and EOS hits are data-dependent.

**Per-request RNG streams** (PR 9): ``slot_seeds`` gives every slot its own
sampling stream — token ``t`` of a request with seed ``s`` draws from
``fold_in(PRNGKey(s), t)`` (:func:`slot_sample`), a function of the request
alone, never of batch composition, slot index, or step index.  Temperature
sampling therefore becomes splice-invariant: the token-granular batcher
produces per-request tokens bit-identical to the wave oracle at any
temperature, not just greedy.  ``prompt_lens``
switches prefill to the pad-mask path: right-padded prompts attend only to
real tokens, the first token samples at each slot's last *real* position,
and decode starts at position ``len`` per slot — a padded prompt's
generation is bit-identical to the same prompt served unpadded.  On top of
this, :func:`token_step` exposes a single-compilation per-step decode
(decode + sample + freeze) used by the token-granular continuous batcher
(``fleet.scheduler``) to splice new requests into a mid-flight batch at
step boundaries with zero recompiles.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import decode_step, prefill

__all__ = ["ServeConfig", "generate", "token_step", "prefill_one",
           "slot_sample"]

# host-side observability (repro.obs): program-install accounting per cache
# kind — the live "zero recompiles" signal CI gates — plus dispatch-wall
# histograms.  All updates happen OUTSIDE traced code, so instrumentation
# cannot perturb tokens, telemetry, or compiled programs (tested).
_PREFILL_WALL = obs.default_registry().histogram(
    "repro_prefill_dispatch_seconds",
    "host wall of generate()'s prefill + first-token sample "
    "(async dispatch: excludes on-device completion)",
    buckets=obs.DISPATCH_BUCKETS)
_DECODE_WALL = obs.default_registry().histogram(
    "repro_decode_dispatch_seconds",
    "host wall of generate()'s decode-loop dispatch by path "
    "(async dispatch: excludes on-device completion)",
    buckets=obs.DISPATCH_BUCKETS)
_DECODE_TOKENS = obs.default_registry().counter(
    "repro_decode_tokens_total",
    "tokens produced by generate() decode loops (slots x steps)")
_SLOTS_RETIRED = obs.default_registry().counter(
    "repro_slots_retired_total",
    "slots whose done-flag fires before the scan/budget end "
    "(per-slot token budgets below the generation length)")


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0   # 0 => greedy
    seed: int = 0
    fused: bool = True         # on-device lax.scan decode (non-adaptive path)
    observe_every: int = 1     # adaptive telemetry decimation period (k >= 1)
    # EOS-triggered early retirement (vectorized paths only): a slot whose
    # sampled token equals eos_id freezes — done-flag, cache writes and
    # position all stop the following step.  None keeps every pre-PR9
    # program byte-identical.
    eos_id: Optional[int] = None


def _sampler(scfg: ServeConfig):
    def sample(logits, key):
        lg = logits[:, -1].astype(jnp.float32)
        if scfg.temperature > 0:
            return jax.random.categorical(key, lg / scfg.temperature, axis=-1)
        return jnp.argmax(lg, axis=-1)

    return sample


def slot_sample(last_logits, seeds, nt, temperature: float):
    """Per-request RNG-stream sampling: row ``s`` of ``last_logits`` (B, V)
    draws token index ``nt[s]`` of the request seeded ``seeds[s]`` from
    ``fold_in(PRNGKey(seeds[s]), nt[s])``.

    The draw depends on (request seed, token index) ONLY — not on batch
    composition, slot index, or global step — so temperature sampling is
    splice-invariant: a request produces the identical token sequence
    whether it runs in a wave, is spliced mid-flight into any slot of a
    token-granular batch, or is served alone.  ``temperature <= 0`` is
    argmax (greedy needs no stream).
    """
    lg = last_logits.astype(jnp.float32)
    if temperature <= 0:
        return jnp.argmax(lg, axis=-1)

    def one(row, seed, t):
        k = jax.random.fold_in(jax.random.PRNGKey(seed), t)
        return jax.random.categorical(k, row / temperature, axis=-1)

    return jax.vmap(one)(lg, jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(nt, jnp.int32))


def generate(params, prompt_batch, cfg: ModelConfig, scfg: ServeConfig,
             par: Optional[ParallelConfig] = None, adaptive=None,
             param_hook: Optional[Callable] = None, mesh=None,
             prompt_lens=None, slot_new_tokens=None, max_cache_len=None,
             slot_seeds=None):
    """prompt_batch: {'tokens': (B, S)} (or family-specific prefill inputs).
    Returns (B, max_new_tokens) int32.

    ``adaptive`` — optional AdaptiveController (or ``fleet.PolicyReader``)
    driving the dynamic SWAPPER policy for ``cfg.ax.targets`` projections
    during decode.
    ``param_hook(step, params) -> params`` — optional per-step parameter
    transform (used by the serve driver to inject synthetic distribution
    drift; values change, shapes don't, so the step is not retraced).  A hook
    forces the stepwise Python loop (params must change between steps).
    ``mesh`` — optional device mesh for the fleet path: the fused adaptive
    decode shards its batch over the mesh batch axes under ``shard_map`` and
    telemetry is aggregated in-graph (requires ``adaptive`` and
    ``scfg.fused``; greedy decoding is bit-identical to the single-host run,
    temperature sampling draws per-shard).
    ``prompt_lens`` — optional (B,) int32 of real prompt lengths: prefill
    runs pad-masked (padded slots attend only to real tokens), the first
    token samples at each slot's last real position, and decode positions
    start at ``prompt_lens`` per slot.
    ``slot_new_tokens`` — optional (B,) int32 per-slot token budgets (each
    ``<= scfg.max_new_tokens``): a slot that exhausts its budget retires in
    place — its token freezes (repeated in the output tail), its cache
    region stops being written, and an all-retired step stops contributing
    telemetry.
    ``max_cache_len`` — optional decode-cache length override (the
    scheduler passes one shared length so every prompt bucket reuses the
    same compiled decode program).
    ``slot_seeds`` — optional (B,) int32 per-request sampling seeds
    (:func:`slot_sample`): every slot draws from its own RNG stream keyed
    on (seed, token index), making temperature sampling splice-invariant.
    ``None`` keeps the pre-PR9 shared key chain (greedy output identical
    either way).
    """
    S = (prompt_batch["tokens"].shape[1] if "tokens" in prompt_batch
         else prompt_batch["embeds"].shape[1])
    B = jax.tree.leaves(prompt_batch)[0].shape[0]
    max_len = max_cache_len or (S + scfg.max_new_tokens + 1)
    assert max_len >= S + scfg.max_new_tokens + 1, (max_len, S, scfg)

    # per-slot (vectorized) decode is engaged only when a caller asks for it
    # (pad-mask prefill / per-slot budgets) or under a mesh (per-slot vectors
    # shard; scalars would have to be replicated-and-broadcast anyway).  The
    # default path keeps the scalar position index: one dynamic_update_slice
    # cache write instead of a per-row scatter, and encdec (whisper) decode
    # — which has no per-slot plumbing — keeps working.
    vec = (prompt_lens is not None or slot_new_tokens is not None
           or mesh is not None or scfg.eos_id is not None
           or slot_seeds is not None)
    if cfg.family == "encdec":
        assert not vec, ("per-slot decode (prompt_lens / slot_new_tokens / "
                         "mesh / eos_id / slot_seeds) is not supported for "
                         "encdec models")

    pl = (None if prompt_lens is None
          else jnp.asarray(prompt_lens, jnp.int32).reshape(B))
    seeds = (None if slot_seeds is None
             else jnp.asarray(slot_seeds, jnp.int32).reshape(B))
    t0 = time.perf_counter()
    with obs.span("prefill", cat="engine", batch=B, seq=S):
        logits, cache = prefill(params, prompt_batch, cfg, par,
                                max_cache_len=max_len, prompt_lens=pl)
        key = jax.random.PRNGKey(scfg.seed)
        sample = _sampler(scfg)
        if seeds is not None:
            # per-request streams: the first token is index 0 of each slot's
            # stream (conditioning on the last REAL position under pad-mask)
            lg = (logits[:, -1] if pl is None
                  else logits[jnp.arange(B), pl - 1])
            tok = slot_sample(lg, seeds, jnp.zeros(B, jnp.int32),
                              scfg.temperature)
        elif pl is None:
            tok = sample(logits, key)
        else:
            # pad-mask path: the next token conditions on the last REAL prompt
            # position, not the pad tail
            tok = sample(logits[jnp.arange(B), pl - 1][:, None], key)
    _PREFILL_WALL.observe(time.perf_counter() - t0)
    n_steps = scfg.max_new_tokens - 1
    if vec:
        pos0 = pl if pl is not None else jnp.full((B,), S, jnp.int32)
        budget = (jnp.full((B,), n_steps, jnp.int32)
                  if slot_new_tokens is None
                  else jnp.asarray(slot_new_tokens, jnp.int32).reshape(B) - 1)
    else:
        pos0, budget = jnp.int32(S), None      # scalar legacy path

    if budget is not None:
        # retirement accounting: a slot whose budget sits below the full
        # generation length WILL freeze before the scan end (host-known —
        # the done-flag math is deterministic in slot_new_tokens)
        _SLOTS_RETIRED.inc(int(np.sum(np.asarray(budget) < n_steps)))

    if adaptive is None and param_hook is None and scfg.fused:
        assert mesh is None, "mesh= requires the adaptive fused path"
        path, run = "fused", lambda: _generate_fused(
            params, cache, tok, key, pos0, budget, cfg, scfg, par, seeds)
    elif adaptive is not None and param_hook is None and scfg.fused:
        path, run = "fused_adaptive", lambda: _generate_fused_adaptive(
            params, cache, tok, key, pos0, budget, B, cfg, scfg, par,
            adaptive, mesh, seeds)
    else:
        assert mesh is None, \
            "mesh= requires the adaptive fused path (no param_hook)"
        path, run = "stepwise", lambda: _generate_stepwise(
            params, cache, tok, key, pos0, budget, cfg, scfg, par, adaptive,
            param_hook, seeds)
    t0 = time.perf_counter()
    with obs.span("decode", cat="engine", path=path, batch=B,
                  steps=scfg.max_new_tokens):
        out = run()
    _DECODE_WALL.observe(time.perf_counter() - t0, path=path)
    _DECODE_TOKENS.inc(B * scfg.max_new_tokens)
    return out


@functools.lru_cache(maxsize=64)
def _fused_decode_fn(cfg, par, n_steps: int, temperature: float,
                     vectorized: bool = False,
                     eos_id: Optional[int] = None, seeded: bool = False):
    """Build (and cache) the jitted whole-loop decode scan.  Keyed on the
    hashable configs so repeated ``generate`` calls reuse the compiled
    program.  The scalar variant takes one traced ``start`` index (the
    pre-PR5 program: one dynamic_update_slice cache write per step); the
    ``vectorized`` variant takes per-slot (B,) positions and token budgets
    as traced vectors, so retired slots freeze without a branch.  With
    ``eos_id`` the done-flag is ``(i < budget) & (tok != eos)`` — the carry
    token freezes at EOS, so the mask needs no extra carry state; with
    ``seeded`` the carry additionally threads per-slot token counters and
    sampling draws from the per-request streams (:func:`slot_sample`)."""
    obs.count_retrace("fused")          # lru miss == new compiled program
    scfg = ServeConfig(temperature=temperature)
    sample = _sampler(scfg)

    if not vectorized:
        assert eos_id is None and not seeded

        @jax.jit
        def decode_scan(params, cache, tok0, key0, start):
            def step(carry, i):
                tok, cache, key = carry
                key, sub = jax.random.split(key)
                logits, cache = decode_step(params, cache, tok[:, None],
                                            start + i, cfg, par)
                tok = sample(logits, sub)
                return (tok, cache, key), tok

            (_, _, _), toks = jax.lax.scan(
                step, (tok0, cache, key0),
                jnp.arange(n_steps, dtype=jnp.int32))
            return toks                               # (n_steps, B)

        return decode_scan

    @jax.jit
    def decode_scan(params, cache, tok0, key0, pos0, budget, seeds):
        def step(carry, i):
            tok, cache, key, pos, nt = carry
            key, sub = jax.random.split(key)
            active = i < budget                        # (B,) done-flags
            if eos_id is not None:
                active = active & (tok != eos_id)      # frozen tok stays EOS
            logits, cache = decode_step(params, cache, tok[:, None],
                                        pos, cfg, par, write_mask=active)
            if seeded:
                nxt = slot_sample(logits[:, -1], seeds, nt, temperature)
            else:
                nxt = sample(logits, sub)
            tok = jnp.where(active, nxt, tok)
            pos = pos + active.astype(jnp.int32)
            nt = nt + active.astype(jnp.int32)
            return (tok, cache, key, pos, nt), tok

        nt0 = jnp.ones_like(pos0)      # token 0 was sampled at prefill
        (_, _, _, _, _), toks = jax.lax.scan(
            step, (tok0, cache, key0, pos0, nt0),
            jnp.arange(n_steps, dtype=jnp.int32))
        return toks                                   # (n_steps, B)

    return decode_scan


def _generate_fused(params, cache, tok, key, pos0, budget, cfg,
                    scfg: ServeConfig, par, seeds=None):
    """The whole decode loop (step + sample) as one on-device ``lax.scan``.
    ``budget is None`` selects the scalar (pre-PR5) program."""
    n_steps = scfg.max_new_tokens - 1
    if n_steps <= 0:
        return tok[:, None]
    decode_scan = _fused_decode_fn(cfg, par, n_steps, scfg.temperature,
                                   vectorized=budget is not None,
                                   eos_id=scfg.eos_id,
                                   seeded=seeds is not None)
    if budget is None:
        toks = decode_scan(params, cache, tok, key, pos0)
    else:
        if seeds is None:
            seeds = jnp.zeros_like(pos0)   # placeholder: seeded=False ignores
        toks = decode_scan(params, cache, tok, key, pos0, budget, seeds)
    return jnp.concatenate([tok[:, None], jnp.swapaxes(toks, 0, 1)], axis=1)


# adaptive fused-decode program cache: (cfg, par, n_steps, temperature,
# k_obs, mesh, cache treedef, batch, tile_rows) -> jitted scan.  Policy
# values are traced inputs, so every policy update and every wave of a
# fixed-shape scheduler bucket reuses one entry (tests assert
# _cache_size() == 1).
_ADAPTIVE_FNS = {}


def _adaptive_decode_fn(cfg, par, n_steps: int, temperature: float,
                        k_obs: int, mesh, cache, batch: int,
                        tile_rows: int = 0, vectorized: bool = False,
                        eos_id: Optional[int] = None, seeded: bool = False):
    """Build (and cache) the fused adaptive decode: one ``lax.scan`` over the
    token loop with telemetry threaded through the scan carry, optionally
    shard_map'd over the mesh batch axes with in-graph record aggregation.

    ``tile_rows > 0`` is the per-row-tile mode: the dyn-tree leaves are
    (tile_rows, 1, 3) config grids, the scopes additionally emit per-tile
    records (they ride the same scan-carry slots — just more record
    fields), and the compiled program is keyed on the granularity, so
    scalar and tile policies each compile once and re-tunes never retrace
    either.

    ``vectorized`` (always on under a mesh) switches from the scalar
    ``start`` index to per-slot (B,) positions and budgets plus a
    *replicated* ``bmax`` scalar: the observe gate is ``(i % k_obs == 0) &
    (i < bmax)`` — equal to "any slot still live" but computed from the
    global budget maximum, so it is identical on every shard (a per-shard
    ``any(active)`` would let a fully-retired shard drop out of the psum
    while the single-host oracle still counts its frozen slots).  The gate
    is deliberately EOS-agnostic for the same reason: EOS hits are
    data-dependent per shard, the budget max is replicated.

    ``eos_id`` folds ``tok != eos`` into the per-slot done-flags;
    ``seeded`` threads per-slot token counters through the carry and takes
    a (B,) ``seeds`` vector for per-request-stream sampling
    (:func:`slot_sample`)."""
    treedef = jax.tree_util.tree_structure(cache)
    key = (cfg, par, n_steps, temperature, k_obs, mesh, treedef, batch,
           tile_rows, vectorized, eos_id, seeded)
    if key in _ADAPTIVE_FNS:
        return _ADAPTIVE_FNS[key]
    obs.count_retrace("fused_adaptive")   # cache miss == new compiled program

    from repro.runtime import ax_scope

    # telemetry records must be fixed-shape scan-carry leaves: the layer
    # stack is unrolled inside the token-scan body (as in the stepwise path)
    dec_par = dataclasses.replace(par or ParallelConfig(), scan_layers=False)
    sample = _sampler(ServeConfig(temperature=temperature))
    n_obs = -(-n_steps // k_obs)          # carry slots: one per gated step

    if mesh is not None:
        assert vectorized, "the sharded adaptive decode is the vectorized one"
        from repro.fleet.collect import aggregate_records, shard_decode_specs

        in_specs, out_specs, axes = shard_decode_specs(cache, batch, mesh,
                                                       seeded=seeded)
    else:
        axes = ()

    def _probe_bufs(params, cache, tok0, pos0, dyn):
        def probe(params, cache, tok0, pos0, dyn):
            with ax_scope(dyn, collect=True, tile_rows=tile_rows) as sc:
                decode_step(params, cache, tok0[:, None], pos0, cfg, dec_par)
                return sc.collected()

        shapes = jax.eval_shape(probe, params, cache, tok0, pos0, dyn)
        return jax.tree.map(
            lambda s: jnp.zeros((n_obs,) + s.shape, s.dtype), shapes)

    if not vectorized:
        assert eos_id is None and not seeded, \
            "eos_id / slot_seeds need the vectorized (per-slot) decode"

        def decode_scan(params, cache, tok0, key0, start, dyn):
            bufs0 = _probe_bufs(params, cache, tok0, start, dyn)

            def step(carry, i):
                tok, cache, key, bufs = carry
                key, sub = jax.random.split(key)
                gate = (i % k_obs) == 0
                with ax_scope(dyn, collect=True, gate=gate,
                              tile_rows=tile_rows) as sc:
                    logits, cache = decode_step(params, cache, tok[:, None],
                                                start + i, cfg, dec_par)
                    telem = sc.collected()
                tok = sample(logits, sub)
                # off-steps produced lax.cond zeros, so the unconditional
                # scatter-add leaves exactly the gated step's record in its
                # slot
                bufs = jax.tree.map(lambda b, r: b.at[i // k_obs].add(r),
                                    bufs, telem)
                return (tok, cache, key, bufs), tok

            (_, _, _, bufs), toks = jax.lax.scan(
                step, (tok0, cache, key0, bufs0),
                jnp.arange(n_steps, dtype=jnp.int32))
            return toks, bufs                   # (n_steps, B), slot records
    else:
        def _vec_scan(params, cache, tok0, key0, pos0, budget, bmax, dyn,
                      seeds):
            bufs0 = _probe_bufs(params, cache, tok0, pos0, dyn)

            def step(carry, i):
                tok, cache, key, pos, nt, bufs = carry
                key, sub = jax.random.split(key)
                active = i < budget              # (B,) per-slot done-flags
                if eos_id is not None:
                    active = active & (tok != eos_id)   # frozen tok stays EOS
                # shard-invariant live gate (see docstring): bmax is the
                # global budget max, replicated under the mesh (and
                # EOS-agnostic — EOS hits differ per shard)
                gate = ((i % k_obs) == 0) & (i < bmax)
                with ax_scope(dyn, collect=True, gate=gate,
                              tile_rows=tile_rows) as sc:
                    logits, cache = decode_step(params, cache, tok[:, None],
                                                pos, cfg, dec_par,
                                                write_mask=active)
                    telem = sc.collected()
                if seeded:
                    nxt = slot_sample(logits[:, -1], seeds, nt, temperature)
                else:
                    nxt = sample(logits, sub)
                tok = jnp.where(active, nxt, tok)
                pos = pos + active.astype(jnp.int32)
                nt = nt + active.astype(jnp.int32)
                bufs = jax.tree.map(lambda b, r: b.at[i // k_obs].add(r),
                                    bufs, telem)
                return (tok, cache, key, pos, nt, bufs), tok

            nt0 = jnp.ones_like(pos0)    # token 0 was sampled at prefill
            (_, _, _, _, _, bufs), toks = jax.lax.scan(
                step, (tok0, cache, key0, pos0, nt0, bufs0),
                jnp.arange(n_steps, dtype=jnp.int32))
            bufs = aggregate_records(bufs, axes) if axes else bufs
            return toks, bufs                   # (n_steps, B), slot records

        if seeded:
            decode_scan = _vec_scan
        else:
            # keep the pre-PR9 signature (and shard specs) when unseeded
            def decode_scan(params, cache, tok0, key0, pos0, budget, bmax,
                            dyn):
                return _vec_scan(params, cache, tok0, key0, pos0, budget,
                                 bmax, dyn, jnp.zeros_like(pos0))

    if mesh is not None:
        decode_scan = jax.shard_map(decode_scan, mesh=mesh,
                                    in_specs=in_specs, out_specs=out_specs,
                                    check_vma=False)
    fn = jax.jit(decode_scan)
    _ADAPTIVE_FNS[key] = fn
    return fn


def _generate_fused_adaptive(params, cache, tok, key, pos0, budget, B, cfg,
                             scfg: ServeConfig, par, adaptive, mesh,
                             seeds=None):
    """Whole adaptive decode loop as one dispatch: run the telemetry-carrying
    scan, then fold each observed slot's fleet record into the controller (in
    step order, matching the stepwise loop's observe sequence)."""
    n_steps = scfg.max_new_tokens - 1
    if n_steps <= 0:
        return tok[:, None]
    k_obs = max(1, int(scfg.observe_every))
    fn = _adaptive_decode_fn(cfg, par, n_steps, scfg.temperature, k_obs,
                             mesh, cache, B,
                             tile_rows=getattr(adaptive, "tile_rows", 0),
                             vectorized=budget is not None,
                             eos_id=scfg.eos_id, seeded=seeds is not None)
    if budget is None:
        toks, bufs = fn(params, cache, tok, key, pos0, adaptive.dyn_tree())
    elif seeds is not None:
        toks, bufs = fn(params, cache, tok, key, pos0, budget,
                        jnp.max(budget), adaptive.dyn_tree(), seeds)
    else:
        toks, bufs = fn(params, cache, tok, key, pos0, budget,
                        jnp.max(budget), adaptive.dyn_tree())
    out = jnp.concatenate([tok[:, None], jnp.swapaxes(toks, 0, 1)], axis=1)
    bufs = jax.device_get(bufs)
    for j in range(-(-n_steps // k_obs)):
        adaptive.observe({t: {k: v[j] for k, v in rec.items()}
                          for t, rec in bufs.items()})
    return out


def _generate_stepwise(params, cache, tok, key, pos0, budget, cfg,
                       scfg: ServeConfig, par, adaptive, param_hook,
                       seeds=None):
    """One host-dispatched decode step per token: the adaptive/telemetry path
    and the ``param_hook`` path (also the fused paths' correctness oracle).
    ``budget is None`` is the scalar (pre-PR5) loop; otherwise positions,
    done-flags and gated cache writes mirror the vectorized scans exactly
    (bit-identical tokens and telemetry, including the ``i < max(budget)``
    observe gate — budget-driven and EOS-agnostic, like the scans').  With
    ``scfg.eos_id`` the host folds ``tok != eos`` into the done-flags (one
    device sync per step — this is the oracle path); with ``seeds`` it
    tracks per-slot token counters and samples from the per-request
    streams."""
    out = [tok]
    vec = budget is not None
    eos = scfg.eos_id
    assert (eos is None and seeds is None) or vec, \
        "eos_id / slot_seeds need the vectorized (per-slot) stepwise loop"

    if adaptive is None:
        step_fn = jax.jit(lambda p, c, t, i, m: decode_step(
            p, c, t, i, cfg, par, write_mask=m))
    else:
        from repro.runtime import ax_scope

        # telemetry records are per-projection-call outputs of the compiled
        # step; under lax.scan over layers they would be stuck inside the scan
        # body, so the adaptive decode unrolls the (short) period stack.
        # Routing per-layer telemetry through scan carries is a ROADMAP
        # follow-on.
        dec_par = dataclasses.replace(par or ParallelConfig(), scan_layers=False)
        tile_rows = getattr(adaptive, "tile_rows", 0)

        def _adaptive_step(p, c, t, i, m, dyn, gate):
            with ax_scope(dyn, collect=True, gate=gate,
                          tile_rows=tile_rows) as sc:
                logits, new_cache = decode_step(p, c, t, i, cfg, dec_par,
                                                write_mask=m)
                return logits, new_cache, sc.collected()

        step_fn = jax.jit(_adaptive_step)

    sample = _sampler(scfg)
    k_obs = max(1, int(scfg.observe_every))
    budget_np = np.asarray(budget) if vec else None
    pos = pos0
    nt = jnp.ones_like(pos0) if vec else None   # token 0 sampled at prefill
    pending = None   # one-step-stale observe: fetch step i-1's telemetry only
    for i in range(scfg.max_new_tokens - 1):   # after step i is dispatched, so
        key, sub = jax.random.split(key)       # async dispatch stays pipelined
        if param_hook is not None:
            params = param_hook(i, params)
        if vec:
            active_np = i < budget_np          # (B,) host-known done-flags
            if eos is not None:
                # oracle-path sync: read the (frozen) carry token and fold
                # the EOS mask in, exactly as the scans derive it in-graph
                active_np = active_np & (np.asarray(tok) != eos)
            active = jnp.asarray(active_np)
            alive = bool(i < budget_np.max())  # == the scans' i < bmax gate
        else:
            active, alive = None, True
        idx = pos if vec else jnp.int32(pos + i)
        if adaptive is None:
            logits, cache = step_fn(params, cache, tok[:, None], idx, active)
        else:
            gate = (i % k_obs == 0) and alive
            logits, cache, telem = step_fn(
                params, cache, tok[:, None], idx, active,
                adaptive.dyn_tree(), jnp.bool_(gate)
            )
            if pending is not None:
                adaptive.observe(jax.device_get(pending))
                pending = None
            if gate:       # off-steps produced zero records (lax.cond) —
                pending = telem   # never surface them to the controller
        if vec:
            if seeds is not None:
                nxt = slot_sample(logits[:, -1], seeds, nt, scfg.temperature)
            else:
                nxt = sample(logits, sub)
            tok = jnp.where(active, nxt, tok)
            pos = pos + active.astype(jnp.int32)
            nt = nt + active.astype(jnp.int32)
        else:
            tok = sample(logits, sub)
        out.append(tok)
    if pending is not None:
        adaptive.observe(jax.device_get(pending))
    return jnp.stack(out, axis=1)


# ---------------------------------------------------------------------------
# token-granular serving: one compiled per-step decode + per-bucket prefill
# ---------------------------------------------------------------------------

# token-step program cache: (cfg, par, temperature, adaptive?, k_obs-free —
# the gate is a traced bool, mesh, cache treedef, batch, tile_rows) ->
# jitted step.  ONE entry serves the whole trace: mid-flight admissions and
# policy updates change traced values only (tests assert _cache_size() == 1).
_TOKEN_FNS = {}


def _token_step_fn(cfg, par, temperature: float, adaptive: bool, mesh,
                   cache, batch: int, tile_rows: int = 0,
                   eos_id: Optional[int] = None, seeded: bool = False):
    """Build (and cache) the jitted token-granular decode step:
    ``(params, cache, tok, sub, pos, active[, dyn, gate][, seeds, nt]) ->
    (tok', cache'[, telem])``.

    Decode + sampling + per-slot freeze run as one dispatch per token for
    the WHOLE slot batch; ``pos`` is the (B,) per-slot position vector and
    ``active`` the (B,) done-flags (False slots keep their token, skip
    their cache write, and — all-False — skip the telemetry summary).
    With ``eos_id`` the step folds ``tok != eos`` into the done-flags
    in-graph — the host retires EOS slots at the step boundary anyway, but
    the traced guard makes an EOS-frozen slot inert even if the host flags
    it active.  With ``seeded`` the step takes (B,) per-request ``seeds``
    and token counters ``nt`` and samples from the per-request streams
    (:func:`slot_sample`) instead of the shared ``sub`` key.
    Under ``mesh`` the step is shard_map'd over the mesh batch axes with
    in-graph telemetry aggregation, exactly like the fused adaptive scan.
    """
    treedef = jax.tree_util.tree_structure(cache)
    fkey = (cfg, par, temperature, adaptive, mesh, treedef, batch, tile_rows,
            eos_id, seeded)
    if fkey in _TOKEN_FNS:
        return _TOKEN_FNS[fkey]
    obs.count_retrace("token_step")       # cache miss == new compiled program

    sample = _sampler(ServeConfig(temperature=temperature))
    if mesh is not None:
        from repro.fleet.collect import aggregate_records, token_step_specs

        in_specs, out_specs, axes = token_step_specs(cache, batch, mesh,
                                                     seeded=seeded)
    else:
        axes = ()

    def _mask(active, tok):
        return active & (tok != eos_id) if eos_id is not None else active

    def _next(logits, sub, seeds, nt):
        with jax.named_scope("sample"):
            if seeded:
                return slot_sample(logits[:, -1], seeds, nt, temperature)
            return sample(logits, sub)

    if adaptive:
        from repro.runtime import ax_scope

        dec_par = dataclasses.replace(par or ParallelConfig(),
                                      scan_layers=False)

        # the host only steps a batch with >= 1 live slot (the scheduler's
        # drain loop), so `gate` alone is the full observe condition — and
        # unlike an in-graph any(active) it is identical on every shard
        def step(params, cache, tok, sub, pos, active, dyn, gate,
                 seeds=None, nt=None):
            active = _mask(active, tok)
            with ax_scope(dyn, collect=True, gate=gate,
                          tile_rows=tile_rows) as sc:
                logits, cache = decode_step(params, cache, tok[:, None],
                                            pos, cfg, dec_par,
                                            write_mask=active)
                telem = sc.collected()
            tok = jnp.where(active, _next(logits, sub, seeds, nt), tok)
            telem = aggregate_records(telem, axes) if axes else telem
            return tok, cache, telem
    else:
        assert mesh is None, "mesh= requires the adaptive token step"

        def step(params, cache, tok, sub, pos, active, seeds=None, nt=None):
            active = _mask(active, tok)
            logits, cache = decode_step(params, cache, tok[:, None], pos,
                                        cfg, par, write_mask=active)
            return jnp.where(active, _next(logits, sub, seeds, nt), tok), cache

    if mesh is not None:
        step = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    fn = jax.jit(step)          # traces and the benchmark name it jit_step
    _TOKEN_FNS[fkey] = fn
    return fn


def token_step(params, cache, tok, sub, pos, active, cfg: ModelConfig,
               par: Optional[ParallelConfig] = None, *, temperature: float = 0.0,
               adaptive=None, mesh=None, gate=True, eos_id: Optional[int] = None,
               seeds=None, nt=None):
    """One token-granular decode step (see :func:`_token_step_fn`).

    Returns ``(tok', cache')`` — plus the telemetry record tree when
    ``adaptive`` is attached (pass it to ``adaptive.observe`` after a
    ``device_get``; off-``gate`` steps return lax.cond zeros that must not
    reach the controller, mirroring the stepwise loop).

    ``eos_id`` arms the in-graph EOS guard; ``seeds``/``nt`` (passed
    together) select per-request-stream sampling — both are part of the
    compiled program's cache key, so a serve that uses them consistently
    still runs ONE program for its whole trace.
    """
    B = int(tok.shape[0])
    assert (seeds is None) == (nt is None), "seeds and nt come together"
    fn = _token_step_fn(cfg, par, temperature, adaptive is not None, mesh,
                        cache, B, tile_rows=getattr(adaptive, "tile_rows", 0),
                        eos_id=eos_id, seeded=seeds is not None)
    extra = () if seeds is None else (jnp.asarray(seeds, jnp.int32),
                                      jnp.asarray(nt, jnp.int32))
    if adaptive is None:
        return fn(params, cache, tok, sub, pos, active, *extra)
    with obs.span("policy_tree", cat="runtime"):
        dyn = adaptive.dyn_tree()
    return fn(params, cache, tok, sub, pos, active, dyn, jnp.bool_(gate),
              *extra)


@functools.lru_cache(maxsize=64)
def _prefill_one_fn(cfg, par, bucket: int, max_cache_len: int,
                    temperature: float, seeded: bool = False):
    """Jitted single-request prefill for one prompt bucket: pad-masked
    forward, first token sampled at the last real position, cache padded to
    the shared ``max_cache_len`` so it splices straight into any slot of
    the token-granular decode cache.  ``seeded`` samples the first token as
    index 0 of the request's RNG stream (:func:`slot_sample`) instead of
    from the shared key."""
    obs.count_retrace("prefill")        # lru miss == new compiled program
    sample = _sampler(ServeConfig(temperature=temperature))

    # the program's name in traces is jit_prefill_bucket (both variants)
    if seeded:
        @jax.jit
        def prefill_bucket(params, toks, lens, seed):
            logits, cache = prefill(params, {"tokens": toks}, cfg, par,
                                    max_cache_len=max_cache_len,
                                    prompt_lens=lens)
            lg = logits[jnp.arange(toks.shape[0]), lens - 1]
            with jax.named_scope("sample"):
                first = slot_sample(lg, seed, jnp.zeros_like(seed),
                                    temperature)
            return first, cache

        return prefill_bucket

    @jax.jit
    def prefill_bucket(params, toks, lens, key):
        logits, cache = prefill(params, {"tokens": toks}, cfg, par,
                                max_cache_len=max_cache_len,
                                prompt_lens=lens)
        lg = logits[jnp.arange(toks.shape[0]), lens - 1][:, None]
        with jax.named_scope("sample"):
            return sample(lg, key), cache

    return prefill_bucket


def prefill_one(params, tokens, length: int, cfg: ModelConfig,
                par: Optional[ParallelConfig] = None, *, max_cache_len: int,
                temperature: float = 0.0, key=None, seed=None):
    """Prefill ONE padded request ``tokens`` (1, bucket) with real length
    ``length``; returns ``(first_token (1,), cache)`` with the cache padded
    to ``max_cache_len``.  Compiled once per prompt bucket (the seeded and
    shared-key samplers are distinct bucket programs).  ``seed`` selects
    per-request-stream sampling: the first token is drawn as index 0 of the
    stream keyed on ``seed``, matching :func:`generate`'s ``slot_seeds``
    first-token draw bit-exactly."""
    fn = _prefill_one_fn(cfg, par, int(tokens.shape[1]), int(max_cache_len),
                         temperature, seed is not None)
    toks = jnp.asarray(tokens, jnp.int32)
    lens = jnp.asarray([length], jnp.int32)
    if seed is not None:
        return fn(params, toks, lens, jnp.asarray([seed], jnp.int32))
    if key is None:
        key = jax.random.PRNGKey(0)
    return fn(params, toks, lens, key)


def splice_slot(cache, fresh, slot):
    """Write single-request decode-cache ``fresh`` (batch dim 1) into row
    ``slot`` of the slot-batched ``cache`` — the mid-flight admission
    splice.  The batch dim is axis 1 for scan-stacked ``stack/`` leaves and
    axis 0 elsewhere (same layout rule as ``fleet.collect.cache_pspecs``);
    ``slot`` is traced, so one compiled program serves every slot."""

    def one(path, big, small):
        bdim = 1 if (path and getattr(path[0], "key", None) == "stack") else 0
        start = [jnp.int32(0)] * big.ndim
        start[bdim] = jnp.asarray(slot, jnp.int32)
        return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                            tuple(start))

    return jax.tree_util.tree_map_with_path(one, cache, fresh)


_SPLICE_FN = jax.jit(splice_slot)


def splice_slot_jit(cache, fresh, slot):
    """Jitted :func:`splice_slot` (one program per cache treedef)."""
    return _SPLICE_FN(cache, fresh, jnp.int32(slot))
