"""Serving driver: batched prefill + decode with the sharded KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
        --batch 4 --prompt-len 64 --new-tokens 32 [--ax] [--adaptive]

``--adaptive`` attaches the online adaptive SWAPPER runtime: the decode step
streams operand/error telemetry, a drift detector scores the live operand
distribution against the one the policy was tuned on, and on drift the
controller re-tunes the swap config in place — zero recompilations.  In
``--smoke`` mode a synthetic distribution drift is injected mid-generation
(``--drift-at``) to exercise the loop end-to-end.

``--tile-rows N`` (with ``--adaptive`` or ``--fleet``) switches the runtime
to per-row-tile granularity: projections serve (N, 1, 3) swap-config grids,
telemetry is collected per row tile, and tile-granular re-tunes publish
``SwapPolicy.tile_grids`` — all with zero recompiles (see
docs/architecture.md).

``--fleet N`` instead runs the mesh-native serving stack: an N-replica
("data",) mesh, the continuous-batching scheduler admitting variable-length
synthetic requests into fixed-shape decode slots, one fused adaptive
``lax.scan`` dispatch per wave with in-graph (psum) telemetry aggregation,
and re-tunes published through the versioned ``PolicyStore``
(``--policy-store``); each logical replica's ``PolicyReader`` staleness
(store versions behind CURRENT) is reported at the end.  On CPU, force
replicas with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

``--token-granular`` (with ``--fleet``) switches the batcher to
token-granular continuous batching: decode runs one compiled per-step
program with per-slot cache positions, and a finished slot admits the next
FIFO request *mid-flight* — its prompt is pad-mask prefilled into the
slot's cache region and spliced into the running batch at the next step
boundary (zero recompiles; per-request tokens bit-identical to the
wave-granular oracle under greedy decoding AND per-request-seeded
temperature sampling).  ``--eos-id TOK`` retires a slot the moment it
samples TOK, ``--arrival-rate RPS`` replays a Poisson arrival trace
through ``run_arrivals`` (queueing delay measured), and
``--async-admission`` overlaps freed-slot prefills with the running
decode (PR 9).

``--chaos-plan PATH`` (with ``--fleet``) installs a ``fleet.chaos``
``FaultPlan`` for the run: deterministic injected faults (torn publishes,
poisoned telemetry, replica kills, stalls) exercise the guarded-rollout /
quarantine / store-recovery paths end-to-end (docs/robustness.md).  The
fleet controller runs with ``canary=True``: retune winners are holdout-
canaried before promotion and regressed adoptions auto-roll back.

Observability (``repro.obs``, see docs/observability.md): ``--metrics-port``
serves live Prometheus ``/metrics`` (``--metrics-hold`` keeps it up after
the run), ``--obs-dir`` writes a Chrome-trace timeline + metric snapshots
at exit, ``--device-trace`` adds a jax.profiler device trace; any of them
also turns on XLA-compile accounting via ``jax.monitoring``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import ARCHS, reduced
from repro.configs.base import AxPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import ServeConfig, generate


@contextlib.contextmanager
def _observability(args):
    """Driver-level observability setup (all opt-in, see docs/observability.md):

    * ``--metrics-port P`` — serve ``/metrics`` (Prometheus text) from a
      stdlib http.server thread for the whole run; ``--metrics-hold S``
      keeps the process alive S extra seconds after serving finishes so an
      external scraper can land at least one scrape.
    * ``--obs-dir DIR`` — install a trace recorder and, at exit, write
      ``DIR/trace.json`` (Chrome trace: load in chrome://tracing/Perfetto),
      ``DIR/metrics.prom`` (final Prometheus snapshot) and one JSON line in
      ``DIR/metrics.jsonl``.
    * ``--device-trace DIR`` — additionally wrap the run in a
      ``jax.profiler`` device trace (heavyweight XLA/TensorBoard dump).

    * ``--statsd HOST:PORT`` — push the registry as StatsD/DogStatsD lines
      over UDP at exit (``--statsd-mirror FILE`` additionally appends every
      line to FILE — the CI artifact, immune to UDP loss).
    * ``--otlp-out PATH|URL`` — push one OTLP-JSON ``resourceMetrics``
      payload to a ``.jsonl`` file (or POST it to an ``http(s)://``
      collector endpoint) at exit.

    Any of these also installs the ``jax.monitoring`` compile listener, so
    ``repro_jax_compiles_total`` counts every XLA backend compile.  At exit
    the bucket-coverage check runs: any histogram family whose +Inf bucket
    swallowed >5% of its observations warns loudly."""
    enabled = (args.metrics_port is not None or args.obs_dir
               or args.device_trace or args.statsd or args.otlp_out)
    if not enabled:
        yield
        return
    obs.install_jax_compile_listener()
    server = (obs.start_metrics_server(args.metrics_port)
              if args.metrics_port is not None else None)
    if server is not None:
        print(f"[obs] serving /metrics on port {server.port}")
    exporters = []
    if args.statsd:
        exporters.append(obs.StatsdExporter.from_spec(
            args.statsd, mirror=args.statsd_mirror))
        print(f"[obs] statsd push -> udp://{args.statsd}"
              + (f" (mirror {args.statsd_mirror})"
                 if args.statsd_mirror else ""))
    if args.otlp_out:
        exporters.append(obs.OtlpJsonExporter(args.otlp_out))
        print(f"[obs] otlp-json push -> {args.otlp_out}")
    rec = None
    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
        rec = obs.TraceRecorder()
        obs.install_recorder(rec)
    dev = (obs.device_trace(args.device_trace) if args.device_trace
           else contextlib.nullcontext())
    try:
        with dev:
            yield
    finally:
        if args.obs_dir:
            obs.install_recorder(None)
            rec.save(os.path.join(args.obs_dir, "trace.json"))
            with open(os.path.join(args.obs_dir, "metrics.prom"), "w") as f:
                f.write(obs.prometheus_text())
            obs.write_snapshot(os.path.join(args.obs_dir, "metrics.jsonl"),
                               run=" ".join(
                                   f"{k}={v}" for k, v in sorted(
                                       vars(args).items()) if v))
            print(f"[obs] trace + metrics snapshots written to {args.obs_dir}")
        if exporters:
            n = obs.push_all(exporters)
            print(f"[obs] pushed {n} payload units through "
                  f"{len(exporters)} backend(s)")
            for e in exporters:
                e.close()
        findings = obs.default_registry().check_bucket_coverage()
        if findings:
            print(f"[obs] {len(findings)} histogram series exceeded the "
                  f"+Inf-bucket coverage threshold (see warnings)")
        if server is not None:
            if args.metrics_hold > 0:
                print(f"[obs] holding /metrics open {args.metrics_hold}s")
                time.sleep(args.metrics_hold)
            server.close()


def _drift_hook(at_step: int, scale: float):
    """Returns a param_hook that, at ``at_step``, rescales every other row of
    the weights' *input* (second-to-last) axis.  Weight quantization groups
    reduce over exactly that axis, so an alternating pattern *within* each
    group shifts the int8 code (bit-occupancy) distribution of the quantized
    weights directly — uniform whole-column scaling would be quantization
    invariant.  A controlled stand-in for live traffic drift (it also
    perturbs downstream activations)."""
    done = {"fired": False}

    def hook(step, params):
        if step != at_step or done["fired"]:
            return params
        done["fired"] = True

        def perturb(w):
            if w.ndim < 2:
                return w
            mask = (jnp.arange(w.shape[-2]) % 2 == 0)[:, None]
            return jnp.where(mask, w * scale, w)

        print(f"[drift] step {step}: injected synthetic weight drift (x{scale})")
        return jax.tree.map(perturb, params)

    return hook


def fleet_requests(args, cfg):
    """The ``--fleet`` synthetic traffic, seeded: ``args.requests`` prompts
    of ``prompt_len/2 .. prompt_len`` random tokens (one prompt bucket),
    each asking for ``1 .. new_tokens`` tokens."""
    from repro.fleet import Request

    rng = np.random.default_rng(0)
    requests = []
    for rid in range(args.requests):
        L = int(rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1))
        requests.append(Request(rid, rng.integers(0, cfg.vocab, L),
                                max_new=int(rng.integers(1, args.new_tokens + 1))))
    return requests


def _run_fleet(args, cfg):
    """The mesh-native serving stack: fleet mesh + continuous batcher +
    policy store (see module docstring).  ``args`` carries the ``--fleet``
    options of :func:`main`.  Returns ``(completions, batcher stats)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.fleet import (BatcherConfig, ContinuousBatcher, PolicyReader,
                             PolicyStore)
    from repro.launch.mesh import make_fleet_mesh
    from repro.runtime import AdaptiveConfig, AdaptiveController, SwapPolicy

    from repro.fleet import chaos

    n = args.fleet
    if len(jax.devices()) < n:
        raise SystemExit(
            f"--fleet {n}: only {len(jax.devices())} devices visible; on CPU "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    mesh = make_fleet_mesh(n)
    harness = None
    if args.chaos_plan:
        plan = chaos.FaultPlan.load(args.chaos_plan)
        harness = chaos.install(plan)
        print(f"[chaos] {plan.describe()}")
    # slots must divide over the replica axis: round the default up to a
    # multiple of n
    slots = args.slots or n * max(1, -(-4 // n))
    store = PolicyStore(args.policy_store)
    # the fleet driver runs guarded rollout: retune winners are canaried on
    # a ring-buffer holdout before promotion, and a regressed adoption
    # auto-rolls CURRENT back to last-good (docs/robustness.md)
    controller = AdaptiveController(
        SwapPolicy.from_ax_policy(cfg.ax), targets=cfg.ax.targets,
        cfg=AdaptiveConfig(min_observe_steps=2, cooldown_steps=2,
                           tile_rows=args.tile_rows, canary=True),
        store=store,
        log_fn=lambda line: print(f"[fleet] {line}"))
    resumed = controller.resume_from_store()
    print(f"[fleet] mesh={mesh.shape} slots={slots} store={store.root} "
          f"{'resumed v' + str(store.current_version()) if resumed else 'fresh'}")
    controller.warmup()
    # SLO/error-budget engine: latency objectives on the batcher's TTFT/e2e
    # stream plus per-target MAE guard bands anchored to the controller's
    # drift reference; a burning QoR SLO re-arms the rollback guard and
    # vetoes canary promotion (docs/observability.md)
    slo = obs.SLOEngine(obs.default_serving_slos(qor_targets=cfg.ax.targets),
                        audit=controller.audit)
    controller.attach_slo(slo)

    # one jitted init writes the weights straight into their replicated
    # placement: no f32 intermediate per tensor, no copy per replica later
    params = jax.jit(init_params, static_argnums=1,
                     out_shardings=NamedSharding(mesh, P()))(
                         jax.random.PRNGKey(0), cfg)
    bcfg = BatcherConfig(n_slots=slots,
                         prompt_buckets=(args.prompt_len,),
                         new_token_bucket=args.new_tokens,
                         temperature=args.temperature,
                         token_granular=args.token_granular,
                         eos_id=args.eos_id,
                         async_admission=args.async_admission)
    bat = ContinuousBatcher(params, cfg, bcfg, adaptive=controller, mesh=mesh)
    del params          # the batcher holds the prepared weights in their place
    bat.attach_slo(slo)
    # one logical PolicyReader per replica: they adopt the policy current at
    # spin-up and then surface the staleness metric (versions behind
    # CURRENT) until their next poll — the fleet lag monitor
    readers = [PolicyReader(store, cfg.ax.targets, tile_rows=args.tile_rows,
                            name=f"r{i}")
               for i in range(n)]
    requests = fleet_requests(args, cfg)
    source = None
    if args.arrival_rate > 0:
        from repro.fleet import poisson_arrivals
        source = poisson_arrivals(requests, args.arrival_rate, seed=0)
        print(f"[fleet] arrival trace: {len(source)} requests @ "
              f"{args.arrival_rate} req/s (Poisson, seed 0)")
    else:
        for r in requests:
            bat.submit(r)
    t0 = time.time()
    done = []
    while True:                # supervise the drain: an injected replica
        try:                   # kill restarts it (faults fire once per plan)
            done.extend(bat.run_arrivals(source) if source is not None
                        else bat.run())
            break
        except chaos.InjectedFault as e:
            print(f"[chaos] survived injected crash ({e}); resuming drain")
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    print(f"[fleet] {bat.describe()}")
    print(f"[fleet] served {len(done)} requests / {toks} tokens in {dt:.2f}s "
          f"(incl. compile)")
    ls = bat.latency_summary()
    if "queue_delay_p99" in ls:
        print(f"[fleet] queue delay p50={ls['queue_delay_p50']:.4f}s "
              f"p99={ls['queue_delay_p99']:.4f}s "
              f"ttft p99={ls.get('ttft_p99', float('nan')):.4f}s")
    if bat.stats.get("eos_retired"):
        print(f"[fleet] eos-retired: {bat.stats['eos_retired']}")
    print(f"[fleet] {controller.telemetry.describe()}")
    print(f"[fleet] {bat.qor.describe()}")
    print(f"[fleet] {slo.describe()}")
    print(f"[fleet] re-tunes: {len(controller.retunes)} "
          f"tile re-tunes: {len(controller.tile_retunes)} "
          f"store v{store.current_version()} {controller.policy.describe()}")
    stale = [r.staleness() for r in readers]
    print("[fleet] replica staleness (versions behind CURRENT): "
          + " ".join(f"r{i}=v{r.version}+{s}" for i, (r, s)
                     in enumerate(zip(readers, stale))))
    for i, r in enumerate(readers):
        try:
            r.poll()
        except chaos.InjectedFault as e:
            print(f"[chaos] reader r{i} survived injected crash ({e}); "
                  f"re-polling")
            r.poll()
    print(f"[fleet] after poll: staleness="
          f"{[r.staleness() for r in readers]} (all replicas adopted "
          f"v{store.current_version()})")
    if harness is not None:
        print(f"[chaos] {harness.describe()}")
        if controller.rollbacks:
            print(f"[chaos] rollbacks: {controller.rollbacks}")
        chaos.uninstall()
    return done, bat.stats


def build_parser() -> argparse.ArgumentParser:
    """The serving driver's options (``chip_smoke.py`` parses its serving
    arguments with this same parser)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ax", action="store_true")
    ap.add_argument("--adaptive", action="store_true",
                    help="online SWAPPER runtime (telemetry + drift-triggered re-tune)")
    ap.add_argument("--tile-rows", type=int, default=0, metavar="N",
                    help="per-row-tile adaptation granularity (0 = scalar "
                         "configs; N > 0 = N-row-tile config grids + tile "
                         "telemetry, with --adaptive/--fleet)")
    ap.add_argument("--drift-at", type=int, default=None,
                    help="decode step at which to inject synthetic drift "
                         "(default: new_tokens//3 with --adaptive --smoke; -1 disables)")
    ap.add_argument("--drift-scale", type=float, default=0.05)
    ap.add_argument("--policy-out", default=None,
                    help="write the final (possibly re-tuned) SwapPolicy JSON here")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve on an N-replica mesh via the continuous "
                         "batcher + policy store (implies --adaptive)")
    ap.add_argument("--token-granular", action="store_true",
                    help="--fleet: per-slot cache positions + mid-flight "
                         "admission (finished slots splice the next FIFO "
                         "request into the running batch; greedy or "
                         "per-request-seeded sampling)")
    ap.add_argument("--eos-id", type=int, default=None, metavar="TOK",
                    help="--fleet: EOS token id — a slot retires the moment "
                         "it samples TOK (finish='eos'), freeing compute "
                         "before its budget")
    ap.add_argument("--arrival-rate", type=float, default=0.0, metavar="RPS",
                    help="--fleet: submit requests as a Poisson arrival "
                         "trace at RPS req/s (run_arrivals: queueing delay "
                         "measured, engine sleeps when idle) instead of "
                         "pre-loading the queue")
    ap.add_argument("--async-admission", action="store_true",
                    help="--fleet --token-granular: overlap a freed slot's "
                         "prefill with the in-flight decode (dispatch-then-"
                         "splice at the next step boundary)")
    ap.add_argument("--slots", type=int, default=0,
                    help="--fleet decode slots per wave (default max(N, 4))")
    ap.add_argument("--requests", type=int, default=16,
                    help="--fleet synthetic request count")
    ap.add_argument("--policy-store", default="/tmp/repro_policy_store",
                    help="--fleet PolicyStore root directory")
    ap.add_argument("--autotune", action="store_true",
                    help="warmup: quick measured-wall schedule sweep over "
                         "this model's projection signatures; the winning "
                         "KernelSchedule table is installed process-wide "
                         "before serving (kernels.autotune)")
    ap.add_argument("--schedule-store", default=None, metavar="DIR",
                    help="with --autotune: also publish the tuned table to "
                         "this ScheduleStore so fleet replicas adopt it "
                         "(versioned JSON + atomic CURRENT + heartbeat)")
    ap.add_argument("--chaos-plan", default=None, metavar="PATH",
                    help="--fleet: install a fleet.chaos FaultPlan JSON "
                         "(fault-injection run; see docs/robustness.md)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="P",
                    help="serve Prometheus /metrics on this port for the "
                         "whole run (0 = ephemeral, printed at startup)")
    ap.add_argument("--metrics-hold", type=float, default=0.0, metavar="S",
                    help="keep /metrics up S seconds after serving finishes "
                         "(lets an external scraper land a scrape)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="write Chrome trace + Prometheus/JSONL metric "
                         "snapshots here at exit")
    ap.add_argument("--device-trace", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler device trace "
                         "(XLA/TensorBoard dump under DIR; heavyweight)")
    ap.add_argument("--statsd", default=None, metavar="HOST:PORT",
                    help="push the metric registry as StatsD/DogStatsD UDP "
                         "datagrams at exit")
    ap.add_argument("--statsd-mirror", default=None, metavar="FILE",
                    help="also append every StatsD line to FILE (lossless "
                         "CI artifact; requires --statsd)")
    ap.add_argument("--otlp-out", default=None, metavar="PATH|URL",
                    help="push one OTLP-JSON resourceMetrics payload at "
                         "exit: append to PATH (.jsonl) or POST to an "
                         "http(s):// collector endpoint")
    return ap


def main():
    enable_compile_cache()
    args = build_parser().parse_args()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = reduced(cfg)
    if args.ax or args.adaptive or args.fleet:
        cfg = dataclasses.replace(cfg, ax=AxPolicy(backend="mxu"))

    if args.autotune:
        _run_autotune(args, cfg)

    with _observability(args):
        if args.fleet:
            _run_fleet(args, cfg)
        else:
            _run_single(args, cfg)


def _run_autotune(args, cfg):
    """--autotune warmup: quick sweep over the decode-step projection
    signatures this config will dispatch (the ax-targeted matmuls see
    M = batch rows per decode step), install the winners process-wide
    (zero-retrace: the table resolves host-side at trace time, before the
    first serving trace even happens), and optionally publish to a
    ScheduleStore for the rest of the fleet."""
    from repro.kernels import install_table
    from repro.kernels.autotune import ScheduleStore, tune_table

    ax = cfg.ax if cfg.ax is not None else AxPolicy(backend="mxu")
    shapes = {(max(args.batch, 1), cfg.d_model, cfg.d_model)}
    if cfg.d_ff:
        shapes.add((max(args.batch, 1), cfg.d_model, cfg.d_ff))
        shapes.add((max(args.batch, 1), cfg.d_ff, cfg.d_model))
    table, reports = tune_table(sorted(shapes), ax.mult_name,
                                backends=(ax.backend,), quick=True)
    install_table(table)
    for rep in reports:
        print(f"[autotune] {rep['sig']}: {rep['winner']} "
              f"best={rep['best_us']:.0f}us default={rep['default_us']:.0f}us")
    if args.schedule_store:
        v = ScheduleStore(args.schedule_store).publish(table)
        print(f"[autotune] published schedule table v{v} "
              f"({len(table)} entries) to {args.schedule_store}")


def _run_single(args, cfg):
    controller = None
    param_hook = None
    if args.adaptive:
        from repro.runtime import AdaptiveConfig, AdaptiveController, SwapPolicy

        policy = SwapPolicy.from_ax_policy(cfg.ax)
        controller = AdaptiveController(
            policy, targets=cfg.ax.targets,
            cfg=AdaptiveConfig(min_observe_steps=2, cooldown_steps=4,
                               tile_rows=args.tile_rows),
            log_fn=lambda line: print(f"[adaptive] {line}"),
        )
        controller.warmup()
        drift_at = args.drift_at
        if drift_at is None:
            drift_at = args.new_tokens // 3 if args.smoke else -1
        if drift_at >= 0:
            param_hook = _drift_hook(drift_at, args.drift_scale)
        print(f"[adaptive] {policy.describe()}")

    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    if cfg.family == "encdec":
        prompt = {
            "frames": jnp.asarray(rng.normal(0, 1, (args.batch, args.prompt_len,
                                                     cfg.d_model)), jnp.bfloat16),
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab,
                                               (args.batch, 8)), jnp.int32),
        }
    else:
        prompt = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)}

    t0 = time.time()
    out = generate(params, prompt, cfg,
                   ServeConfig(max_new_tokens=args.new_tokens,
                               temperature=args.temperature),
                   adaptive=controller, param_hook=param_hook)
    dt = time.time() - t0
    toks = out.size
    print(f"arch={cfg.name} generated {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s incl. compile)")
    print(np.asarray(out)[:, :16])

    if controller is not None:
        print(f"[adaptive] {controller.telemetry.describe()}")
        print(f"[adaptive] re-tunes: {len(controller.retunes)} "
              f"tile re-tunes: {len(controller.tile_retunes)} "
              f"final {controller.policy.describe()}")
        if args.policy_out:
            controller.policy.save(args.policy_out)
            print(f"[adaptive] policy written to {args.policy_out}")


if __name__ == "__main__":
    main()
