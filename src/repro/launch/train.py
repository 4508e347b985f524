"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b --smoke \
        --steps 200 --batch 8 --seq 128 [--ax] [--ckpt-dir /tmp/ck]

``--smoke`` uses the reduced same-family config (CPU-runnable ~100M-class
with --d-model overrides); omit it on real hardware for the full config.
Supervised: checkpoints every N steps, restarts on failure, straggler log.
With ``--adaptive``, re-tuned SwapPolicies are versioned into
``<ckpt_dir>/policy`` (the fleet ``PolicyStore`` format) and a restarted job
resumes the adapted policy, not the offline-tuned one.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, ParallelConfig, reduced
from repro.configs.base import AxPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.train import (
    AdamWConfig,
    DataConfig,
    FaultConfig,
    SyntheticStream,
    init_train_state,
    make_train_step,
    run_supervised,
)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--compress", default="none", choices=["none", "bf16"])
    ap.add_argument("--ax", action="store_true", help="SWAPPER approximate matmuls")
    ap.add_argument("--tile-rows", type=int, default=0, metavar="N",
                    help="per-row-tile adaptation granularity for --adaptive "
                         "(0 = scalar configs)")
    ap.add_argument("--adaptive", action="store_true",
                    help="online adaptive SWAPPER (telemetry + drift re-tune)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = reduced(cfg)
    if args.ax or args.adaptive:
        cfg = dataclasses.replace(cfg, ax=AxPolicy(backend="mxu"))
    par = ParallelConfig(remat=args.remat, grad_accum=args.grad_accum, fsdp=False,
                         seq_shard=False)
    if args.adaptive:
        print(f"[adaptive] forcing scan_layers=False, remat=none (was "
              f"{args.remat}), grad_accum=1 (was {args.grad_accum}): telemetry "
              f"records must be outer-trace outputs (see train_step)")
        par = dataclasses.replace(par, scan_layers=False, remat="none", grad_accum=1)
    opt = AdamWConfig(lr=args.lr, compress=args.compress)

    stream = SyntheticStream(
        DataConfig(cfg.vocab, args.seq, args.batch, seed=0, mode="arith")
    )
    step = jax.jit(make_train_step(cfg, par, opt, adaptive=args.adaptive,
                                   tile_rows=args.tile_rows),
                   donate_argnums=(0,))

    if args.adaptive:
        import os

        from repro.fleet import PolicyStore
        from repro.runtime import AdaptiveController, SwapPolicy

        # policy checkpointing rides the PolicyStore format alongside the
        # train checkpoints: every re-tune publishes a new version under
        # <ckpt_dir>/policy, and an elastic restart resumes the *adapted*
        # policy instead of reverting to the offline-tuned one
        store = PolicyStore(os.path.join(args.ckpt_dir, "policy"))
        from repro.runtime import AdaptiveConfig

        controller = AdaptiveController(
            SwapPolicy.from_ax_policy(cfg.ax), targets=cfg.ax.targets,
            cfg=AdaptiveConfig(tile_rows=args.tile_rows),
            log_fn=lambda line: print(f"[adaptive] {line}"), store=store,
        )
        if controller.resume_from_store():
            print(f"[adaptive] resumed policy v{store.current_version()} "
                  f"from {store.root}")
        controller.warmup()

        pending = [None]   # one-step-stale observe keeps dispatch pipelined

        def step_fn(state, batch):
            state, metrics = step(state, jax.tree.map(jnp.asarray, batch),
                                  controller.dyn_tree())
            telem = metrics.pop("ax_telemetry")
            if pending[0] is not None:
                controller.observe(jax.device_get(pending[0]))
            pending[0] = telem
            return state, metrics
    else:
        def step_fn(state, batch):
            return step(state, jax.tree.map(jnp.asarray, batch))

    def make_state():
        params = init_params(jax.random.PRNGKey(0), cfg)
        n = sum(x.size for x in jax.tree.leaves(params))
        print(f"arch={cfg.name} params={n/1e6:.1f}M ax={'on' if cfg.ax else 'off'}")
        return init_train_state(params, opt)

    t0 = time.time()

    def on_step(i, metrics):
        if (i + 1) % args.log_every == 0:
            print(f"step {i+1}: loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({(time.time()-t0)/(i+1):.3f}s/step)")

    state, log = run_supervised(
        make_state, step_fn, stream, args.steps,
        FaultConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
        on_step=on_step,
    )
    if args.adaptive and pending[0] is not None:
        controller.observe(jax.device_get(pending[0]))   # flush final step
        print(f"[adaptive] {controller.telemetry.describe()}")
        print(f"[adaptive] re-tunes: {len(controller.retunes)} "
              f"store v{store.current_version()} "
              f"final {controller.policy.describe()}")
    print(f"done: {log}")


if __name__ == "__main__":
    main()
