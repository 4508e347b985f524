"""Chip smoke test behind ``chip_smoke.py``: qwen2-72b at its published
widths through the token-granular fleet path (``launch/serve --fleet``).

One chip (no arguments): serve ``TRAFFIC`` through ``serve._run_fleet`` and
check every completion, then take one prefill through the approximate
``mxu`` policy and through the exact bf16 path and report the relative
logit error, and check the policy's int8 matmul on the chip (``mxu`` and
the compiled Pallas kernel) against the ``emul`` reference on the host
CPU.  ``--chips 4``: serve the same requests on one device and on
the four-replica ``("data",)`` mesh in one process, compare the tokens of
every request, and check the in-graph telemetry psum against per-replica
sums.  Any failed check raises :class:`SmokeFailure`; only :func:`main`
insists on a TPU and on the published widths, so tests drive the other
functions at ``reduced()`` size on the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.configs.base import AxPolicy, ModelConfig
from repro.launch.compile_cache import enable_compile_cache

__all__ = ["SmokeFailure", "CompileClock", "smoke_config", "serve",
           "check_served", "prefill_logit_error", "matmul_reference_check",
           "fleet_compare", "psum_check", "main", "TRAFFIC", "PUBLISHED",
           "CUTS"]

ARCH = "qwen2-72b"
# the published qwen2-72b widths (configs/qwen2_72b.py); main() refuses to
# run if the config in the tree ever drifts from them
PUBLISHED = dict(d_model=8192, n_heads=64, n_kv_heads=8, head_dim=128,
                 d_ff=29568, vocab=152064, qkv_bias=True, rope_theta=1e6)
# depth and storage dtype are the only cuts: 2 of 80 layers, and bf16
# weights as a serving deployment stores them (f32 would put 10 GB in the
# untied embedding and head alone)
CUTS = dict(n_layers=2, param_dtype="bfloat16")
# 8 requests, prompts 256..512 tokens in one 512 bucket, 1..32 new tokens,
# 8 decode slots, greedy
TRAFFIC = dict(requests=8, prompt_len=512, new_tokens=32, slots=8)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


class CompileClock:
    """Sums the backend-compile walls JAX reports through ``jax.monitoring``
    (a persistent-cache hit reports its load time instead).  Listeners
    cannot be removed, so make one per process."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name == _COMPILE_EVENT:
            self.seconds += secs


def smoke_config(base: ModelConfig) -> ModelConfig:
    """``base`` with the smoke cuts and the ``--fleet`` ax policy."""
    return dataclasses.replace(base, ax=AxPolicy(backend="mxu"), **CUTS)


def _log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def footprint(cfg: ModelConfig, slots: int, max_cache_len: int) -> dict:
    """Parameter count and bytes, and KV-cache bytes, from shapes alone."""
    from repro.models import init_cache, init_params

    p = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    c = jax.eval_shape(lambda: init_cache(cfg, slots, max_cache_len))
    leaves = jax.tree.leaves(p)
    return dict(params=sum(x.size for x in leaves),
                param_bytes=sum(x.size * x.dtype.itemsize for x in leaves),
                cache_bytes=sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(c)))


def serve(cfg: ModelConfig, fleet: int, clock: CompileClock,
          requests: int, prompt_len: int, new_tokens: int,
          slots: int) -> dict:
    """Serve the ``--fleet`` synthetic traffic through ``_run_fleet`` on a
    ``fleet``-replica mesh, token-granular, with a fresh policy store."""
    from repro.launch.serve import _run_fleet, build_parser, fleet_requests

    with tempfile.TemporaryDirectory() as store:
        args = build_parser().parse_args([
            "--arch", ARCH, "--fleet", str(fleet), "--token-granular",
            "--requests", str(requests), "--prompt-len", str(prompt_len),
            "--new-tokens", str(new_tokens), "--slots", str(slots),
            "--policy-store", store])
        c0, t0 = clock.seconds, time.perf_counter()
        done, stats = _run_fleet(args, cfg)
        wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    return dict(done=done, stats=dict(stats),
                requests=fleet_requests(args, cfg),
                compile_s=compile_s, serve_s=wall - compile_s)


def check_served(res: dict, cfg: ModelConfig) -> int:
    """Every request completed with its full budget of in-vocabulary
    tokens, and the step program never retraced after warm-up.  Returns
    the number of tokens served."""
    want = {r.rid: r.max_new for r in res["requests"]}
    got = {c.rid: c for c in res["done"]}
    if sorted(got) != sorted(want) or len(res["done"]) != len(want):
        raise SmokeFailure(f"completed rids {sorted(got)} != submitted "
                           f"{sorted(want)}")
    for rid, c in got.items():
        toks = np.asarray(c.tokens)
        if c.status != "ok" or len(toks) != want[rid]:
            raise SmokeFailure(f"request {rid}: status {c.status}, "
                               f"{len(toks)}/{want[rid]} tokens")
        if toks.size and (toks.min() < 0 or toks.max() >= cfg.vocab):
            raise SmokeFailure(f"request {rid}: token outside the vocabulary "
                               f"[0, {cfg.vocab}): {toks.tolist()}")
    retraces = res["stats"]["decode_retraces_post_warmup"]
    if retraces > 0:
        raise SmokeFailure(f"{retraces} post-warmup retraces of the step")
    return sum(len(c.tokens) for c in res["done"])


def prefill_logit_error(cfg: ModelConfig, prompt_len: int) -> dict:
    """One prefill of a seeded prompt through ``cfg.ax`` and through the
    exact bf16 path (``ax=None``), same weights: the relative Frobenius
    error of the logits and the share of positions whose top-1 agrees."""
    from repro.models import init_params, prefill

    params = jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (1, prompt_len)), jnp.int32)

    def logits_fn(c):
        return jax.jit(lambda p, t: prefill(
            p, {"tokens": t}, c, max_cache_len=prompt_len)[0][0]
            .astype(jnp.float32))

    approx = logits_fn(cfg)(params, tokens)
    exact = logits_fn(dataclasses.replace(cfg, ax=None))(params, tokens)

    @jax.jit
    def compare(a, e):
        return dict(
            finite=jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(e)),
            rel_err=jnp.linalg.norm(a - e) / jnp.linalg.norm(e),
            top1=jnp.mean(jnp.argmax(a, -1) == jnp.argmax(e, -1)))

    out = {k: v.item() for k, v in jax.device_get(compare(approx, exact)).items()}
    if not out["finite"]:
        raise SmokeFailure("non-finite prefill logits")
    if not np.isfinite(out["rel_err"]):
        raise SmokeFailure(f"relative logit error {out['rel_err']}")
    return out


def matmul_reference_check(cfg: ModelConfig) -> list:
    """The policy's approximate int8 matmul on the default device, through
    the ``mxu`` factorization and the compiled Pallas ``kernel``, against
    the pure-jnp ``emul`` reference computed on the host CPU, for the
    policy's swap config, a B-side one and NoSwap.  Every path sums int8
    products in int32, so the tolerance is zero.  The input is small
    (128 x 512 @ 512 x 256) because the reference holds every product.
    Returns the backends checked."""
    import repro.runtime as R
    from repro.core.swapper import SwapConfig
    from repro.quant.ax import ax_matmul_int_dyn

    rng = np.random.default_rng(3)
    a = rng.integers(-128, 128, (128, 512)).astype(np.int8)
    b = rng.integers(-128, 128, (512, 256)).astype(np.int8)
    triples = [np.asarray(R.triple_of(s), np.int32)
               for s in (cfg.ax.swap, SwapConfig("B", 3, 1), None)]

    def run(backend, dyn):
        pol = dataclasses.replace(cfg.ax, backend=backend)
        return np.asarray(jax.jit(lambda a, b, d: ax_matmul_int_dyn(
            a, b, pol, d))(a, b, dyn))

    backends = ("mxu", "kernel")
    for dyn in triples:
        with jax.default_device(jax.devices("cpu")[0]):
            ref = run("emul", dyn)
        for backend in backends:
            got = run(backend, dyn)
            if not np.array_equal(got, ref):
                raise SmokeFailure(
                    f"{backend} matmul differs from the emul reference for "
                    f"triple {dyn.tolist()}: {int((got != ref).sum())} of "
                    f"{ref.size} outputs")
    return list(backends)


def psum_check(cfg: ModelConfig, n: int) -> int:
    """The fleet telemetry aggregation (``fleet.collect``) on an ``n``-
    replica mesh: the psum'd record of an operand stream sharded over the
    replicas must equal, exactly, the sum of the records each replica's
    slice gives alone (err_max: their max).  Every field is an integer
    count (bit counts are integer-valued float32, error limbs uint32), so
    the tolerance is zero.  Returns the number of fields compared."""
    import repro.runtime as R
    from repro.core import multipliers as M
    from repro.fleet import make_sharded_summarizer
    from repro.launch.mesh import make_fleet_mesh
    from repro.runtime.telemetry import (MAX_FIELDS, SUM_FIELDS,
                                         combine_records)

    mult = M.get(cfg.ax.mult_name)
    dyn = jnp.asarray(R.triple_of(cfg.ax.swap), jnp.int32)
    N = R.TELEMETRY_SAMPLE
    rng = np.random.default_rng(2)
    a = rng.integers(-127, 128, n * N).astype(np.int32)
    b = rng.integers(-127, 128, n * N).astype(np.int32)
    f = make_sharded_summarizer(mult.name, make_fleet_mesh(n))
    got = jax.device_get(f(jnp.asarray(a), jnp.asarray(b), dyn))
    per_replica = [
        {"t": {k: np.asarray(v)[None] for k, v in jax.device_get(
            R.operand_summary(jnp.asarray(a[s * N:(s + 1) * N]),
                              jnp.asarray(b[s * N:(s + 1) * N]),
                              mult, dyn)).items()}}
        for s in range(n)]
    ref = combine_records(per_replica)["t"]
    fields = [k for k in got if k in SUM_FIELDS + MAX_FIELDS]
    bad = [k for k in fields
           if not np.array_equal(got[k], ref[k].reshape(got[k].shape))]
    if bad or not fields:
        raise SmokeFailure(f"psum'd telemetry != per-replica sums: {bad}")
    return len(fields)


def fleet_compare(cfg: ModelConfig, n: int, clock: CompileClock,
                  **traffic) -> dict:
    """The same requests on one device and on an ``n``-replica mesh, in
    this process.  Tokens must match exactly: a slot's decode reads only
    its own row, the approximate projections accumulate int8 products
    exactly in int32, and the exact bf16 ones reduce over K identically
    whatever the batch split, so a differing token means the sharded
    path computed something else."""
    one = serve(cfg, 1, clock, **traffic)
    check_served(one, cfg)
    gc.collect()                 # release the one-device weights first
    many = serve(cfg, n, clock, **traffic)
    check_served(many, cfg)
    ref = {c.rid: np.asarray(c.tokens) for c in one["done"]}
    differ = [c.rid for c in many["done"]
              if not np.array_equal(np.asarray(c.tokens), ref[c.rid])]
    if differ:
        raise SmokeFailure(f"requests {differ}: {n}-replica tokens differ "
                           f"from one device")
    return dict(one=one, many=many, fields=psum_check(cfg, n))


def _report_serve(tag: str, res: dict, n_tokens: int) -> None:
    _log(f"{tag}: served {len(res['done'])} requests / {n_tokens} tokens; "
         f"cold single-run walls, not metrics: compile {res['compile_s']:.1f} s "
         f"(XLA compile or cache load), serving {res['serve_s']:.1f} s; "
         f"post-warmup retraces {res['stats']['decode_retraces_post_warmup']}")


def main(argv=None) -> int:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the 4-replica fleet path and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    clock = CompileClock()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1
    base = ARCHS[ARCH]
    drift = {k: (getattr(base, k), v) for k, v in PUBLISHED.items()
             if getattr(base, k) != v}
    if drift:
        raise SmokeFailure(f"{ARCH} config differs from the published "
                           f"widths: {drift}")
    cfg = smoke_config(base)
    fp = footprint(cfg, TRAFFIC["slots"],
                   TRAFFIC["prompt_len"] + TRAFFIC["new_tokens"] + 1)
    _log(f"{ARCH} at published widths ({', '.join(f'{k}={v}' for k, v in PUBLISHED.items())}); "
         f"cuts: n_layers {base.n_layers} -> {cfg.n_layers}, param_dtype "
         f"{base.param_dtype} -> {cfg.param_dtype}; ax {cfg.ax.backend} "
         f"{cfg.ax.mult_name} on {'+'.join(cfg.ax.targets)}")
    _log(f"bytes per replica: {fp['params'] / 1e9:.3f} B params = "
         f"{fp['param_bytes'] / 1e9:.2f} GB, KV cache {fp['cache_bytes'] / 1e6:.1f} MB "
         f"({TRAFFIC['slots']} slots x {TRAFFIC['prompt_len'] + TRAFFIC['new_tokens'] + 1} "
         f"positions); traffic {TRAFFIC}")
    _log(f"device: {devs[0].device_kind} x {len(devs)}")
    if args.chips == 1:
        res = serve(cfg, 1, clock, **TRAFFIC)
        _report_serve("fleet 1", res, check_served(res, cfg))
        gc.collect()
        err = prefill_logit_error(cfg, TRAFFIC["prompt_len"])
        _log(f"prefill of {TRAFFIC['prompt_len']} tokens, mxu policy vs exact "
             f"bf16: relative logit error {err['rel_err']:.6f}, top-1 agreement "
             f"{err['top1']:.4f}")
        checked = matmul_reference_check(cfg)
        _log(f"{cfg.ax.mult_name} int8 matmul 128x512x256 on the chip via "
             f"{' and '.join(checked)} equals the emul reference on the host "
             f"CPU for 3 swap configs (tolerance: exact)")
    else:
        res = fleet_compare(cfg, args.chips, clock, **TRAFFIC)
        _report_serve("fleet 1", res["one"], check_served(res["one"], cfg))
        _report_serve(f"fleet {args.chips}", res["many"],
                      check_served(res["many"], cfg))
        _log(f"{len(res['many']['done'])}/{len(res['one']['done'])} requests: "
             f"{args.chips}-replica tokens equal one device's (tolerance: exact); "
             f"psum'd telemetry equals per-replica sums on {res['fields']} "
             f"fields (tolerance: exact)")
    stats = devs[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        _log(f"peak device memory (device 0): "
             f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB of "
             f"{stats.get('bytes_limit', 0) / 1e9:.2f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0
