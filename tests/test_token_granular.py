"""Token-granular serving (PR 5): per-slot cache positions, pad-mask
prefill, done-flag gating, and mid-flight admission.

Layers: vector ``cache_index`` must be value-identical to the scalar path
and per-slot writes maskable.  Engine: pad-masked prompts must generate
bit-identically to the same prompt served unpadded, the fused scans must
honor per-slot budgets, and the fused/stepwise paths must stay mutual
oracles.  Scheduler: token-granular draining must reproduce the wave
oracle's per-request tokens bit-exactly on mixed-length traces with zero
recompiles across splices and policy updates; idle wave slots must
backfill from the next FIFO bucket.  The forced-8-device mesh variant runs
in a subprocess (multidevice lane).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as C
import repro.runtime as R
from repro.configs.base import AxPolicy
from repro.fleet import BatcherConfig, ContinuousBatcher, Request

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _model(backend="mxu", n_layers=2):
    import repro.configs as CFG
    from repro.models import init_params

    cfg = CFG.reduced(CFG.ARCHS["qwen2-72b"])
    cfg = dataclasses.replace(cfg, n_layers=n_layers,
                              ax=AxPolicy(backend=backend))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _controller(cfg, **kw):
    kw.setdefault("cfg", R.AdaptiveConfig(min_observe_steps=10 ** 6))
    return R.AdaptiveController(R.SwapPolicy.from_ax_policy(cfg.ax),
                                targets=cfg.ax.targets, **kw)


# ---------------------------------------------------------------------------
# layers: vector cache_index == scalar path; write_mask keeps slots inert
# ---------------------------------------------------------------------------

def test_vector_cache_index_matches_scalar():
    from repro.models import decode_step, prefill

    cfg, params = _model()
    rng = np.random.default_rng(0)
    B, S = 3, 10
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    _, cache = prefill(params, {"tokens": toks}, cfg, max_cache_len=S + 4)
    t = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    l_s, c_s = decode_step(params, cache, t, jnp.int32(S), cfg)
    l_v, c_v = decode_step(params, cache, t, jnp.full((B,), S, jnp.int32),
                           cfg, write_mask=jnp.ones((B,), bool))
    assert np.array_equal(np.asarray(l_s), np.asarray(l_v))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)), c_s, c_v))


def test_write_mask_keeps_retired_slot_cache_inert():
    from repro.models import decode_step, prefill

    cfg, params = _model()
    rng = np.random.default_rng(1)
    B, S = 3, 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    _, cache = prefill(params, {"tokens": toks}, cfg, max_cache_len=S + 4)
    t = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)), jnp.int32)
    mask = jnp.asarray([True, False, True])
    _, c_m = decode_step(params, cache, t, jnp.full((B,), S, jnp.int32),
                         cfg, write_mask=mask)
    for (path, old), new in zip(
            jax.tree_util.tree_flatten_with_path(cache)[0],
            jax.tree.leaves(c_m)):
        bdim = 1 if getattr(path[0], "key", None) == "stack" else 0
        old, new = np.asarray(old), np.asarray(new)
        assert np.array_equal(old.take(1, bdim), new.take(1, bdim)), path
        assert not np.array_equal(old.take(0, bdim), new.take(0, bdim)), path


# ---------------------------------------------------------------------------
# pad-mask prefill: bit-identical logits at every bucket size, all backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["mxu", "emul", "kernel"])
def test_padmask_prefill_bit_identical_all_buckets(backend):
    """ISSUE satellite: a padded prompt's logits at its real positions must
    equal the unpadded run bit-for-bit, at every bucket size, on all three
    SWAPPER backends."""
    from repro.models import prefill

    cfg, params = _model(backend=backend, n_layers=1 if backend == "kernel" else 2)
    rng = np.random.default_rng(2)
    B, L = 2, 5
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (B, L)), jnp.int32)
    max_len = 24
    lg_ref, _ = prefill(params, {"tokens": prompt}, cfg, max_cache_len=max_len,
                        prompt_lens=jnp.full((B,), L, jnp.int32))
    buckets = (8, 16) if backend == "kernel" else (6, 8, 12, 16)
    for bucket in buckets:
        padded = jnp.concatenate(
            [prompt, jnp.broadcast_to(prompt[:, -1:], (B, bucket - L))], axis=1)
        lg, _ = prefill(params, {"tokens": padded}, cfg, max_cache_len=max_len,
                        prompt_lens=jnp.full((B,), L, jnp.int32))
        assert np.array_equal(np.asarray(lg_ref), np.asarray(lg[:, :L])), (
            backend, bucket)


def test_padmask_generate_matches_unpadded_per_request():
    """Mixed-length padded batch: every slot's generation equals the same
    prompt served alone and unpadded (greedy)."""
    from repro.serve import ServeConfig, generate

    cfg, params = _model()
    rng = np.random.default_rng(3)
    B, bucket, T = 4, 12, 6
    lens = np.asarray([4, 7, 12, 9], np.int32)
    prompts = [rng.integers(0, cfg.vocab, int(L)).astype(np.int32)
               for L in lens]
    batch = np.stack([np.concatenate([p, np.full(bucket - len(p), p[-1],
                                                 np.int32)])
                      for p in prompts])
    max_len = bucket + T + 1
    out = np.asarray(generate(
        params, {"tokens": jnp.asarray(batch)}, cfg,
        ServeConfig(max_new_tokens=T), prompt_lens=lens,
        max_cache_len=max_len))
    for i, p in enumerate(prompts):
        solo = np.asarray(generate(
            params, {"tokens": jnp.asarray(p[None])}, cfg,
            ServeConfig(max_new_tokens=T),
            prompt_lens=np.asarray([len(p)], np.int32),
            max_cache_len=max_len))
        assert np.array_equal(out[i], solo[0]), i


def test_slot_budgets_freeze_and_match_oracle():
    """Per-slot done-flags: a retired slot's token freezes; active prefixes
    are unaffected; fused and stepwise paths agree bit-for-bit."""
    from repro.serve import ServeConfig, generate

    cfg, params = _model()
    rng = np.random.default_rng(4)
    B, S, T = 3, 8, 7
    prompt = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                    jnp.int32)}
    budgets = np.asarray([2, T, 5], np.int32)
    scfg = ServeConfig(max_new_tokens=T)
    full = np.asarray(generate(params, prompt, cfg, scfg))
    out_f = np.asarray(generate(params, prompt, cfg, scfg,
                                slot_new_tokens=budgets))
    out_s = np.asarray(generate(
        params, prompt, cfg, dataclasses.replace(scfg, fused=False),
        slot_new_tokens=budgets))
    assert np.array_equal(out_f, out_s)
    for b in range(B):
        n = int(budgets[b])
        assert np.array_equal(out_f[b, :n], full[b, :n]), b   # live prefix
        assert (out_f[b, n:] == out_f[b, n - 1]).all(), b     # frozen tail


def test_adaptive_fused_with_budgets_matches_stepwise():
    """The adaptive scan's telemetry gating under per-slot budgets mirrors
    the stepwise loop (tokens + telemetry bit-identical)."""
    from repro.serve import ServeConfig, generate

    cfg, params = _model()
    rng = np.random.default_rng(5)
    prompt = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 8)),
                                    jnp.int32)}
    budgets = np.asarray([3, 6], np.int32)
    cA, cB = _controller(cfg), _controller(cfg)
    kw = dict(max_new_tokens=6, observe_every=2)
    o_loop = generate(params, prompt, cfg, ServeConfig(fused=False, **kw),
                      adaptive=cA, slot_new_tokens=budgets)
    o_scan = generate(params, prompt, cfg, ServeConfig(fused=True, **kw),
                      adaptive=cB, slot_new_tokens=budgets)
    assert np.array_equal(np.asarray(o_loop), np.asarray(o_scan))
    sA, sB = cA.telemetry.snapshot(), cB.telemetry.snapshot()
    assert set(sA) == set(sB)
    for t in sA:
        for f in ("mae", "wce", "ep", "n", "n_steps"):
            assert sA[t][f] == sB[t][f], (t, f)
        assert np.array_equal(sA[t]["bit_probs"], sB[t]["bit_probs"]), t


# ---------------------------------------------------------------------------
# scheduler: token-granular vs wave oracle, backfill, zero recompiles
# ---------------------------------------------------------------------------

def _mixed_trace(cfg, n_req, seed=7, max_new=6):
    rng = np.random.default_rng(seed)
    return [Request(rid, rng.integers(0, cfg.vocab, int(rng.integers(3, 17))),
                    max_new=int(rng.integers(1, max_new + 1)))
            for rid in range(n_req)]


def _serve(params, cfg, token_granular, trace, adaptive, n_slots=3, T=6):
    bcfg = BatcherConfig(n_slots=n_slots, prompt_buckets=(8, 16),
                         new_token_bucket=T, token_granular=token_granular)
    bat = ContinuousBatcher(params, cfg, bcfg, adaptive=adaptive)
    for r in trace:
        bat.submit(Request(r.rid, np.asarray(r.tokens).copy(), r.max_new))
    done = bat.run()
    return {c.rid: c.tokens.tolist() for c in done}, bat


def test_token_granular_matches_wave_oracle_bit_exact():
    """ISSUE acceptance: same prompts, same seeds => identical per-request
    tokens between token-granular and wave-granular modes on a mixed-length
    trace, with mid-flight admissions actually happening and occupancy at
    least the wave mode's."""
    from repro.serve import engine as E

    cfg, params = _model()
    trace = _mixed_trace(cfg, 10)
    wave, wave_bat = _serve(params, cfg, False, trace, _controller(cfg))
    n_fns0 = len(E._TOKEN_FNS)
    tok, tok_bat = _serve(params, cfg, True, trace, _controller(cfg))
    assert set(wave) == set(tok) == {r.rid for r in trace}
    for rid in wave:
        assert wave[rid] == tok[rid], rid
    assert tok_bat.stats["splices"] > 0          # admission was mid-flight
    assert tok_bat.occupancy() >= wave_bat.occupancy()
    # one compiled step program for the whole trace (splices retrace nothing)
    new_fns = list(E._TOKEN_FNS.values())[n_fns0:]
    assert len(new_fns) == 1 and new_fns[0]._cache_size() == 1

    # a policy update between traces also reuses the program
    ctrl = _controller(cfg)
    ctrl.policy.set_config("mlp", C.SwapConfig("B", 5, 1))
    tok2, _ = _serve(params, cfg, True, trace, ctrl)
    assert new_fns[0]._cache_size() == 1
    assert any(tok2[r] != tok[r] for r in tok)   # the policy actually bites


def test_token_granular_without_adaptive():
    """The non-adaptive token step (static policy) drains correctly too."""
    cfg, params = _model()
    trace = _mixed_trace(cfg, 6, seed=9)
    wave, _ = _serve(params, cfg, False, trace, None)
    tok, bat = _serve(params, cfg, True, trace, None)
    assert wave == tok
    assert bat.stats["requests"] == 6


def test_prepared_drain_matches_raw_engine(monkeypatch):
    """The batcher serves from weights prepared at load
    (quant.ax.prepare_params): the same token-granular drain with the
    engine fed the raw weights gives the same tokens and the same
    telemetry, and the gauge counts every approximated projection."""
    from repro import obs
    import repro.fleet.scheduler as SCH

    cfg, params = _model()
    trace = _mixed_trace(cfg, 6, seed=3)
    gauge = obs.default_registry().get("repro_ax_prepared_projections")
    c_prep = _controller(cfg)
    prep, bat = _serve(params, cfg, True, trace, c_prep)
    assert gauge.value() == 8            # 2 layers x (in, gate, out, o)
    assert "wfg" in bat.params["layers"]["p0"]["mlp"]["in"]
    monkeypatch.setattr(SCH, "prepare_params", lambda p, cfg: p)
    c_raw = _controller(cfg)
    raw, bat_raw = _serve(params, cfg, True, trace, c_raw)
    assert gauge.value() == 0 and bat_raw.params is params
    assert prep == raw and bat.stats["splices"] > 0
    s_prep, s_raw = c_prep.telemetry.snapshot(), c_raw.telemetry.snapshot()
    assert set(s_prep) == set(s_raw) == set(cfg.ax.targets)
    for t in s_raw:
        assert s_raw[t]["n_steps"] > 0
        for f in ("mae", "wce", "ep", "n", "n_steps"):
            assert s_prep[t][f] == s_raw[t][f], (t, f)
        assert np.array_equal(s_prep[t]["bit_probs"], s_raw[t]["bit_probs"]), t


def test_wave_backfills_idle_slots_from_next_fifo_bucket():
    """ISSUE satellite: idle slots admit the next FIFO requests from other
    buckets (outputs kept) instead of cycling already-admitted prompts."""
    cfg, params = _model()
    rng = np.random.default_rng(11)
    bcfg = BatcherConfig(n_slots=4, prompt_buckets=(8, 16),
                         new_token_bucket=4)
    bat = ContinuousBatcher(params, cfg, bcfg, adaptive=_controller(cfg))
    # one long request (bucket 16) then three short ones (bucket 8): the
    # first wave picks bucket 16 and backfills its 3 idle slots with the
    # short requests, draining everything in ONE wave
    bat.submit(Request(0, rng.integers(0, cfg.vocab, 12), max_new=3))
    for rid in (1, 2, 3):
        bat.submit(Request(rid, rng.integers(0, cfg.vocab, 5), max_new=2))
    done = bat.run()
    assert sorted(c.rid for c in done) == [0, 1, 2, 3]
    assert bat.stats["waves"] == 1
    assert bat.stats["backfilled"] == 3
    assert bat.stats["filler_tokens"] == 0
    # backfilled outputs are real: rid 1 equals its solo-served tokens
    solo = ContinuousBatcher(params, cfg,
                             BatcherConfig(n_slots=4, prompt_buckets=(8, 16),
                                           new_token_bucket=4),
                             adaptive=_controller(cfg))
    rng = np.random.default_rng(11)
    rng.integers(0, cfg.vocab, 12)
    p1 = rng.integers(0, cfg.vocab, 5)
    solo.submit(Request(1, p1, max_new=2))
    (c1,) = solo.run()
    got = {c.rid: c.tokens for c in done}
    assert np.array_equal(got[1], c1.tokens)


def test_wave_retire_order_and_budget_assert():
    cfg, params = _model()
    bat = ContinuousBatcher(
        params, cfg,
        BatcherConfig(n_slots=2, prompt_buckets=(8,), new_token_bucket=4),
        adaptive=_controller(cfg))
    rng = np.random.default_rng(2)
    for rid in range(5):
        bat.submit(Request(rid, rng.integers(0, cfg.vocab,
                                             int(rng.integers(2, 9))),
                           max_new=int(rng.integers(1, 5))))
    with pytest.raises(AssertionError):
        bat.submit(Request(99, np.zeros(4, np.int32), max_new=5))
    done = bat.run()
    assert [c.rid for c in done] == list(range(5))


# ---------------------------------------------------------------------------
# named scopes and phase spans: what traces and profiles can attribute
# ---------------------------------------------------------------------------

def _op_names(hlo_text):
    import re

    return set(re.findall(r'op_name="([^"]+)"', hlo_text))


def test_step_programs_carry_named_scopes_and_stable_names():
    """The optimized token-step and prefill HLO name the approximate
    projections (ax.<target>), the telemetry summaries (ax_telemetry.
    <target>) and the sampler (sample) in their op_name metadata, and the
    programs keep the module names profiles and the benchmark read."""
    from repro.models import init_cache
    from repro.serve import engine as E

    cfg, params = _model()
    ctrl = _controller(cfg)
    B, L = 2, 24
    cache = init_cache(cfg, B, L)
    tok = jnp.zeros(B, jnp.int32)
    pos = jnp.zeros(B, jnp.int32)
    act = jnp.ones(B, bool)
    key = jax.random.PRNGKey(0)
    E.token_step(params, cache, tok, key, pos, act, cfg, adaptive=ctrl)
    step_fn = E._token_step_fn(cfg, None, 0.0, True, None, cache, B)
    step = step_fn.lower(params, cache, tok, key, pos, act, ctrl.dyn_tree(),
                         jnp.bool_(True)).compile().as_text()
    names = _op_names(step)
    for scope in ("ax.mlp", "ax.attn_out", "ax_telemetry.mlp",
                  "ax_telemetry.attn_out", "sample"):
        assert any(f"/{scope}/" in n for n in names), scope
    # the telemetry summary nests inside its projection's scope
    assert any("/ax.mlp/ax_telemetry.mlp/" in n for n in names)
    toks = jnp.zeros((1, 16), jnp.int32)
    lens = jnp.asarray([5], jnp.int32)
    prefill = E._prefill_one_fn(cfg, None, 16, L, 0.0).lower(
        params, toks, lens, key).compile().as_text()
    seeded = E._prefill_one_fn(cfg, None, 16, L, 0.5, True).lower(
        params, toks, lens, lens).compile().as_text()
    for text in (prefill, seeded):
        names = _op_names(text)
        assert any("/sample/" in n for n in names)
        assert any("/ax.mlp/" in n for n in names)
    splice = E._SPLICE_FN.lower(cache, init_cache(cfg, 1, L),
                                jnp.int32(0)).compile().as_text()
    modules = [t.split(",", 1)[0].split()[1]
               for t in (step, prefill, seeded, splice)]
    assert modules == ["jit_step", "jit_prefill_bucket",
                       "jit_prefill_bucket", "jit_splice_slot"]


def test_token_loop_emits_each_phase_span_once_per_step():
    """A token-granular drain under a recorder: every per-step phase span
    appears exactly once per decode step with that step's id; the
    boundary phases (arrivals, admissions) once per step boundary; and
    each span's parent is the span around it."""
    from repro import obs
    from repro.fleet import ArrivalSource

    cfg, params = _model()
    trace = _mixed_trace(cfg, 6, seed=5)
    bcfg = BatcherConfig(n_slots=3, prompt_buckets=(8, 16),
                         new_token_bucket=6, token_granular=True)
    bat = ContinuousBatcher(params, cfg, bcfg, adaptive=_controller(cfg))
    bat.attach_slo(obs.SLOEngine(obs.default_serving_slos()))
    rec = obs.TraceRecorder()
    prev = obs.install_recorder(rec)
    try:
        bat.run_arrivals(ArrivalSource([(0.0, r) for r in trace]))
    finally:
        obs.install_recorder(prev)
    n = bat.stats["decode_steps"]
    assert n > 3 and bat.stats["splices"] > 0
    spans = [e for e in rec.events() if e["ph"] == "X"]
    by_id = {e["id"]: e for e in spans}

    def steps_of(name):
        return sorted(e["args"]["step"] for e in spans if e["name"] == name)

    for name in ("step_prepare", "token_step", "telemetry_read",
                 "token_read", "qor_observe", "retire_sweep",
                 "controller_observe"):
        assert steps_of(name) == list(range(n)), name
    for name in ("poll_arrivals", "fill_slots"):
        assert steps_of(name) == list(range(-1, n)), name

    def parent(e):
        p = e["args"]["parent"]
        return None if p is None else by_id[p]["name"]

    want = {"step_prepare": None, "token_step": None, "token_read": None,
            "telemetry_read": None, "qor_observe": None,
            "controller_observe": None, "retire_sweep": None,
            "poll_arrivals": None, "fill_slots": None,
            "policy_tree": "token_step", "admit_dispatch": "fill_slots",
            "admit": "fill_slots"}
    for e in spans:
        if e["name"] in want:
            assert parent(e) == want[e["name"]], e
    slo = [parent(e) for e in spans if e["name"] == "slo_observe"]
    assert len(slo) == len(trace)                 # one per retirement
    assert set(slo) <= {"retire_sweep", "fill_slots"}


# ---------------------------------------------------------------------------
# 8-device mesh: token-granular splicing under shard_map
# ---------------------------------------------------------------------------

def _run_sub(code, timeout=540):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(out.stdout[-2000:])


_TOKEN_MESH_SCRIPT = r"""
import dataclasses, json
import jax, numpy as np
import repro.configs as CFG
import repro.runtime as R
from repro.configs.base import AxPolicy
from repro.fleet import BatcherConfig, ContinuousBatcher, Request
from repro.launch.mesh import make_fleet_mesh
from repro.models import init_params
from repro.serve import engine as E

cfg = CFG.reduced(CFG.ARCHS["qwen2-72b"])
cfg = dataclasses.replace(cfg, n_layers=2, ax=AxPolicy(backend="mxu"))
params = init_params(jax.random.PRNGKey(0), cfg)
mesh = make_fleet_mesh(8)

def ctrl():
    return R.AdaptiveController(
        R.SwapPolicy.from_ax_policy(cfg.ax), targets=cfg.ax.targets,
        cfg=R.AdaptiveConfig(min_observe_steps=10**6))

def trace():
    rng = np.random.default_rng(7)
    return [Request(rid, rng.integers(0, cfg.vocab, int(rng.integers(3, 17))),
                    max_new=int(rng.integers(1, 5)))
            for rid in range(12)]

def serve(token, mesh_):
    bcfg = BatcherConfig(n_slots=8, prompt_buckets=(8, 16),
                         new_token_bucket=4, token_granular=token)
    bat = ContinuousBatcher(params, cfg, bcfg, adaptive=ctrl(), mesh=mesh_)
    for r in trace():
        bat.submit(Request(r.rid, np.asarray(r.tokens).copy(), r.max_new))
    return {c.rid: c.tokens.tolist() for c in bat.run()}, bat

res = {"devices": jax.device_count()}
wave, _ = serve(False, None)              # single-host wave oracle
tokm, bat = serve(True, mesh)             # sharded token-granular
res["tokens_identical"] = bool(wave == tokm)
res["splices"] = bat.stats["splices"]
sizes0 = {k: f._cache_size() for k, f in E._TOKEN_FNS.items()}
c2 = ctrl()
c2.policy.set_config("mlp", __import__("repro.core", fromlist=["x"]).SwapConfig("B", 5, 1))
bcfg = BatcherConfig(n_slots=8, prompt_buckets=(8, 16), new_token_bucket=4,
                     token_granular=True)
bat2 = ContinuousBatcher(params, cfg, bcfg, adaptive=c2, mesh=mesh)
for r in trace():
    bat2.submit(Request(r.rid, np.asarray(r.tokens).copy(), r.max_new))
bat2.run()
res["retrace_free"] = all(f._cache_size() == sizes0[k]
                          for k, f in E._TOKEN_FNS.items())
print("RESULT:" + json.dumps(res))
"""


@pytest.mark.multidevice
def test_token_granular_sharded_matches_wave_oracle_8dev():
    """ISSUE acceptance: on a forced 8-device mesh the token-granular
    batcher (sharded step + mid-flight splices) reproduces the single-host
    wave oracle's per-request tokens bit-exactly with zero recompiles
    across splices and a policy update."""
    r = _run_sub(_TOKEN_MESH_SCRIPT)
    assert r["devices"] == 8
    assert r["tokens_identical"], r
    assert r["splices"] > 0, r
    assert r["retrace_free"], r
