"""Unified observability layer (PR 6): metrics registry semantics,
Prometheus exposition (golden file), trace-span JSON well-formedness, the
retune audit trail, the policy-store heartbeat fast-path, and — the one
that guards the serving guarantees — a regression test that the recompile
gauge stays 0 across token-granular splices and a policy update WITH the
instrumentation live (metrics + trace recorder + compile listener all on),
and that tokens stay bit-identical to the uninstrumented wave oracle.
"""
import dataclasses
import json
import os
import urllib.request

import jax
import numpy as np
import pytest

import repro.core as C
import repro.runtime as R
from repro import obs
from repro.configs.base import AxPolicy
from repro.fleet import (BatcherConfig, ContinuousBatcher, PolicyReader,
                         PolicyStore, Request)

DATA = os.path.join(os.path.dirname(__file__), "data")


# ---------------------------------------------------------------------------
# registry: label-set semantics, declaration rules
# ---------------------------------------------------------------------------

def test_counter_label_sets_and_totals():
    reg = obs.MetricsRegistry()
    c = reg.counter("x_total", "help")
    c.inc(1, mode="wave")
    c.inc(2, mode="token")
    c.inc(3, mode="wave")
    assert c.value(mode="wave") == 4
    assert c.value(mode="token") == 2
    assert c.value(mode="absent") == 0
    assert c.total() == 6
    with pytest.raises(AssertionError):
        c.inc(-1)


def test_label_order_never_matters():
    reg = obs.MetricsRegistry()
    c = reg.counter("y_total", "h")
    c.inc(1, a="1", b="2")
    c.inc(1, b="2", a="1")
    assert c.value(a="1", b="2") == 2
    assert len(c.series()) == 1


def test_declaration_get_or_create_and_mismatch():
    reg = obs.MetricsRegistry()
    c1 = reg.counter("z_total", "same help")
    c2 = reg.counter("z_total", "same help")     # get-or-create: same object
    assert c1 is c2
    with pytest.raises(AssertionError):
        reg.gauge("z_total", "same help")         # type mismatch
    with pytest.raises(AssertionError):
        reg.counter("z_total", "different help")  # help mismatch
    h1 = reg.histogram("h_seconds", "h", buckets=(1.0, 2.0))
    assert reg.histogram("h_seconds", "h", buckets=(2.0, 1.0)) is h1
    with pytest.raises(AssertionError):
        reg.histogram("h_seconds", "h", buckets=(1.0, 3.0))


def test_gauge_set_and_inc():
    reg = obs.MetricsRegistry()
    g = reg.gauge("g", "h")
    g.set(2.5, target="mlp")
    g.inc(0.5, target="mlp")
    g.set(7, target="attn")
    assert g.value(target="mlp") == 3.0
    assert g.value(target="attn") == 7.0


# ---------------------------------------------------------------------------
# histogram: bucket-edge semantics (v <= le), percentiles
# ---------------------------------------------------------------------------

def test_histogram_bucket_edges_inclusive():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat", "h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 9.0):     # edge values land IN the
        h.observe(v)                              # edge's bucket (v <= le)
    cum = dict(h.cumulative())
    assert cum[1.0] == 2          # 0.5, 1.0
    assert cum[2.0] == 4          # + 1.5, 2.0
    assert cum[5.0] == 5          # + 5.0
    assert cum[float("inf")] == 6  # + 9.0
    snap = h.snapshot()
    assert snap["count"] == 6 and snap["sum"] == pytest.approx(19.0)


def test_histogram_percentile_bucket_resolution():
    reg = obs.MetricsRegistry()
    h = reg.histogram("p", "h", buckets=(0.01, 0.1, 1.0))
    assert h.percentile(0.5) is None              # empty series
    for _ in range(98):
        h.observe(0.005)
    h.observe(0.5)
    h.observe(50.0)                               # +Inf bucket
    assert h.percentile(0.5) == 0.01
    assert h.percentile(0.99) == 1.0
    assert h.percentile(1.0) == 1.0               # +Inf reports last edge


# ---------------------------------------------------------------------------
# Prometheus exposition: golden file
# ---------------------------------------------------------------------------

def _golden_registry() -> obs.MetricsRegistry:
    reg = obs.MetricsRegistry()
    c = reg.counter("repro_demo_total", "a counter with labels")
    c.inc(3, mode="wave")
    c.inc(1.5, mode="token")
    g = reg.gauge("repro_demo_occupancy", 'quoted "help" with\nnewline')
    g.set(0.75)
    h = reg.histogram("repro_demo_seconds", "a histogram",
                      buckets=(0.1, 1.0, 10.0))
    h.observe(0.05, path="a")
    h.observe(0.5, path="a")
    h.observe(99.0, path="a")
    return reg


def test_prometheus_exposition_matches_golden_file():
    text = obs.prometheus_text(_golden_registry())
    golden = os.path.join(DATA, "metrics_golden.prom")
    with open(golden) as f:
        assert text == f.read()


def test_prometheus_text_deterministic_and_escaped():
    a = obs.prometheus_text(_golden_registry())
    b = obs.prometheus_text(_golden_registry())
    assert a == b
    assert r'quoted \"help\" with\nnewline' in a
    assert 'le="+Inf"' in a
    # cumulative bucket counts, sum/count per series
    assert 'repro_demo_seconds_bucket{path="a",le="0.1"} 1' in a
    assert 'repro_demo_seconds_bucket{path="a",le="+Inf"} 3' in a
    assert 'repro_demo_seconds_count{path="a"} 3' in a


# ---------------------------------------------------------------------------
# /metrics scrape endpoint
# ---------------------------------------------------------------------------

def test_metrics_server_serves_prometheus_text():
    reg = _golden_registry()
    with obs.start_metrics_server(0, reg, host="127.0.0.1") as srv:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read().decode()
        assert body == obs.prometheus_text(reg)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=10)


def test_jsonl_snapshot_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    obs.write_snapshot(path, _golden_registry(), run="first")
    obs.write_snapshot(path, _golden_registry(), run="second")
    lines = [json.loads(s) for s in open(path).read().splitlines()]
    assert [s["run"] for s in lines] == ["first", "second"]
    m = lines[0]["metrics"]["repro_demo_seconds"]
    assert m["kind"] == "histogram"
    assert m["series"]["path=a"]["count"] == 3
    assert m["series"]["path=a"]["buckets"][-1] == ["+Inf", 3]


# ---------------------------------------------------------------------------
# trace spans: Chrome-trace JSON well-formedness
# ---------------------------------------------------------------------------

def test_trace_recorder_chrome_format(tmp_path):
    rec = obs.TraceRecorder()
    prev = obs.install_recorder(rec)
    try:
        obs.async_begin("request", 7, prompt_len=5)
        with obs.span("prefill", cat="engine", rid=7):
            with obs.span("inner"):
                pass
        obs.instant("splice", slot=2)
        obs.async_end("request", 7)
    finally:
        obs.install_recorder(prev)
    path = str(tmp_path / "trace.json")
    rec.save(path)
    doc = json.loads(open(path).read())          # well-formed JSON
    evs = doc["traceEvents"]
    assert [e["ph"] for e in evs] == ["b", "X", "X", "i", "e"]
    for e in evs:
        assert {"name", "ph", "cat", "pid", "tid", "ts"} <= set(e)
        json.dumps(e)                             # every event serializable
    (b_ev, inner, outer, inst, e_ev) = evs
    assert b_ev["id"] == e_ev["id"] == "7"
    assert b_ev["args"]["prompt_len"] == 5
    # nested span closed first, and sits inside the outer span's interval
    assert inner["name"] == "inner" and outer["name"] == "prefill"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert e_ev["ts"] >= b_ev["ts"]


def test_span_without_recorder_is_noop():
    prev = obs.install_recorder(None)
    try:
        with obs.span("anything", rid=1):         # must not raise or record
            obs.instant("x")
            obs.async_begin("r", 1)
            obs.async_end("r", 1)
    finally:
        obs.install_recorder(prev)


def test_span_parent_ids_nest_per_thread():
    """Each span carries an id; args.parent is the id of the span open
    around it on the same thread (None at the top), and request-scoped
    args ride along unchanged."""
    import threading

    rec = obs.TraceRecorder()
    prev = obs.install_recorder(rec)
    try:
        with obs.span("outer", rid=3):
            with obs.span("mid"):
                with obs.span("leaf", step=1):
                    pass
            with obs.span("sibling"):
                pass
            t = threading.Thread(target=lambda: obs.span("other").__enter__()
                                 .__exit__(None, None, None))
            t.start()
            t.join(timeout=10)
        with obs.span("top"):
            pass
    finally:
        obs.install_recorder(prev)
    assert not t.is_alive()
    ev = {e["name"]: e for e in rec.events()}
    assert len({e["id"] for e in ev.values()}) == len(ev) == 6
    assert ev["outer"]["args"] == {"rid": 3, "parent": None}
    assert ev["mid"]["args"]["parent"] == ev["outer"]["id"]
    assert ev["leaf"]["args"] == {"step": 1, "parent": ev["mid"]["id"]}
    assert ev["sibling"]["args"]["parent"] == ev["outer"]["id"]
    assert ev["other"]["args"]["parent"] is None      # its own thread's top
    assert ev["top"]["args"]["parent"] is None
    # a span's event is well-formed Chrome trace JSON like the others
    json.dumps(rec.events())


def test_span_on_profiler_host_plane_matches_recorder(tmp_path):
    """With a recorder installed, a span also lands on the profiler's host
    plane (a TraceAnnotation); moved onto the trace's clock by one marker,
    its recorder copy agrees with its xplane copy within 100 us."""
    import glob
    import time

    from jax.profiler import ProfileData

    rec = obs.TraceRecorder()
    offset = time.perf_counter() - rec.now_us() / 1e6
    prev = obs.install_recorder(rec)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("clock_marker"):
            mark = time.perf_counter()
        for _ in range(3):
            with obs.span("phase_probe", cat="scheduler", step=0):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
        obs.install_recorder(prev)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    shift = host["clock_marker"][0][0] - mark
    mine = [(offset + e["ts"] / 1e6 + shift,
             offset + (e["ts"] + e["dur"]) / 1e6 + shift)
            for e in rec.events() if e["name"] == "phase_probe"]
    theirs = sorted(host["phase_probe"])
    assert len(mine) == len(theirs) == 3
    for (a0, a1), (b0, b1) in zip(mine, theirs):
        assert abs(a0 - b0) < 100e-6 and abs(a1 - b1) < 100e-6


# ---------------------------------------------------------------------------
# audit trail
# ---------------------------------------------------------------------------

def test_audit_log_roundtrip_and_seq_resume(tmp_path):
    path = str(tmp_path / "audit.jsonl")
    log = obs.AuditLog(path)
    ev0 = log.append("retune", target="mlp", drift=0.05, store_version=1)
    ev1 = log.append("tile_retune", target="attn_out",
                     grid_digest=obs.grid_digest(np.arange(12).reshape(4, 1, 3)))
    assert (ev0["seq"], ev1["seq"]) == (0, 1)
    got = log.read()
    assert [e["kind"] for e in got] == ["retune", "tile_retune"]
    assert got[0]["drift"] == 0.05 and got[0]["store_version"] == 1
    # a reopened log continues the sequence; a torn tail line is skipped
    with open(path, "a") as f:
        f.write('{"seq": 2, "kind": "torn...')
    log2 = obs.AuditLog(path)
    ev2 = log2.append("retune", target="mlp")
    assert ev2["seq"] == 2
    assert len(log2.read()) == 3                  # torn line dropped


def test_grid_digest_stable_and_shape_sensitive():
    g = np.arange(12, dtype=np.int32).reshape(4, 1, 3)
    assert obs.grid_digest(g) == obs.grid_digest(g.copy())
    assert obs.grid_digest(g) != obs.grid_digest(g.reshape(2, 2, 3))
    assert len(obs.grid_digest(g)) == 12


def test_controller_retune_writes_audit_event(tmp_path):
    """A store-backed controller's re-tune appends one structured audit
    event carrying the published store version."""
    store = PolicyStore(str(tmp_path / "store"))
    policy = R.SwapPolicy(mult_name="mul8s_trunc0_4")
    ctrl = R.AdaptiveController(policy, targets=("mlp",), store=store)
    rng = np.random.default_rng(0)
    ctrl.buffers["mlp"].add(rng.integers(-100, 100, 512),
                            rng.integers(-100, 100, 512))
    ev = ctrl.retune("mlp", drift=0.123)
    events = ctrl.audit.read()
    assert len(events) == 1
    e = events[0]
    assert e["kind"] == "retune" and e["target"] == "mlp"
    assert e["drift"] == pytest.approx(0.123)
    assert e["store_version"] == store.current_version()
    assert e["predicted_gain"] == pytest.approx(ev.old_score - ev.new_score)
    assert os.path.exists(os.path.join(store.root, obs.AUDIT_FILENAME))


# ---------------------------------------------------------------------------
# store heartbeat fast-path + staleness disambiguation
# ---------------------------------------------------------------------------

def test_heartbeat_mtime_is_version_and_monotonic(tmp_path):
    store = PolicyStore(str(tmp_path / "s"))
    p = R.SwapPolicy(mult_name="mul8s_trunc0_4")
    assert store.heartbeat_ns() is None           # nothing published
    v1 = store.publish(p)
    assert store.heartbeat_ns() == v1
    v2 = store.publish(p)                         # same-instant publishes
    assert store.heartbeat_ns() == v2 == v1 + 1   # still distinct signals


def test_reader_poll_fast_paths_on_heartbeat(tmp_path, monkeypatch):
    store = PolicyStore(str(tmp_path / "s"))
    p = R.SwapPolicy(mult_name="mul8s_trunc0_4")
    store.publish(p)
    reader = PolicyReader(store, targets=("mlp",), name="r0")
    assert reader.version == 1
    calls = {"n": 0}
    orig = store.current_version

    def counting():
        calls["n"] += 1
        return orig()

    monkeypatch.setattr(store, "current_version", counting)
    for _ in range(5):
        assert reader.poll() is False             # heartbeat unchanged:
    assert calls["n"] == 0                        # CURRENT never read
    store.publish(p)
    assert reader.poll() is True                  # heartbeat moved: full poll
    assert calls["n"] >= 1
    assert reader.version == 2


def test_reader_without_heartbeat_still_polls(tmp_path):
    """Pre-heartbeat store layouts (no HEARTBEAT file) keep working: every
    poll takes the full path."""
    store = PolicyStore(str(tmp_path / "s"))
    p = R.SwapPolicy(mult_name="mul8s_trunc0_4")
    store.publish(p)
    os.remove(os.path.join(store.root, "HEARTBEAT"))
    reader = PolicyReader(store, targets=("mlp",), name="r0")
    assert reader.version == 1
    store.publish(p)
    os.remove(os.path.join(store.root, "HEARTBEAT"))
    assert reader.poll() is True
    assert reader.version == 2


def test_staleness_distinguishes_empty_store_from_behind(tmp_path):
    reg = obs.default_registry()
    published = reg.get("repro_policy_store_published")
    store = PolicyStore(str(tmp_path / "s"))
    reader = PolicyReader(store, targets=("mlp",), name="rx")
    # empty store: staleness 0 is vacuous; the published gauge says WHY
    assert reader.staleness() == 0
    assert reg.get("repro_replica_staleness").value(replica="rx") == 0
    p = R.SwapPolicy(mult_name="mul8s_trunc0_4")
    v1 = store.publish(p)
    assert published.value() == v1
    assert reader.staleness() == 1                # now genuinely behind
    store.publish(p)
    assert reader.staleness() == 2
    reader.poll()
    assert reader.staleness() == 0
    assert reg.get("repro_replica_staleness").value(replica="rx") == 0


# ---------------------------------------------------------------------------
# recompile accounting: the gauge guards the serving guarantees
# ---------------------------------------------------------------------------

def _tiny_model():
    import repro.configs as CFG
    from repro.models import init_params

    cfg = CFG.reduced(CFG.ARCHS["qwen2-72b"])
    cfg = dataclasses.replace(
        cfg, n_layers=2,
        ax=AxPolicy(mult_name="mul8s_trunc0_4", backend="mxu"))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _ctrl(cfg):
    return R.AdaptiveController(
        R.SwapPolicy.from_ax_policy(cfg.ax), targets=cfg.ax.targets,
        cfg=R.AdaptiveConfig(min_observe_steps=10 ** 6))


def _serve(params, cfg, token_granular, trace, adaptive):
    bcfg = BatcherConfig(n_slots=2, prompt_buckets=(8, 16),
                         new_token_bucket=4, token_granular=token_granular)
    bat = ContinuousBatcher(params, cfg, bcfg, adaptive=adaptive)
    for r in trace:
        bat.submit(Request(r.rid, np.asarray(r.tokens).copy(), r.max_new))
    done = bat.run()
    return {c.rid: c.tokens.tolist() for c in done}, bat


def test_recompile_gauge_zero_across_splices_and_policy_update():
    """ISSUE acceptance: with ALL instrumentation live (metrics, trace
    recorder, jax.monitoring compile listener), a token-granular drain with
    mid-flight splices followed by a policy-update drain keeps the
    recompile gauge at zero post-warmup — and per-request tokens stay
    bit-identical to the wave oracle, proving instrumentation is host-side
    only."""
    cfg, params = _tiny_model()
    rng = np.random.default_rng(7)
    trace = [Request(rid, rng.integers(0, cfg.vocab, int(rng.integers(3, 17))),
                     max_new=int(rng.integers(1, 5)))
             for rid in range(8)]

    wave, _ = _serve(params, cfg, False, trace, _ctrl(cfg))

    obs.install_jax_compile_listener()
    rec = obs.TraceRecorder()
    prev = obs.install_recorder(rec)
    try:
        tok, bat = _serve(params, cfg, True, trace, _ctrl(cfg))
    finally:
        obs.install_recorder(prev)
    assert wave == tok                       # bit-identity with obs live
    assert bat.stats["splices"] > 0
    assert bat.stats["decode_retraces_post_warmup"] == 0
    reg = obs.default_registry()
    assert reg.get("repro_decode_retraces_post_warmup").value() == 0
    assert reg.get("repro_splices_total").total() >= 1
    # the drain's timeline actually recorded spans
    names = {e["name"] for e in rec.events()}
    assert {"admit", "token_step", "request"} <= names

    # a policy update between drains must not move the retrace counter
    before = obs.retrace_total("token_step")
    ctrl = _ctrl(cfg)
    ctrl.policy.set_config("mlp", C.SwapConfig("B", 5, 1))
    tok2, bat2 = _serve(params, cfg, True, trace, ctrl)
    assert obs.retrace_total("token_step") == before
    assert bat2.stats["decode_retraces_post_warmup"] == 0
    assert any(tok2[r] != tok[r] for r in tok)   # the policy actually bites


def test_latency_log_and_summary_populated():
    cfg, params = _tiny_model()
    rng = np.random.default_rng(3)
    trace = [Request(rid, rng.integers(0, cfg.vocab, 6), max_new=3)
             for rid in range(4)]
    _, bat = _serve(params, cfg, True, trace, _ctrl(cfg))
    assert len(bat.request_log) == 4
    for r in bat.request_log:
        assert r["ttft"] is not None and 0 <= r["ttft"] <= r["e2e"]
    s = bat.latency_summary()
    assert s["requests"] == 4
    assert s["ttft_p50"] <= s["ttft_p99"]
    assert s["e2e_p50"] <= s["e2e_p99"]
