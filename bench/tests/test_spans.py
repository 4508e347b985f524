"""The readers of the program's named scopes and phase spans: on synthetic
ops and spans, on a small HLO text, and against the per-program time the
accepted ``decode_step_ms`` reader divides."""
import types

import pytest

from harness import devtrace, readers, spans, timeline
from harness.devtrace import Op

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %convert.1 = f32[4]{0} convert(%param_0), metadata={op_name="jit(step)/convert_element_type"}
  ROOT %abs.2 = f32[4]{0} abs(%convert.1), metadata={op_name="jit(step)/ax.mlp/abs"}
}

%region_0.1 (reduce.4: f32[], reduce.5: f32[]) -> f32[] {
  %reduce.4 = f32[] parameter(0), metadata={op_name="reduce_max"}
  %reduce.5 = f32[] parameter(1), metadata={op_name="reduce_max"}
  ROOT %max.3 = f32[] maximum(%reduce.4, %reduce.5), metadata={op_name="jit(step)/ax.mlp/reduce_max"}
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %bitcast_abs_fusion = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %dot.3 = f32[4]{0} dot(%bitcast_abs_fusion, %bitcast_abs_fusion), metadata={op_name="jit(step)/ax.attn_out/ax_telemetry.attn_out/cond/dot_general"}
  %iota_reduce_fusion = s32[] fusion(%dot.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/sample/argmax"}
  ROOT %add.7 = f32[4]{0} add(%dot.3, %dot.3), metadata={op_name="jit(step)/add"}
}
"""


def test_family_is_the_innermost_scope():
    assert spans.family("jit(step)/ax.mlp/dot_general") == "ax"
    assert spans.family(
        "jit(step)/ax.mlp/ax_telemetry.mlp/cond/abs") == "ax_telemetry"
    assert spans.family("jit(step)/ax.mlp/jit(sample)/sample/argmax") == "sample"
    assert spans.family("jit(step)/jit(_where)/select_n") is None
    assert spans.family("reduce_max") is None


def test_scopes_from_hlo_text():
    sc = spans.scopes_from_text(HLO)
    assert sc["dot.3"] == "ax_telemetry"
    assert sc["iota_reduce_fusion"] == "sample"   # its own metadata first
    assert sc["bitcast_abs_fusion"] == "ax"       # none: its fused root's
    assert sc["add.7"] is None
    assert sc["max.3"] == "ax"


def test_fusions_that_span_two_scopes():
    assert spans.fused_families(HLO) == {
        "bitcast_abs_fusion": ["ax", "none"],
        "iota_reduce_fusion": ["ax", "none"]}


def test_hlo_scopes_of_a_live_program():
    """The scope map comes from the optimized HLO the process holds, and a
    module that never compiled gives nothing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scoped_probe(x):
        with jax.named_scope("ax.mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("sample"):
            return jnp.argmax(y, axis=-1)

    scoped_probe(jnp.ones((16, 16))).block_until_ready()
    sc = spans.hlo_scopes("jit_scoped_probe")
    assert "ax" in sc.values() and "sample" in sc.values()
    assert spans.hlo_scopes("jit_never_compiled") is None


def _ops():
    # d0: a loop op (no scope) whose body ops carry scopes, then a
    # sampler op; d1 runs one ax op.  One op of another program.
    return [Op("d0", "while.1", "jit_step", 0.0, 4.0),
            Op("d0", "fusion.1", "jit_step", 0.5, 1.5),      # ax
            Op("d0", "fusion.2", "jit_step", 1.5, 2.0),      # telemetry
            Op("d0", "iota_reduce_fusion", "jit_step", 4.0, 4.5),
            Op("d0", "fusion.9", "jit_prefill_bucket", 5.0, 6.0),
            Op("d1", "fusion.1", "jit_step", 0.0, 1.0)]


SCOPES = {"while.1": None, "fusion.1": "ax", "fusion.2": "ax_telemetry",
          "iota_reduce_fusion": "sample", "fusion.9": "ax"}


def test_scoped_parts_add_up_to_program_time():
    secs = spans.scope_seconds(_ops(), 0.0, 10.0, ["jit_step"], SCOPES)
    assert secs == {None: pytest.approx(2.5), "ax": pytest.approx(2.0),
                    "ax_telemetry": pytest.approx(0.5),
                    "sample": pytest.approx(0.5)}
    assert sum(secs.values()) == pytest.approx(
        devtrace.program_time(_ops(), 0.0, 10.0, ["jit_step"]))
    # clipped to the window like program_time
    part = spans.scope_seconds(_ops(), 1.0, 4.2, ["jit_step"], SCOPES)
    assert sum(part.values()) == pytest.approx(
        devtrace.program_time(_ops(), 1.0, 4.2, ["jit_step"]))
    assert part["ax"] == pytest.approx(0.5)


def _ctx(ops=None, host=(), steps=(0.1, 1.1, 2.1, 3.1)):
    st = timeline.Steps({k: t for k, t in enumerate(steps)}, {})
    trace = dict(ops=list(ops or _ops()), spans=list(host), t0=0.0, t1=10.0,
                 shift=0.0)
    return types.SimpleNamespace(steps=st, w0=0.0, w1=10.0, trace=trace)


def test_scope_ms_per_step_divides_like_decode_step_ms():
    ctx = _ctx()
    parts = {f: spans.scope_ms_per_step(ctx, f, scopes=SCOPES)
             for f in ("ax", "ax_telemetry", "sample")}
    # 4 steps, 2 devices
    assert parts["ax"] == pytest.approx(2.0 / 2 / 4 * 1e3)
    assert parts["sample"] == pytest.approx(0.5 / 2 / 4 * 1e3)
    rest = spans.scope_seconds(_ops(), 0, 10, ["jit_step"], SCOPES)[None]
    total = sum(parts.values()) + rest / 2 / 4 * 1e3
    assert total == pytest.approx(readers.program_ms_per_step(ctx, ["jit_step"]))
    # a scope whose work XLA fused into another scope's op reads 0
    fused = dict(SCOPES, iota_reduce_fusion=None, **{"argmax.3": "sample"})
    assert spans.scope_ms_per_step(ctx, "sample", scopes=fused) == 0.0
    # a program built without the scopes reads nothing, never 0
    bare = {k: None for k in SCOPES}
    assert spans.scope_ms_per_step(ctx, "ax", scopes=bare) is None
    assert spans.scope_ms_per_step(ctx, "ax", scopes={}) is None
    assert spans.scope_ms_per_step(_ctx(steps=()), "ax", scopes=SCOPES) is None


def _loop_spans():
    """Three steps; the second boundary admits a request whose prefill the
    host waits on inside ``admit``."""
    return [("token_step", 0.00, 0.01), ("token_read", 0.02, 0.03),
            ("retire_sweep", 0.03, 0.031),
            ("token_step", 0.034, 0.036), ("token_read", 0.05, 0.052),
            ("fill_slots", 0.052, 0.157),
            ("admit_dispatch", 0.053, 0.055), ("admit", 0.055, 0.155),
            ("token_step", 0.16, 0.162),
            ("controller_observe", 0.036, 0.04), ("retune", 0.037, 0.039),
            ("slo_observe", 0.156, 0.157)]


def test_host_ms_per_step_leaves_out_admit_waits():
    ctx = _ctx(ops=[Op("d0", "x", "jit_step", 0, 1)], host=_loop_spans())
    # step 1: 0.036 - 0.030 = 6 ms; step 2: 0.162 - 0.052 - 0.100 = 10 ms;
    # step 0 has no token_read before it
    assert spans.host_ms_per_step(ctx) == pytest.approx(8.0)
    # without the phase spans (a program that lacks them) it reads nothing
    bare = [s for s in _loop_spans() if s[0] != "token_read"]
    assert spans.host_ms_per_step(_ctx(host=bare)) is None


def test_runtime_host_ms_per_step_is_a_union():
    ctx = _ctx(host=_loop_spans(), steps=(0.001, 0.034, 0.16))
    # controller_observe 4 ms holds retune; slo_observe 1 ms; 3 steps
    assert spans.runtime_host_ms_per_step(ctx) == pytest.approx(5.0 / 3)
    bare = [s for s in _loop_spans() if s[0] not in spans.RUNTIME_SPANS]
    assert spans.runtime_host_ms_per_step(_ctx(host=bare)) is None


def test_idle_by_innermost_span():
    ops = [Op("d0", "a", "jit_step", 0.0, 1.0), Op("d0", "b", "jit_step", 2.0, 3.0),
           Op("d1", "c", "jit_step", 0.0, 4.0)]
    host = [("fill_slots", 1.0, 1.8), ("admit", 1.2, 1.5),
            ("token_step", 1.9, 2.5), ("step_prepare", 3.5, 3.6)]
    idle = spans.idle_by_span(ops, 0.0, 4.0, host, "d0")
    assert idle == {"fill_slots": pytest.approx(0.5),
                    "admit": pytest.approx(0.3),
                    "token_step": pytest.approx(0.1),
                    "step_prepare": pytest.approx(0.1),
                    "host_idle": pytest.approx(1.0)}
    assert sum(idle.values()) == pytest.approx(
        4.0 - devtrace.busy(ops, 0.0, 4.0)["d0"])


def test_traced_cpu_run_reads_the_scopes_and_phase_spans():
    """A traced run of the cell at the reduced size: the new per-layer
    metrics are all read, and the scoped parts of the step fit inside
    ``decode_step_ms``."""
    import time

    from harness import cell, spec
    from repro.configs import reduced

    name = spec.load_benchmark()["workloads"][0]["name"]
    out = cell.run(name, 2 ** 31 + 11, 2.0, True, time.perf_counter(),
                   shrink=reduced, allow_cpu=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for k in ("ax_ms_per_step.batch", "host_ms_per_step.batch",
              "runtime_host_ms_per_step.batch"):
        assert m[k] > 0, k
    scoped = (m["ax_ms_per_step.batch"] + m["telemetry_ms_per_step.batch"]
              + m["sample_ms_per_step.batch"])
    assert scoped <= m["decode_step_ms.batch"] * (1 + 1e-9)
