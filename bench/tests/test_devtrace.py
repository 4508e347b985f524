"""The reduction from trace events to busy time, idle share, per-program
time and the breakdown: on a synthetic op list and on a small trace
recorded with the CPU backend."""
import pytest

from harness import devtrace
from harness.devtrace import Op


def _ops():
    return [Op("d0", "a", "jit_step", 0.0, 1.0),
            Op("d0", "b", "jit_step", 0.5, 1.5),     # overlaps a
            Op("d0", "c", "jit_fn", 3.0, 4.0),
            Op("d1", "a", "jit_step", 0.0, 2.0)]


def test_busy_union_and_clip():
    busy = devtrace.busy(_ops(), 0.0, 5.0)
    assert busy == {"d0": pytest.approx(2.5), "d1": pytest.approx(2.0)}
    assert devtrace.busy(_ops(), 1.0, 3.5) == {
        "d0": pytest.approx(1.0), "d1": pytest.approx(1.0)}


def test_program_time_and_missing_program():
    assert devtrace.program_time(_ops(), 0, 5, ["jit_step"]) == pytest.approx(3.5)
    assert devtrace.program_time(_ops(), 0, 5, ["jit_fn"]) == pytest.approx(1.0)
    assert devtrace.program_time(_ops(), 0, 5, ["jit_absent"]) is None


def test_gaps_labelled_by_host_span():
    host = [("admit", 1.4, 2.9), ("token_step", 4.0, 4.2)]
    gaps = devtrace.idle_gaps(_ops(), 0.0, 5.0, host, "d0")
    assert gaps == [["admit", pytest.approx(1.5)], ["token_step", pytest.approx(1.0)]]


def test_top_ops():
    top = devtrace.top_ops(_ops(), 0, 5, n=2)
    assert top[0] == ["jit_step/a", pytest.approx(3.0)]


def test_cpu_trace(tmp_path):
    """A trace recorded here: ops of the jitted program are found, named by
    their module, and the marker that aligns host times is there."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def prog(x):
        return jnp.tanh(x @ x) + 1.0

    x = jnp.ones((128, 128))
    prog(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window_open"):
        pass
    for _ in range(3):
        prog(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, host = devtrace.read(devtrace.find_xplane(str(tmp_path)))
    assert "bench_window_open" in host
    mine = [o for o in ops if o.module == "jit_prog"]
    assert mine and all(o.end >= o.start for o in mine)
    t0 = min(o.start for o in ops)
    t1 = max(o.end for o in ops)
    assert devtrace.program_time(ops, t0, t1, ["jit_prog"]) > 0
    assert devtrace.program_time(ops, t0, t1, ["jit_other"]) is None
    busy = devtrace.busy(ops, t0, t1)
    assert 0 < sum(busy.values()) <= (t1 - t0) * len(busy)
