"""A whole run at the reduced size on the CPU, with the timed path broken
underneath, comes out not correct; the same run unbroken comes out
correct.  The limit is set for the reduced size in a copy of the
benchmark (the cells' own limits hold the full-size numbers)."""
import json
import shutil
import time

import jax.numpy as jnp
import pytest

import repro.fleet.scheduler as sched
from harness import cell, spec
from repro.configs import reduced

CELL = "qwen2-72b-2l-noswap.batch"
SECONDS = 2.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark with the cell's config limit set, as on the chip,
    between a sound run's number and the control's at the reduced size:
    their geometric mean (the sound number floored at 1e-4 of the
    control's, since a short window's mean gap can read 0).  Sound runs
    of other requests read several times the first one."""
    tmp = tmp_path_factory.mktemp("bench")
    shutil.copytree(spec.ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    sound = _run(tmp, None, limit=1e9, control=True)
    stat = _key(tmp)[len("logit_"):]
    lower = sound["control"]["program"][stat]
    upper = sound["control"]["control"][stat]
    assert upper > 10 * lower
    _set_limit(tmp, (max(lower, upper * 1e-4) * upper) ** 0.5)
    return tmp


def _key(root):
    c = spec.config(spec.load_benchmark(root), spec.cell(
        spec.load_benchmark(root), CELL)["config"], root)
    return "logit_" + c["correct"]["statistic"]


def _set_limit(root, limit):
    bench = spec.load_benchmark(root)
    entry = next(c for c in bench["configs"] if c["name"] == spec.cell(
        bench, CELL)["config"])
    path = root / entry["file"]
    c = json.loads(path.read_text())
    c["correct"]["limit"] = limit
    path.write_text(json.dumps(c))


def _run(root, fault, limit=None, control=False):
    if limit is not None:
        _set_limit(root, limit)
    return cell.run(CELL, 424242, SECONDS, False, time.perf_counter(),
                    shrink=reduced, allow_cpu=True, root=root, fault=fault,
                    control=control)


def _wrap_step(monkeypatch, edit):
    real = sched.token_step

    def broken(*a, **kw):
        return edit(real(*a, **kw), a)

    monkeypatch.setattr(sched, "token_step", broken)


def test_sound_run_is_correct(root):
    assert _run(root, None)["correct"]


def test_control_is_not_correct(root):
    """The reference in fp8, the step below the configuration's bfloat16,
    read at the rows the program served, fails the limit the program
    passes."""
    out = _run(root, None, control=True)
    stat = _key(root)[len("logit_"):]
    limit = out["checks"][_key(root)]["limit"]
    assert out["correct"]
    assert out["control"]["program"][stat] <= limit
    assert out["control"]["value"] == out["control"]["control"][stat] > limit
    assert out["control"]["correct"] is False


def test_state_left_unchanged(root, monkeypatch):
    """The step returns the cache it was given: no K/V is written."""
    _wrap_step(monkeypatch, lambda out, a: (out[0], a[1]) + tuple(out[2:]))
    assert not _run(root, None)["correct"]


def test_token_altered(root, monkeypatch):
    """Every token the step produces is moved to the next id."""
    _wrap_step(monkeypatch, lambda out, a: (out[0] + 1,) + tuple(out[1:]))
    assert not _run(root, None)["correct"]


def test_half_the_slots_left_out(root, monkeypatch):
    """The upper half of the slots is never decoded: their tokens repeat."""
    real = sched.token_step

    def broken(params, cache, tok, sub, pos, active, *a, **kw):
        half = active.shape[0] // 2
        active = active.at[half:].set(False)
        return real(params, cache, tok, sub, pos, active, *a, **kw)

    monkeypatch.setattr(sched, "token_step", broken)
    assert not _run(root, None)["correct"]


def test_first_token_altered(root, monkeypatch):
    """The prefill's first token is moved to the next id."""
    real = sched.prefill_one

    def broken(*a, **kw):
        first, fresh = real(*a, **kw)
        return first + jnp.int32(1), fresh

    monkeypatch.setattr(sched, "prefill_one", broken)
    assert not _run(root, None)["correct"]
