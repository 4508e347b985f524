"""The benchmark's weights: the same from one seed every time, so the
replay can make them anew; and the serving stack keeps no raw tree beside
the batcher's prepared one."""
import gc
import shutil
import time
import weakref

import jax
import numpy as np
import pytest

from _support import config as _config
from harness import cell, serving, spec, weights
from repro.configs import reduced
from repro.models import init_params

CELL = "qwen2-72b-2l-noswap.batch"
MODEL = spec.load_reference("dense_gqa")


def _cfg():
    return serving.program_config(_config("qwen2-72b-2l-noswap"), MODEL,
                                  reduced)


def _layout(cfg):
    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))


def _approximated(tree):
    """The raw weights of the approximated projections (mlp, attn_out)."""
    layer = tree["layers"]["p0"]
    return [layer["mlp"][k]["w"] for k in ("in", "gate", "out")] + [
        layer["attn"]["o"]["w"]]


def test_one_seed_one_tree():
    layout = _layout(_cfg())
    a, b = weights.make(layout, 2 ** 33 + 5), weights.make(layout, 2 ** 33 + 5)
    c = weights.make(layout, 2 ** 33 + 6)
    leaves = jax.tree.leaves(a)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y, z in zip(leaves, jax.tree.leaves(b), jax.tree.leaves(c)):
        assert x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert not all(np.array_equal(np.asarray(x), np.asarray(z))
                   for x, z in zip(leaves, jax.tree.leaves(c)))


@pytest.fixture
def spied(monkeypatch):
    """Each tree ``weights.make`` returns, as weak references to its
    approximated weights and the live device bytes when it was called."""
    calls = []
    real = weights.make

    def spy(layout, seed, sharding=None):
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        out = real(layout, seed, sharding)
        calls.append(dict(live=live, refs=[weakref.ref(w) for w in
                                           _approximated(out)]))
        return out

    monkeypatch.setattr(weights, "make", spy)
    return calls


def test_build_drops_the_raw_tree(spied):
    """The batcher holds the prepared weights; the raw approximated ones
    go once it is built, as in ``launch/serve``."""
    cfg = _cfg()
    mix = spec.traffic("batch")
    stack = serving.build(cfg, mix, 1, 3)
    try:
        gc.collect()
        assert len(spied) == 1
        assert [r() for r in spied[0]["refs"]] == [None] * 4
        assert "wfg" in stack.batcher.params["layers"]["p0"]["mlp"]["in"]
    finally:
        shutil.rmtree(stack.store_dir, ignore_errors=True)


def test_replay_makes_the_weights_anew_once_the_stack_is_gone(spied):
    gc.collect()
    before = sum(a.nbytes for a in jax.live_arrays())
    out = cell.run(CELL, 2 ** 33 + 11, 2.0, False, time.perf_counter(),
                   shrink=reduced, allow_cpu=True)
    assert out["checks"]["served_tokens_checked"]["value"] > 0
    build, replay = spied
    assert [r() for r in build["refs"]] == [None] * 4
    assert replay["live"] <= before       # nothing of the stack is left
