"""The plain reference against the program's own prefill, both in float32
on the CPU at the reduced size, same weights: they agree to rounding.
(The approximate multiplier is discontinuous in its int8 operands, so a
last-bit difference upstream can move a quantized code; each case keeps
one approximated target, or few layers, where that stays rare.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _support import config as _config
from harness import reference, serving, weights
from repro.configs import reduced
from repro.models import init_params, prefill

TRIPLES = ([1, 3, 0], [0, 3, 1], [1, 0, 2])
CASES = [("qwen2-72b-2l-noswap", ("mlp", "attn_out"), 2),
         ("starcoder2-15b-8l", ("mlp",), 2),
         ("starcoder2-15b-8l", ("attn_out",), 1),
         ("starcoder2-15b-8l", (), 4)]


def _swap(cfg, t):
    return dataclasses.replace(cfg.ax, swap_operand="A" if t[0] == 1 else "B",
                               swap_bit=t[1], swap_value=t[2] % 2,
                               swap_enabled=t[2] < 2)


@pytest.mark.parametrize("name,targets,layers", CASES)
@pytest.mark.parametrize("triple", TRIPLES)
def test_reference_matches_program(name, targets, layers, triple):
    config = _config(name)
    cfg = serving.program_config(config, reduced)
    cfg = dataclasses.replace(cfg, n_layers=layers, param_dtype="float32",
                              compute_dtype="float32")
    cfg = dataclasses.replace(cfg, ax=dataclasses.replace(
        _swap(cfg, triple), targets=targets))
    run = serving.as_run(config, cfg)
    run["approx"] = dict(run["approx"], targets=list(targets))
    params = weights.make(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), 5)
    S = 2 * reference.Q_CHUNK
    toks = np.random.default_rng(1).integers(0, cfg.vocab, S).astype(np.int32)
    prog = np.asarray(prefill(params, {"tokens": jnp.asarray(toks[None])}, cfg,
                              max_cache_len=S)[0][0])
    ref = reference.Reference(params, run)
    rows = {t: np.tile(np.asarray(triple, np.int32), (S, 1)) for t in targets}
    x = ref.hidden(toks, rows)
    mine = np.asarray(x @ params["lm_head"]["w"].T)
    err = np.linalg.norm(prog - mine) / np.linalg.norm(mine)
    assert err < 3e-2, err
    assert (prog.argmax(-1) == mine.argmax(-1)).mean() > 0.95
    # the gaps the check reads: 0 where the token is the reference's first
    nxt = mine.argmax(-1)
    gaps = ref.gaps(toks, nxt, rows)["gap"]
    assert np.abs(gaps).max() < 1e-5


def test_mixed_triples_per_row():
    """Rows served under different triples: each row equals the replay of
    the whole sequence under its own triple only where earlier rows agree,
    so check the first row of the second half against a one-triple run."""
    config = _config("qwen2-72b-2l-noswap")
    cfg = serving.program_config(config, reduced)
    run = serving.as_run(config, cfg)
    params = weights.make(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), 6)
    ref = reference.Reference(params, run)
    S = reference.Q_CHUNK
    toks = np.random.default_rng(2).integers(0, cfg.vocab, S).astype(np.int32)
    a = {t: np.tile(np.asarray([1, 3, 0], np.int32), (S, 1)) for t in ref.targets}
    b = {t: np.tile(np.asarray([0, 2, 1], np.int32), (S, 1)) for t in ref.targets}
    mixed = {t: np.concatenate([a[t][:100], b[t][100:]]) for t in ref.targets}
    xa, xm = np.asarray(ref.hidden(toks, a)), np.asarray(ref.hidden(toks, mixed))
    assert np.array_equal(xa[:100], xm[:100])       # causal: earlier rows alone
    assert not np.allclose(xa[100:], xm[100:])
