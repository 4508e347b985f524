"""The plain reference against the program's own prefill, both in float32
on the CPU at the reduced size, same weights: they agree to rounding.
(The approximate multiplier is discontinuous in its int8 operands, so a
last-bit difference upstream can move a quantized code; each case keeps
one approximated target, or few layers, where that stays rare.)"""
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _support import config as _config
from harness import reference, serving, spec, weights
from repro.configs import reduced
from repro.models import init_params, prefill

MODEL = spec.load_reference("dense_gqa")

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
    cfg = serving.program_config(config, MODEL, reduced)
    cfg = dataclasses.replace(cfg, n_layers=layers, param_dtype="float32",
                              compute_dtype="float32")
    cfg = dataclasses.replace(cfg, ax=dataclasses.replace(
        _swap(cfg, triple), targets=targets))
    run = serving.as_run(config, cfg, MODEL)
    run["approx"] = dict(run["approx"], targets=list(targets))
    params = weights.make(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), 5)
    S = 2 * MODEL.ROWS
    toks = np.random.default_rng(1).integers(0, cfg.vocab, S).astype(np.int32)
    prog = np.asarray(prefill(params, {"tokens": jnp.asarray(toks[None])}, cfg,
                              max_cache_len=S)[0][0])
    ref = MODEL.Reference(params, run)
    rows = {t: np.tile(np.asarray(triple, np.int32), (S, 1)) for t in targets}
    x = ref.hidden(toks, rows)
    mine = np.asarray(x @ params["lm_head"]["w"].T)
    err = np.linalg.norm(prog - mine) / np.linalg.norm(mine)
    assert err < 3e-2, err
    assert (prog.argmax(-1) == mine.argmax(-1)).mean() > 0.95
    # the gaps the check reads: 0 where the token is the reference's first
    nxt = mine.argmax(-1)
    gaps = reference.gaps(ref, toks, nxt, rows)["gap"]
    assert np.abs(gaps).max() < 1e-5


def test_mixed_triples_per_row():
    """Rows served under different triples: each row equals the replay of
    the whole sequence under its own triple only where earlier rows agree,
    so check the first row of the second half against a one-triple run."""
    config = _config("qwen2-72b-2l-noswap")
    cfg = serving.program_config(config, MODEL, reduced)
    run = serving.as_run(config, cfg, MODEL)
    params = weights.make(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), 6)
    ref = MODEL.Reference(params, run)
    S = MODEL.ROWS
    toks = np.random.default_rng(2).integers(0, cfg.vocab, S).astype(np.int32)
    a = {t: np.tile(np.asarray([1, 3, 0], np.int32), (S, 1)) for t in ref.targets}
    b = {t: np.tile(np.asarray([0, 2, 1], np.int32), (S, 1)) for t in ref.targets}
    mixed = {t: np.concatenate([a[t][:100], b[t][100:]]) for t in ref.targets}
    xa, xm = np.asarray(ref.hidden(toks, a)), np.asarray(ref.hidden(toks, mixed))
    assert np.array_equal(xa[:100], xm[:100])       # causal: earlier rows alone
    assert not np.allclose(xa[100:], xm[100:])


# Digests (sha256 of the bytes, first 16 hex digits) of what the reference
# gave before its model moved from harness/reference.py into
# references/dense_gqa.py, computed by the code of that parent commit with
# the inputs of ``_pinned`` below; the move changed no arithmetic.
PINNED = {
    "qwen2-72b-2l-noswap": dict(
        hidden="8eacd0fa6c835486", hidden_fp8="3fabf211cd35301d",
        gap="aae24c07399011dd", rank="b234f4e8343084ad",
        control_gap="df93bd8e03d16654", control_rank="9701a88797ec2580"),
    "starcoder2-15b-8l": dict(
        hidden="5357e0e5946be9c2", hidden_fp8="5092740b45da6024",
        gap="dc2fb02849a752dd", rank="e47fc875f8fe0f95",
        control_gap="7bf0c1e2651d1cb3", control_rank="858846a11acd2826"),
}


@functools.lru_cache(maxsize=None)
def _pinned(name):
    """At the reduced size, bf16 weights from seed 11: the hidden states
    (float32 and the fp8 control) and the gaps and ranks, with the
    control's, of 256 rows under three triples (a third of the rows
    each) against random next tokens."""
    config = _config(name)
    cfg = serving.program_config(config, MODEL, reduced)
    run = serving.as_run(config, cfg, MODEL)
    params = weights.make(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), 11)
    S = 256
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, S).astype(np.int32)
    nxt = rng.integers(0, cfg.vocab, S).astype(np.int32)
    rows = np.asarray([TRIPLES[i * 3 // S] for i in range(S)], np.int32)
    triples = {t: rows for t in run["approx"]["targets"]}
    ref = MODEL.Reference(params, run)
    out = dict(hidden=ref.hidden(toks, triples),
               hidden_fp8=ref.hidden(toks, triples, store="fp8"))
    out.update(reference.gaps(ref, toks, nxt, triples, control=True))
    return {k: hashlib.sha256(np.ascontiguousarray(np.asarray(v)).tobytes())
            .hexdigest()[:16] for k, v in out.items()}


@pytest.mark.parametrize("name", sorted(PINNED))
@pytest.mark.parametrize("what", sorted(PINNED["qwen2-72b-2l-noswap"]))
def test_moved_reference_reproduces_the_old(name, what):
    assert _pinned(name)[what] == PINNED[name][what]
