"""The ``starcoder2`` reference (StarCoder2's published block: LayerNorm
with bias, a bias on every linear, a sliding window) against the program's
``starcoder2-15b-published``, on the benchmark's seeded weights at the
reduced size on the CPU, where the window (64) is shorter than the
sequences, and its configuration and work count."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cell, check, reference, serving, spec, weights, window
from repro.configs import reduced
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serve import engine as E

NAME = "starcoder2-15b-8l-noswap"
CELL = "starcoder2-15b-8l-noswap.code-edit"
MODEL = spec.load_reference("starcoder2")


def _config():
    return json.loads((spec.ROOT / "bench" / "configs" / f"{NAME}.json")
                      .read_text())


def _program(targets=("mlp", "attn_out"), layers=2, dtype="float32",
             triple=(1, 3, 2)):
    """The program's config and the matching run of the file: reduced,
    ``layers`` deep, in ``dtype``, approximating ``targets`` under
    ``triple``."""
    config = _config()
    cfg = serving.program_config(config, MODEL, reduced)
    cfg = dataclasses.replace(
        cfg, n_layers=layers, param_dtype=dtype, compute_dtype=dtype,
        ax=dataclasses.replace(
            cfg.ax, targets=tuple(targets),
            swap_operand="A" if triple[0] == 1 else "B", swap_bit=triple[1],
            swap_value=triple[2] % 2, swap_enabled=triple[2] < 2))
    run = serving.as_run(config, cfg, MODEL)
    run["approx"] = dict(run["approx"], targets=list(targets),
                         swap=list(triple))
    params = weights.make(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), 2 ** 33 + 5)
    return cfg, run, params


def _logits(ref, tokens, triples):
    return np.asarray(ref.hidden(tokens, triples) @ ref.head.T)


def _rel(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ---------------------------------------------------------------------------
# the configuration and the work count
# ---------------------------------------------------------------------------

def test_file_maps_to_the_published_program():
    config = _config()
    cfg = serving.program_config(config, MODEL)
    assert (cfg.name, cfg.n_layers, cfg.local_window, cfg.norm) == (
        "starcoder2-15b-published", 8, 4096, "layer")
    assert set(cfg.layer_kinds()) == {"local"} and cfg.o_bias
    # the widths under the dense block (the test stub's arch) are refused:
    # RMSNorm, no o bias, no window
    config["program"] = {"arch": "starcoder2-15b"}
    with pytest.raises(ValueError, match="norm_type"):
        serving.program_config(config, MODEL)


@pytest.mark.parametrize("small", [True, False])
def test_params_match_the_program_layout(small):
    from repro.launch.smoke import footprint

    config = _config()
    cfg = serving.program_config(config, MODEL, reduced if small else None)
    assert (MODEL.params(serving.as_run(config, cfg, MODEL))
            == footprint(cfg, 1, 8)["params"])
    if not small:
        assert MODEL.params(config) == pytest.approx(3.674e9, rel=1e-3)


def test_work_counts_attention_over_the_window_only():
    c = _config()
    W, H, hd, L = 4096, 48, 128, 8
    near, far = MODEL.per_token(c, 99, False), MODEL.per_token(c, 9999, False)
    edge = MODEL.per_token(c, W - 1, False)
    assert far["flops"] == edge["flops"]                 # clipped at W keys
    assert edge["flops"] - near["flops"] == L * 4 * (W - 100) * H * hd
    for P in (100, W, W + 700):
        toks = [MODEL.per_token(c, p, p == P - 1) for p in range(P)]
        assert MODEL.prefill(c, P) == dict(
            int8=sum(t["int8"] for t in toks),
            flops=sum(t["flops"] for t in toks))
    kv = L * 2 * 4 * 128 * 2                             # bytes per position
    assert MODEL.window_bytes(c, 100) == 100 * kv
    assert MODEL.window_bytes(c, 9000) == W * kv == 67_108_864


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------

def test_reference_matches_program_prefill_in_float32():
    """Every projection exact, both in float32: the program's prefill
    (banded windowed attention, LayerNorm with bias) agrees with the
    reference to 1e-4 of the logits' norm.  The two differ only by the
    order of float32 sums and the CPU's default matmul precision against
    HIGHEST (observed 2.5e-7).  Attention scores taken in bfloat16 read
    5.4e-4 here, LayerNorms computed in bfloat16 5.0e-3: both fail."""
    cfg, run, params = _program(targets=())
    S = 2 * MODEL.ROWS
    toks = np.random.default_rng(1).integers(0, cfg.vocab, S).astype(np.int32)
    prog = np.asarray(prefill(params, {"tokens": jnp.asarray(toks[None])},
                              cfg, max_cache_len=S)[0][0])
    mine = _logits(MODEL.Reference(params, run), toks, {})
    assert _rel(prog, mine) < 1e-4


@pytest.mark.parametrize("triple", [(1, 3, 0), (0, 3, 1), (1, 0, 2)])
def test_reference_matches_program_prefill_approximated(triple):
    """Both approximated targets under three triples: the approximate
    multiplier is discontinuous in its int8 operands, so a last-bit
    difference upstream can move a code; 3e-2 of the norm and 95 % of the
    argmaxes hold (as for ``dense_gqa``), and the gap of the reference's
    own first choice is 0."""
    cfg, run, params = _program(triple=triple)
    S = 2 * MODEL.ROWS
    toks = np.random.default_rng(2).integers(0, cfg.vocab, S).astype(np.int32)
    prog = np.asarray(prefill(params, {"tokens": jnp.asarray(toks[None])},
                              cfg, max_cache_len=S)[0][0])
    ref = MODEL.Reference(params, run)
    rows = {t: np.tile(np.asarray(triple, np.int32), (S, 1))
            for t in ref.targets}
    mine = _logits(ref, toks, rows)
    assert _rel(prog, mine) < 3e-2
    assert (prog.argmax(-1) == mine.argmax(-1)).mean() > 0.95
    assert np.abs(reference.gaps(ref, toks, mine.argmax(-1), rows)["gap"]
                  ).max() < 1e-5


def _serve(cfg, params, prompts, steps, bucket):
    """The token-granular path: each prompt prefilled alone in ``bucket``
    and spliced into its slot of a batch, then ``steps`` greedy token steps
    at per-slot positions.  Returns the served tokens per prompt and the
    program's decode logits (teacher-forced on those tokens from the same
    spliced cache)."""
    B, mcl = len(prompts) + 1, bucket + steps + 1
    cache = init_cache(cfg, B, mcl)
    first = []
    for s, p in enumerate(prompts):
        padded = np.concatenate([p, np.full(bucket - len(p), p[-1])])
        tok, fresh = E.prefill_one(params, padded[None], len(p), cfg,
                                   max_cache_len=mcl)
        first.append(int(tok[0]))
        cache = E.splice_slot_jit(cache, fresh, s)
    start = cache
    pos = np.asarray([len(p) for p in prompts] + [0], np.int32)
    active = np.asarray([True] * len(prompts) + [False])
    tok = np.asarray(first + [0], np.int32)
    served = [[t] for t in first]
    key = jax.random.PRNGKey(0)
    for i in range(steps - 1):
        tok, cache = E.token_step(params, cache, jnp.asarray(tok), key,
                                  jnp.asarray(pos + i), jnp.asarray(active),
                                  cfg)
        tok = np.asarray(tok)
        for s in range(len(prompts)):
            served[s].append(int(tok[s]))
    logits, cache = [], start
    for i in range(steps - 1):
        feed = np.asarray([sv[i] for sv in served] + [0], np.int32)[:, None]
        lg, cache = decode_step(params, cache, jnp.asarray(feed),
                                jnp.asarray(pos + i), cfg,
                                write_mask=jnp.asarray(active))
        logits.append(np.asarray(lg[:, 0]))
    return served, np.stack(logits, 1)


def test_prefill_then_token_granular_decode_against_the_reference():
    """Two prompts longer than the window (100 and 150 tokens, window 64),
    prefilled in a 160 bucket and decoded 60 steps past the ring's wrap,
    every projection exact, in float32.  Replayed through the reference:
    every served token is the reference's first (gap < 1e-4: greedy ties
    aside the program picks the argmax) and the program's decode logits
    agree to 1e-4 of their norm (observed 2.7e-7; bfloat16 attention
    scores read 5.3e-4 and bfloat16 LayerNorms 5.1e-3, as in the prefill
    test)."""
    cfg, run, params = _program(targets=())
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (100, 150)]
    steps = 60
    served, prog = _serve(cfg, params, prompts, steps, bucket=160)
    ref = MODEL.Reference(params, run)
    length = -(-(150 + steps) // MODEL.ROWS) * MODEL.ROWS
    for s, (p, sv) in enumerate(zip(prompts, served)):
        tokens, nxt, _, sl = check.rows(p, np.asarray(sv, np.int32), 0,
                                        {}, [1, 3, 2], (), length)
        gaps = reference.gaps(ref, tokens, nxt, {})
        assert gaps["gap"][sl].max() < 1e-4, s
        mine = _logits(ref, tokens, {})[len(p):len(p) + steps - 1]
        assert _rel(prog[s], mine) < 1e-4, s


def test_served_bf16_approximated_decode_holds_the_cells_statistic():
    """The cell as served (bfloat16 weights and compute, both targets
    approximated under NoSwap, prefill then token-granular decode past the
    wrap): the mean gap of the served tokens stays under the configuration's
    limit, as a run's check reads it."""
    cfg, run, params = _program(dtype="bfloat16")
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (90, 140)]
    steps = 60
    served, _ = _serve(cfg, params, prompts, steps, bucket=160)
    ref = MODEL.Reference(params, run)
    length = -(-(140 + steps) // MODEL.ROWS) * MODEL.ROWS
    gaps = []
    for p, sv in zip(prompts, served):
        rows = {t: np.tile(np.asarray([1, 3, 2], np.int32), (length, 1))
                for t in ref.targets}
        tokens, nxt, _, sl = check.rows(p, np.asarray(sv, np.int32), 0,
                                        {}, [1, 3, 2], (), length)
        gaps.append(reference.gaps(ref, tokens, nxt, rows)["gap"][sl])
    assert np.concatenate(gaps).mean() < _config()["correct"]["limit"]


# ---------------------------------------------------------------------------
# the window metrics' readers
# ---------------------------------------------------------------------------

def test_attn_kind_is_the_innermost_scope():
    assert window.attn_kind("jit(step)/while/body/attn.local/dot") == "attn.local"
    assert window.attn_kind("jit(step)/attn.global/x") == "attn.global"
    assert window.attn_kind("jit(step)/attn.local/ax.attn_out/dot") is None
    assert window.attn_kind("jit(step)/ax.mlp/dot") is None
    assert window.attn_kind("jit(step)/dot") is None


def test_traced_cpu_run_reads_the_window_metrics():
    """A traced run of the cell at the reduced size: every metric the CPU
    can give is read (the roofline and the MFU need the chip's peaks), and
    the windowed attention is a part of its program's device time."""
    out = cell.run(CELL, 2 ** 31 + 13, 2.0, True, time.perf_counter(),
                   shrink=reduced, allow_cpu=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for k in ("decode_step_ms", "prefill_ms_per_step",
              "window_attn_ms_per_step", "prefill_window_attn_ms_per_step",
              "ax_ms_per_step", "device_idle", "host_ms_per_step"):
        assert m[f"{k}.code-edit"] > 0, k
    assert "window_attn_roofline.code-edit" not in m
    assert "mfu.code-edit" not in m
    assert m["window_attn_ms_per_step.code-edit"] < m["decode_step_ms.code-edit"]
    assert (m["prefill_window_attn_ms_per_step.code-edit"]
            < m["prefill_ms_per_step.code-edit"])
    assert out["checks"]["compiles_in_window"]["value"] == 0
