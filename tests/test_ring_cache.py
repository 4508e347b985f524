"""Sliding-window (ring) caches on the token-granular path, on StarCoder2's
published block (``starcoder2-15b-published``: LayerNorm with bias, a bias
on every linear, every layer windowed) at a reduced size whose window is
shorter than the prompts, so every ring wraps.

Layers: the banded windowed attention equals a dense masked softmax.
Model: pad-mask prefill into a ring at several bucket paddings, a splice
into any slot, and decode past the wrap with slots at different
positions, each compared with the same model's full forward.  Scheduler:
token-granular serving of the windowed stack matches the wave oracle, and
counts the positions that left each slot's ring."""
import dataclasses
import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as CFG
import repro.runtime as R
from repro.configs.base import AxPolicy
from repro.fleet import BatcherConfig, ContinuousBatcher, Request
from repro.models import decode_step, init_cache, init_params, prefill
from repro.models.layers import chunked_attention, norm_apply
from repro.models.transformer import forward
from repro.serve import engine as E

WINDOW = 8


def _model(window=WINDOW, n_layers=2, dtype="float32", ax=None):
    """The published block, reduced, with every norm gain and bias drawn
    away from its init so that the LayerNorm bias and the ``o`` bias bite."""
    cfg = CFG.reduced(CFG.ARCHS["starcoder2-15b-published"])
    cfg = dataclasses.replace(cfg, n_layers=n_layers, local_window=window,
                              param_dtype=dtype, compute_dtype=dtype, ax=ax)
    params = init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)

    def perturb(path, x):
        name = getattr(path[-1], "key", "")
        if name not in ("b", "scale"):
            return x
        k = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2 ** 31)
        return x + (0.1 * jax.random.normal(k, x.shape)).astype(x.dtype)

    return cfg, jax.tree_util.tree_map_with_path(perturb, params)


def _full(params, cfg, toks):
    """Logits of the full causal forward over ``toks`` (1, T)."""
    return np.asarray(forward(params, {"tokens": jnp.asarray(toks)}, cfg,
                              mode="train")[0][0])


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,q_chunk,kv_chunk",
                         [(8, 4, 4), (8, 5, 3), (16, 8, 8), (3, 4, 2)])
def test_banded_attention_matches_dense_mask(window, q_chunk, kv_chunk):
    """Windowed self-attention that visits only each block's band of keys
    equals a dense softmax under the causal mask clipped to the window,
    with pad keys pushed out of a shorter row."""
    rng = np.random.default_rng(0)
    B, S, H, KV, hd = 2, 37, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    lens = jnp.asarray([37, 29])
    kpos = jnp.where(pos < lens[:, None], pos, 2 ** 30)
    out = chunked_attention(q, k, v, pos, kpos, window=window,
                            q_chunk=q_chunk, kv_chunk=kv_chunk)
    kk, vv = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision="highest") / np.sqrt(hd)
    d = pos[:, :, None] - kpos[:, None, :]
    s = jnp.where(((d >= 0) & (d < window))[:, None], s, -jnp.inf)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv,
                     precision="highest")
    np.testing.assert_allclose(out[0], ref[0], atol=2e-6)
    np.testing.assert_allclose(out[1, :29], ref[1, :29], atol=2e-6)


def test_layernorm_with_bias_and_o_bias():
    """The published block's parameters: each norm a gain offset from 1
    (``scale``) and a bias ``b``; a bias on ``o``; the norm is LayerNorm."""
    cfg, params = _model()
    layer = params["layers"]["p0"]
    assert set(layer["ln1"]) == set(layer["ln2"]) == {"scale", "b"}
    assert set(params["ln_f"]) == {"scale", "b"}
    assert layer["attn"]["o"]["b"].shape == (cfg.n_layers, cfg.d_model)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(3, cfg.d_model)) * 3
                    + 1, jnp.float32)
    p = {"scale": params["ln_f"]["scale"], "b": params["ln_f"]["b"]}
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    want = (x - mu) / jnp.sqrt(var + cfg.norm_eps) * (1 + p["scale"]) + p["b"]
    np.testing.assert_allclose(norm_apply(x, p, cfg), want, rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# model: ring prefill, splice, decode past the wrap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket", [13, 16, 24, 40])
def test_padmask_prefill_into_a_ring_at_every_padding(bucket):
    """A 13-token prompt padded to ``bucket``: its real logits, its ring
    (the last 8 real positions, never the pad tail) and the decode that
    follows past several wraps all match the full forward."""
    cfg, params = _model()
    L, T = 13, 30
    toks = np.random.default_rng(2).integers(0, cfg.vocab, (1, T)).astype(np.int32)
    full = _full(params, cfg, toks)
    padded = np.concatenate([toks[:, :L], np.full((1, bucket - L), 7, np.int32)], 1)
    lg, cache = prefill(params, {"tokens": jnp.asarray(padded)}, cfg,
                        max_cache_len=48, prompt_lens=jnp.asarray([L]))
    np.testing.assert_allclose(np.asarray(lg[0, :L]), full[:L], atol=1e-4)
    ring = cache["stack"]["p0"]["k"]
    assert ring.shape[2] == WINDOW                # (layers, B, ring, KV, hd)
    _, alone = prefill(params, {"tokens": jnp.asarray(toks[:, :L])}, cfg,
                       max_cache_len=48)
    np.testing.assert_allclose(np.asarray(ring),
                               np.asarray(alone["stack"]["p0"]["k"]),
                               atol=1e-5)
    for p in range(L, T):
        lg1, cache = decode_step(params, cache, jnp.asarray(toks[:, p:p + 1]),
                                 jnp.asarray([p], jnp.int32), cfg,
                                 write_mask=jnp.ones((1,), bool))
        np.testing.assert_allclose(np.asarray(lg1[0, 0]), full[p], atol=1e-4,
                                   err_msg=f"position {p}")


def test_splice_and_decode_past_the_wrap_per_slot():
    """Three prompts (5, 11 and 19 tokens; a window of 8) prefilled one by
    one in a 24-token bucket, spliced into slots 2, 0 and 3 of a 4-slot
    cache, then decoded together for 14 steps at their own positions: each
    slot's logits follow its own full forward as its ring wraps, and the
    empty slot 1, masked from writing, keeps its cache untouched."""
    cfg, params = _model()
    rng = np.random.default_rng(3)
    lens, slots, steps, bucket, mcl = (5, 11, 19), (2, 0, 3), 14, 24, 40
    seqs = [rng.integers(0, cfg.vocab, L + steps).astype(np.int32) for L in lens]
    cache = init_cache(cfg, 4, mcl)
    for seq, L, slot in zip(seqs, lens, slots):
        padded = np.concatenate([seq[:L], np.full(bucket - L, seq[L - 1])])
        _, fresh = E.prefill_one(params, padded[None], L, cfg,
                                 max_cache_len=mcl)
        cache = E.splice_slot_jit(cache, fresh, slot)
    untouched = np.asarray(cache["stack"]["p0"]["k"][:, 1])
    fulls = [_full(params, cfg, s[None]) for s in seqs]
    pos = np.zeros(4, np.int32)
    tok = np.zeros((4, 1), np.int32)
    active = np.zeros(4, bool)
    for L, slot in zip(lens, slots):
        pos[slot], active[slot] = L, True
    for i in range(steps):
        for seq, L, slot in zip(seqs, lens, slots):
            tok[slot, 0] = seq[L + i]
        lg, cache = decode_step(params, cache, jnp.asarray(tok),
                                jnp.asarray(pos + i), cfg,
                                write_mask=jnp.asarray(active))
        for full, L, slot in zip(fulls, lens, slots):
            np.testing.assert_allclose(np.asarray(lg[slot, 0]), full[L + i],
                                       atol=1e-4, err_msg=f"slot {slot} step {i}")
    assert np.array_equal(np.asarray(cache["stack"]["p0"]["k"][:, 1]), untouched)


# ---------------------------------------------------------------------------
# scheduler: token-granular serving of the windowed stack
# ---------------------------------------------------------------------------

def _controller(cfg):
    return R.AdaptiveController(
        R.SwapPolicy.from_ax_policy(cfg.ax), targets=cfg.ax.targets,
        cfg=R.AdaptiveConfig(min_observe_steps=10 ** 6))


def _clipped():
    from repro import obs

    return obs.default_registry().get(
        "repro_window_clipped_positions_total").value()


def test_token_granular_windowed_matches_wave_oracle():
    """Mixed prompts of 3-30 tokens against a window of 8 in buckets 16 and
    32: token-granular serving (splices mid-flight) gives every request the
    wave oracle's tokens, and the window-clipped counter adds, per decode
    step, max(0, pos - window) over the active slots."""
    cfg, params = _model(dtype="bfloat16", ax=AxPolicy(backend="mxu"))
    rng = np.random.default_rng(4)
    trace = [Request(rid, rng.integers(0, cfg.vocab, int(rng.integers(3, 31))),
                     max_new=int(rng.integers(1, 9))) for rid in range(9)]

    def serve(token_granular):
        bcfg = BatcherConfig(n_slots=3, prompt_buckets=(16, 32),
                             new_token_bucket=8, token_granular=token_granular)
        bat = ContinuousBatcher(params, cfg, bcfg, adaptive=_controller(cfg))
        for r in trace:
            bat.submit(Request(r.rid, np.asarray(r.tokens).copy(), r.max_new))
        return {c.rid: c.tokens.tolist() for c in bat.run()}, bat

    wave, _ = serve(False)
    before = _clipped()
    tok, bat = serve(True)
    assert wave == tok and set(tok) == {r.rid for r in trace}
    assert bat.stats["splices"] > 0
    want = sum(max(0, p - WINDOW) for r in trace
               for p in range(len(r.tokens), len(r.tokens) + r.max_new - 1))
    assert want > 0 and _clipped() - before == want


def test_token_granular_refuses_recurrent_and_ssm_stacks():
    for name in ("recurrentgemma-2b", "mamba2-370m"):
        cfg = CFG.reduced(CFG.ARCHS[name])
        params = init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(AssertionError, match="recurrent, ssm and encdec"):
            ContinuousBatcher(params, cfg, BatcherConfig(token_granular=True))


def test_step_programs_name_attention_by_kind():
    """The token step and the prefill open ``attn.local`` on the windowed
    stack and ``attn.global`` on a full-attention one (HLO metadata)."""
    qwen = dataclasses.replace(CFG.reduced(CFG.ARCHS["qwen2-72b"]), n_layers=1)
    for cfg, kind in ((_model()[0], "attn.local"), (qwen, "attn.global")):
        params = init_params(jax.random.PRNGKey(0), cfg)
        B, L = 2, 24
        cache = init_cache(cfg, B, L)
        z = jnp.zeros(B, jnp.int32)
        step = E._token_step_fn(cfg, None, 0.0, False, None, cache, B).lower(
            params, cache, z, jax.random.PRNGKey(0), z,
            jnp.ones(B, bool)).compile().as_text()
        pre = E._prefill_one_fn(cfg, None, 16, L, 0.0).lower(
            params, jnp.zeros((1, 16), jnp.int32), jnp.asarray([5]),
            jax.random.PRNGKey(0)).compile().as_text()
        for text in (step, pre):
            names = set(re.findall(r'op_name="([^"]+)"', text))
            assert any(f"/{kind}/" in n for n in names), kind
