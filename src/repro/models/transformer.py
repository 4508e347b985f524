"""Generic decoder-only stack covering the dense / moe / hybrid / ssm / vlm
families.  Layers of the repeating pattern are scan-stacked per
position-in-period (compile-time O(period), not O(n_layers)); leading
heterogeneous layers (e.g. deepseek's dense layer 0) and the pattern
remainder are unrolled.

Layer kinds (ModelConfig.layer_kinds()):
    'global'    — full-attention block + FFN
    'local'     — sliding-window attention block + FFN
    'recurrent' — RG-LRU block + FFN
    'ssm'       — Mamba2 SSD block (no separate FFN branch)
    'dense_ffn' — full attention + dense FFN (inside MoE models)

Modes:
    train   — logits for next-token loss, no caches
    prefill — logits + decode-ready cache pytree (padded to max_cache_len)
    decode  — single-token step against the cache (cache_index = position)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.launch.sharding import shard
from repro.quant.ax import take_layer

from . import blocks
from .layers import attn_apply, attn_init, make_rope, mlp_apply, mlp_init, ninit, rmsnorm

__all__ = ["init_params", "forward", "Stack", "init_cache"]


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, kind: str, dtype):
    ks = jax.random.split(key, 4)
    p = {"ln1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}}
    if kind in ("global", "local", "dense_ffn"):
        p["attn"] = attn_init(ks[0], cfg, dtype)
    elif kind == "recurrent":
        p["rec"] = blocks.rglru_init(ks[0], cfg, dtype)
    elif kind == "ssm":
        p["ssm"] = blocks.ssd_init(ks[0], cfg, dtype)
        return p  # mamba block: single residual branch
    else:
        raise ValueError(kind)
    p["ln2"] = {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}
    if cfg.family == "moe" and kind != "dense_ffn":
        p["moe"] = blocks.moe_init(ks[1], cfg, dtype)
    else:
        ff = cfg.d_ff
        p["mlp"] = mlp_init(ks[1], cfg.d_model, ff, cfg.act, dtype,
                            bias=cfg.qkv_bias and cfg.act == "gelu")
    return p


def _layer_apply(p, x, cfg: ModelConfig, kind: str, *, pos, inv_freq, mode,
                 cache=None, cache_index=None, max_cache_len=0,
                 prompt_lens=None, write_mask=None):
    """Returns (x, new_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if kind in ("global", "local", "dense_ffn"):
        window = cfg.local_window if kind == "local" else 0
        a, new_cache = attn_apply(
            p["attn"], h, cfg, pos=pos, inv_freq=inv_freq, causal=True,
            window=window, mode=mode, cache=cache, cache_index=cache_index,
            max_cache_len=max_cache_len, prompt_lens=prompt_lens,
            write_mask=write_mask,
        )
    elif kind == "recurrent":
        rc = cache
        if mode == "prefill" and rc is None:
            rc = _empty_cache(cfg, kind, x.shape[0], max_cache_len, x.dtype)
        a, new_cache = blocks.rglru_apply(p["rec"], h, cfg, rc if mode != "train" else None)
    elif kind == "ssm":
        rc = cache
        if mode == "prefill" and rc is None:
            rc = _empty_cache(cfg, kind, x.shape[0], max_cache_len, x.dtype)
        a, new_cache = blocks.ssd_apply(p["ssm"], h, cfg, rc if mode != "train" else None)
        x = shard(x + a, "batch", "seq", None)
        return x, new_cache, aux
    else:
        raise ValueError(kind)
    x = x + a
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        m, aux = blocks.moe_apply(p["moe"], h, cfg)
    else:
        m = mlp_apply(p["mlp"], h, cfg.act, cfg.ax)
    x = shard(x + m, "batch", "seq", None)
    return x, new_cache, aux


def _empty_cache(cfg: ModelConfig, kind: str, batch, max_len, dtype):
    if kind == "ssm":
        din = cfg.ssm_expand * cfg.d_model
        H = din // cfg.ssm_head_dim
        return {
            "h": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch, 3, din + 2 * cfg.ssm_state), dtype),
        }
    hd = cfg.head_dim_
    if kind in ("global", "dense_ffn"):
        shp = (batch, max_len, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
    if kind == "local":
        ring = min(cfg.local_window, max_len)
        shp = (batch, ring, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
    if kind == "recurrent":
        return {"h": jnp.zeros((batch, cfg.d_rnn), jnp.float32),
                "conv": jnp.zeros((batch, 3, cfg.d_rnn), dtype)}
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# stack structure
# ---------------------------------------------------------------------------

class Stack:
    """Which layers are scan-stacked (repeating pattern) vs unrolled."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        kinds = list(cfg.layer_kinds())
        self.lead_kinds = kinds[: cfg.first_dense]
        body = kinds[cfg.first_dense:]
        period = (list(cfg.pattern) if cfg.pattern
                  else (["ssm"] if cfg.family == "ssm"
                        else (["global", "moe_"][0:1] if cfg.family != "moe" else ["global"])))
        # normalize: for moe family the body kind string is still 'global'
        self.period = period
        self.n_periods = len(body) // len(period)
        self.rest_kinds = body[self.n_periods * len(period):]


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache pytree for a model (used by tests / serving)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    st = Stack(cfg)
    cache = {}
    for i, kind in enumerate(st.lead_kinds):
        cache[f"lead{i}"] = _empty_cache(cfg, kind, batch, max_len, dtype)
    if st.n_periods:
        cache["stack"] = {
            f"p{j}": jax.tree.map(
                lambda l: jnp.broadcast_to(l, (st.n_periods,) + l.shape).copy(),
                _empty_cache(cfg, kind, batch, max_len, dtype),
            )
            for j, kind in enumerate(st.period)
        }
    for i, kind in enumerate(st.rest_kinds):
        cache[f"rest{i}"] = _empty_cache(cfg, kind, batch, max_len, dtype)
    return cache


def init_params(key, cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    st = Stack(cfg)
    keys = jax.random.split(key, 8)
    V = cfg.padded_vocab
    params = {
        "embed": {"w": ninit(keys[0], (V, cfg.d_model), dtype, scale=0.02)},
        "ln_f": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": ninit(keys[1], (V, cfg.d_model), dtype, scale=0.02)}
    for i, kind in enumerate(st.lead_kinds):
        params[f"lead{i}"] = _layer_init(jax.random.fold_in(keys[2], i), cfg, kind, dtype)
    if st.n_periods:
        def stacked(key, kind):
            return jax.vmap(lambda k: _layer_init(k, cfg, kind, dtype))(
                jax.random.split(key, st.n_periods)
            )
        params["layers"] = {
            f"p{j}": stacked(jax.random.fold_in(keys[3], j), kind)
            for j, kind in enumerate(st.period)
        }
    for i, kind in enumerate(st.rest_kinds):
        params[f"rest{i}"] = _layer_init(jax.random.fold_in(keys[4], i), cfg, kind, dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _embed_in(params, batch, cfg: ModelConfig, dtype):
    if "embeds" in batch:  # vlm-style stub frontend
        x = batch["embeds"].astype(dtype)
        B, S = x.shape[:2]
    else:
        tok = batch["tokens"]
        B, S = tok.shape
        x = jnp.take(params["embed"]["w"], tok, axis=0).astype(dtype)
        if cfg.family != "ssm":
            x = x * jnp.asarray(cfg.d_model, dtype) ** 0.5 if cfg.tie_embeddings else x
    if "pos" in batch:
        pos = batch["pos"]
    else:
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        if cfg.mrope:
            pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
    return x, pos


def forward(
    params,
    batch,
    cfg: ModelConfig,
    par: Optional[ParallelConfig] = None,
    *,
    mode: str = "train",
    cache=None,
    cache_index=None,
    max_cache_len: int = 0,
    prompt_lens=None,
    write_mask=None,
):
    """Returns (logits, new_cache, aux_loss).

    ``cache_index`` — decode position: a scalar (whole-batch, the wave path)
    or an int32 ``(B,)`` vector of per-slot positions (token-granular
    serving).  ``prompt_lens`` — optional ``(B,)`` real prompt lengths for
    pad-mask prefill (right-padded prompts attend only to real tokens);
    requires a full-attention stack (no ring/recurrent/ssm state, which
    would absorb the pad tail).  ``write_mask`` — optional ``(B,)`` bool
    gating decode cache writes per slot (retired slots stay inert).
    """
    par = par or ParallelConfig()
    dtype = jnp.dtype(cfg.compute_dtype)
    st = Stack(cfg)
    if prompt_lens is not None:
        assert all(k in ("global", "dense_ffn") for k in cfg.layer_kinds()), (
            f"pad-mask prefill needs a full-attention stack; "
            f"{cfg.name} has kinds {sorted(set(cfg.layer_kinds()))}")
    x, pos = _embed_in(params, batch, cfg, dtype)
    x = shard(x, "batch", "seq", None)
    B = x.shape[0]
    if mode == "decode" and "pos" not in batch:
        ci = jnp.asarray(cache_index, jnp.int32)
        pos = jnp.broadcast_to(ci[:, None] if ci.ndim == 1 else ci, (B, 1))
        pos = pos.astype(jnp.int32)
        if cfg.mrope:
            pos = jnp.broadcast_to(pos[..., None], (B, 1, 3))
    inv_freq = make_rope(cfg.head_dim_, cfg.rope_theta) if cfg.n_heads else None

    apply_kw = dict(pos=pos, inv_freq=inv_freq, mode=mode,
                    cache_index=cache_index, max_cache_len=max_cache_len,
                    prompt_lens=prompt_lens if mode != "decode" else None,
                    write_mask=write_mask if mode == "decode" else None)
    new_cache = {}
    aux = jnp.zeros((), jnp.float32)

    # --- leading unrolled layers ------------------------------------------
    for i, kind in enumerate(st.lead_kinds):
        lc = cache[f"lead{i}"] if mode == "decode" else None
        x, nc, a = _layer_apply(params[f"lead{i}"], x, cfg, kind, cache=lc, **apply_kw)
        aux = aux + a
        if mode != "train":
            new_cache[f"lead{i}"] = nc

    # --- scan over pattern periods -----------------------------------------
    if st.n_periods:
        period = st.period

        def body(carry, xs):
            x, aux = carry
            pp, cc = xs
            ncs = {}
            for j, kind in enumerate(period):
                lc = cc[f"p{j}"] if cc is not None else None
                x, nc, a = _layer_apply(pp[f"p{j}"], x, cfg, kind, cache=lc, **apply_kw)
                aux = aux + a
                ncs[f"p{j}"] = nc if nc is not None else 0
            return (x, aux), (ncs if mode != "train" else 0)

        scan_body = body
        if mode == "train" and par.remat == "layer":
            scan_body = jax.checkpoint(body, prevent_cse=False)
        elif mode == "train" and par.remat == "dots":
            scan_body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        cache_xs = cache["stack"] if mode == "decode" else None
        if mode == "decode":
            xs = (params["layers"], cache_xs)
        else:
            xs = (params["layers"], None)
            # scan requires a pytree with a leading axis; replace None by
            # per-period dummies
            xs = (params["layers"],
                  {f"p{j}": jnp.zeros((st.n_periods,), jnp.float32) for j in range(len(period))})

            def body_nocache(carry, xs):
                x, aux = carry
                pp, _ = xs
                ncs = {}
                for j, kind in enumerate(period):
                    x2, nc, a = _layer_apply(pp[f"p{j}"], x, cfg, kind, cache=None, **apply_kw)
                    x = x2
                    aux = aux + a
                    ncs[f"p{j}"] = nc if nc is not None else 0
                return (x, aux), (ncs if mode == "prefill" else 0)

            scan_body = body_nocache
            if mode == "train" and par.remat == "layer":
                scan_body = jax.checkpoint(body_nocache, prevent_cse=False)
            elif mode == "train" and par.remat == "dots":
                scan_body = jax.checkpoint(
                    body_nocache, prevent_cse=False,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        if par.scan_layers:
            (x, aux), ys = jax.lax.scan(scan_body, (x, aux), xs)
            if mode != "train":
                new_cache["stack"] = ys
        else:
            ys_list = []
            for n in range(st.n_periods):
                sl = take_layer(xs, n)
                (x, aux), y = scan_body((x, aux), sl)
                ys_list.append(y)
            if mode != "train":
                new_cache["stack"] = jax.tree.map(lambda *ts: jnp.stack(ts), *ys_list)

    # --- trailing unrolled layers -------------------------------------------
    for i, kind in enumerate(st.rest_kinds):
        lc = cache[f"rest{i}"] if mode == "decode" else None
        x, nc, a = _layer_apply(params[f"rest{i}"], x, cfg, kind, cache=lc, **apply_kw)
        aux = aux + a
        if mode != "train":
            new_cache[f"rest{i}"] = nc

    x = rmsnorm(x, params["ln_f"], cfg.norm_eps)
    head_w = params["embed"]["w"] if cfg.tie_embeddings else params["lm_head"]["w"]
    logits = jnp.einsum("bsd,vd->bsv", x, head_w.astype(x.dtype))
    logits = shard(logits, "batch", None, "vocab")  # vocab-parallel loss
    return logits, (new_cache if mode != "train" else None), aux
