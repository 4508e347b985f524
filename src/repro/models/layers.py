"""Shared model layers: projections (exact or SWAPPER-approximate), norms,
RoPE/M-RoPE, GQA attention (chunked flash-style for long context, cached for
decode), MLPs, embeddings.

Parameters are plain nested dicts of arrays.  Logical sharding axes are
derived from parameter *paths* by ``axes_for_path`` (see launch/sharding.py
for the logical->mesh mapping); activations carry explicit logical
constraints via ``shard(...)`` which no-ops outside a mesh context.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AxPolicy, ModelConfig
from repro.launch.sharding import shard

__all__ = [
    "dense",
    "rmsnorm",
    "layernorm",
    "make_rope",
    "apply_rope",
    "attention",
    "attn_init",
    "attn_apply",
    "mlp_init",
    "mlp_apply",
    "axes_for_path",
]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def ninit(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# the projection target ``dense`` serves under each subtree name: the names
# the rules below read (``in/w``, ``o/w``, ...), and the map
# ``quant.ax.prepare_params`` finds a policy's projections by
PROJECTION_TARGETS = {"in": "mlp", "gate": "mlp", "out": "mlp",
                      "o": "attn_out", "q": "attn_qkv", "k": "attn_qkv",
                      "v": "attn_qkv"}


def axes_for_path(path: str, ndim: int):
    """Logical axes for a parameter, derived from its '/'-joined path.
    A leading 'layers' segment (scan-stacked) contributes a None axis.
    The leaves of a prepared projection (``quant.ax.prepare_weight``) shard
    like the ``w`` they replace: ``wq`` as it, ``wfg`` with its limb axis
    unsharded, ``sw`` (1, N) along N."""
    head, _, name = path.rpartition("/")
    if name in ("wq", "wfg", "sw"):
        extra = 1 if name == "wfg" else 0
        w_axes = list(axes_for_path(f"{head}/w", ndim - extra))
        if name == "sw":
            w_axes[-2] = None
        return tuple(w_axes[:-2]) + (None,) * extra + tuple(w_axes[-2:])
    parts = path.split("/")
    stacked = parts and parts[0] == "layers"
    if stacked:
        parts = parts[1:]
    leaf = "/".join(parts)
    base_ndim = ndim - (1 if stacked else 0)

    def a(*axes):
        assert len(axes) == base_ndim, (path, ndim, axes)
        return (("layers",) if stacked else ()) + tuple(axes)

    if leaf.endswith("embed/w") or leaf == "lm_head/w":
        return a("vocab", "embed") if not leaf.startswith("pos") else a(None, "embed")
    if leaf == "pos_embed/w":
        return a(None, "embed")
    if "/q/w" in leaf or leaf.endswith("q/w"):
        return a("embed", "heads")
    if leaf.endswith(("k/w", "v/w")):
        return a("embed", "heads")
    if leaf.endswith("o/w"):
        return a("heads", "embed")
    if leaf.endswith(("q/b", "k/b", "v/b")):
        return a("heads")
    if leaf.endswith("router/w"):
        return a("embed", "experts")
    if leaf.startswith("experts/") or "/experts/" in leaf:
        if leaf.endswith(("in/w", "gate/w")):
            return a("experts", "embed", "ff")
        if leaf.endswith("out/w"):
            return a("experts", "ff", "embed")
    if leaf.endswith(("in/w", "gate/w")):
        return a("embed", "ff")
    if leaf.endswith("out/w"):
        return a("ff", "embed")
    if leaf.endswith(("in/b", "gate/b")):
        return a("ff")
    if leaf.endswith(("out/b", "o/b")):
        return a("embed")
    if leaf.endswith("scale") or leaf.endswith("bias"):
        return a(*([None] * base_ndim))
    # rg-lru / ssm specific
    if leaf.endswith(("wa/w", "wx/w")):
        return a("ff", "ff")
    if leaf.endswith("conv/w"):
        return a(None, "ff")
    if leaf.endswith(("a_log", "d_skip", "dt_bias", "lam")):
        return a(*(["ff"] if base_ndim == 1 else [None] * base_ndim))
    if leaf.endswith(("wb/w", "wc/w")):
        return a("embed", None)
    if leaf.endswith("wdt/w"):
        return a("embed", None)
    return tuple([None] * ndim)


# ---------------------------------------------------------------------------
# projections — exact or SWAPPER-approximate per policy
# ---------------------------------------------------------------------------

def dense(x, p, ax: Optional[AxPolicy] = None, target: str = ""):
    """y = x @ w (+ b).  Routes through the SWAPPER approximate path when the
    policy covers this projection target (DESIGN.md §5).  Under an open
    adaptive-runtime scope the swap config enters as a traced triple instead
    of a baked constant, so the controller can re-tune without recompiles.
    A prepared ``p`` (``quant.ax.prepare_params``: serving) is read as
    prepared; a raw ``{"w": ...}`` is quantized per call."""
    if ax is not None and target in ax.targets:
        from repro.quant.ax import (ax_dense, ax_dense_dyn, ax_dense_prepared,
                                    is_prepared)
        from repro.runtime.scope import active_scope

        scope = active_scope()
        dyn = scope.triple_for(target) if scope is not None else None
        # HLO metadata only: traces and profiles name the approximate
        # path's ops (quantize, limbs, int8 dot, dequantize) by target
        with jax.named_scope(f"ax.{target}"):
            if is_prepared(p):
                y = ax_dense_prepared(x, p, ax, dyn, scope=scope,
                                      target=target)
            elif dyn is not None:
                y = ax_dense_dyn(x, p["w"].astype(x.dtype), ax, dyn,
                                 scope=scope, target=target)
            else:
                y = ax_dense(x, p["w"].astype(x.dtype), ax)
    else:
        y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def rmsnorm(x, p, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + p["scale"].astype(jnp.float32))).astype(x.dtype)


def layernorm(x, p, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (+ M-RoPE stub for qwen2-vl)
# ---------------------------------------------------------------------------

def make_rope(head_dim: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    return jnp.asarray(inv, jnp.float32)  # (hd/2,)


def apply_rope(x, pos, inv_freq):
    """x (B,S,H,hd); pos (B,S) int32 or (B,S,3) for M-RoPE."""
    hd = x.shape[-1]
    half = hd // 2
    if pos.ndim == 3:  # M-RoPE: temporal/height/width sections over freq dims
        sec = [half // 4, (half * 3) // 8, half - half // 4 - (half * 3) // 8]
        freqs = []
        start = 0
        for i, s in enumerate(sec):
            f = pos[..., i : i + 1].astype(jnp.float32) * inv_freq[start : start + s]
            freqs.append(f)
            start += s
        ang = jnp.concatenate(freqs, axis=-1)  # (B,S,half)
    else:
        ang = pos[..., None].astype(jnp.float32) * inv_freq  # (B,S,half)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinusoid_pos(seq, d_model, dtype):
    pos = np.arange(seq)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    ang = pos / (10000 ** (dim / d_model))
    emb = np.zeros((seq, d_model), np.float32)
    emb[:, 0::2] = np.sin(ang)
    emb[:, 1::2] = np.cos(ang)
    return jnp.asarray(emb, dtype)


# ---------------------------------------------------------------------------
# attention — chunked (flash-style online softmax) + decode path
# ---------------------------------------------------------------------------

def _mask_bias(qi, kj, *, causal, window, dtype):
    """(..., q, k) additive mask bias from global positions qi, kj."""
    d = qi[..., :, None] - kj[..., None, :]
    m = jnp.full(d.shape, True)
    if causal:
        m = m & (d >= 0)
    if window:
        m = m & (d < window)
    return jnp.where(m, 0.0, -1e30).astype(dtype)


# Cost-accounting mode for the dry-run: XLA's HloCostAnalysis counts a
# while-loop body ONCE regardless of trip count, so the roofline pass
# compiles small unrolled model variants and extrapolates (launch/dryrun.py).
# When True, the attention chunk loops are fully unrolled (and the q loop
# collapsed) so every FLOP appears in the HLO exactly once.
COST_MODE = False


def chunked_attention(
    q, k, v, q_pos, k_pos, *, causal=True, window=0, q_chunk=512, kv_chunk=1024,
):
    """Flash-style attention with O(chunk^2) memory.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) with H = KV * G.
    Positions are global indices (decode offsets supported).
    """
    B, Sq, H, hd = q.shape
    if COST_MODE:
        q_chunk = Sq  # single q block; kv scan unrolled below
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd)

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    # pad to multiples (positions padded with -1 -> masked out by causal)
    def padq(x, fill=0):
        pad = nq * q_chunk - Sq
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2), constant_values=fill)

    def padk(x, fill=0):
        pad = nk * kv_chunk - Sk
        return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2), constant_values=fill)

    qg = padq(qg)
    qp = padq(q_pos, fill=-(2**30))
    kk = padk(k)
    vv = padk(v)
    kp = padk(k_pos, fill=2**30)

    qg = qg.reshape(B, nq, q_chunk, KV, G, hd)
    qp = qp.reshape(B, nq, q_chunk)
    kk = kk.reshape(B, nk, kv_chunk, KV, hd)
    vv = vv.reshape(B, nk, kv_chunk, KV, hd)
    kp = kp.reshape(B, nk, kv_chunk)

    def q_block(args):
        qb, qpb = args  # (B, qc, KV, G, hd), (B, qc)

        def kv_step(carry, blk):
            m_prev, l_prev, acc = carry
            kb, vb, kpb = blk  # (B, kc, KV, hd), (B, kc)
            s = jnp.einsum("bqkgh,bckh->bkgqc", qb, kb).astype(jnp.float32) * scale
            bias = _mask_bias(qpb[:, None, None, :], kpb[:, None, None, :],
                              causal=causal, window=window, dtype=jnp.float32)
            s = s + bias  # (B,KV,G,qc,kc)
            m_new = jnp.maximum(m_prev, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(-1)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(vb.dtype), vb).astype(jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, G, q_chunk), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kk.swapaxes(0, 1), vv.swapaxes(0, 1), kp.swapaxes(0, 1)),
            unroll=nk if COST_MODE else 1,
        )
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)  # (B, qc, KV, G, hd)

    if nq == 1:
        out = q_block((qg[:, 0], qp[:, 0]))[:, None]
    else:
        out = jax.lax.map(q_block, (qg.swapaxes(0, 1), qp.swapaxes(0, 1))).swapaxes(0, 1)
    out = out.reshape(B, nq * q_chunk, KV, G, hd)[:, :Sq]
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, q_pos, kv_len, *, window=0):
    """Single-token attention over a (possibly ring-buffered) cache.

    q: (B, 1, H, hd); caches: (B, S, KV, hd); kv_len: valid prefix length.
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bckh->bkgc", qg, k_cache).astype(jnp.float32) * scale
    idx = jnp.arange(S)[None, :]
    valid = idx < kv_len[:, None]
    if window:
        valid = valid & (idx > (q_pos[:, None] - window))
    valid = valid & (idx <= q_pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgc,bckh->bkgh", p, v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (init + apply with optional cache)
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype):
    hd = cfg.head_dim_
    H = cfg.n_heads * hd
    KVH = cfg.n_kv_heads * hd
    ks = jax.random.split(key, 4)
    p = {
        "q": {"w": ninit(ks[0], (cfg.d_model, H), dtype)},
        "k": {"w": ninit(ks[1], (cfg.d_model, KVH), dtype)},
        "v": {"w": ninit(ks[2], (cfg.d_model, KVH), dtype)},
        "o": {"w": ninit(ks[3], (H, cfg.d_model), dtype)},
    }
    if cfg.qkv_bias:
        for nm, width in (("q", H), ("k", KVH), ("v", KVH)):
            p[nm]["b"] = jnp.zeros((width,), dtype)
    return p


def attn_apply(
    p, x, cfg: ModelConfig, *, pos, inv_freq, causal=True, window=0,
    mode="train", cache=None, cache_index=None, max_cache_len=0,
    q_chunk=512, kv_chunk=1024, cross_kv=None, prompt_lens=None,
    write_mask=None,
):
    """GQA attention block.

    mode='train'   — chunked flash-style attention, no cache, returns (y, None)
    mode='prefill' — same compute, additionally returns a decode-ready cache
                     padded to ``max_cache_len`` (ring layout for local layers)
    mode='decode'  — S==1 step against ``cache``; writes this step's K/V at
                     ``cache_index`` (mod ring for local layers — positions
                     older than the window being overwritten IS the window
                     mask) and returns the updated cache.

    ``cache_index`` is a scalar (one position for the whole batch, the wave
    path) or an int32 ``(B,)`` vector (per-slot positions, the token-granular
    path: each slot writes its own cache row and attends its own prefix
    length).  ``write_mask`` — optional ``(B,)`` bool gating the per-slot
    cache write (False rows are dropped, keeping a retired slot's cache
    region inert).  ``prompt_lens`` — optional ``(B,)`` int32 of real prompt
    lengths for prefill: right-pad key positions beyond a slot's length are
    pushed outside every causal window so padded prompts attend only to real
    tokens.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim_
    ax = cfg.ax
    q = dense(x, p["q"], ax, "attn_qkv").reshape(B, S, cfg.n_heads, hd)
    if cross_kv is None:
        k = dense(x, p["k"], ax, "attn_qkv").reshape(B, S, cfg.n_kv_heads, hd)
        v = dense(x, p["v"], ax, "attn_qkv").reshape(B, S, cfg.n_kv_heads, hd)
        if inv_freq is not None:
            q = apply_rope(q, pos, inv_freq)
            k = apply_rope(k, pos, inv_freq)
    else:
        k, v = cross_kv  # precomputed encoder K/V (whisper cross-attention)

    cdtype = jnp.dtype(cfg.compute_dtype)
    new_cache = None
    if mode == "decode" and cross_kv is None:
        ring = cache["k"].shape[1]
        ci = jnp.asarray(cache_index, jnp.int32)
        slot = (ci % ring) if window else ci
        if ci.ndim == 0:
            kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, slot, 0, 0))
            vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, slot, 0, 0))
        else:
            # per-slot positions: each batch row writes its own cache row;
            # masked rows are redirected out of bounds and dropped, so a
            # retired slot's cache region stays byte-identical until a fresh
            # request is spliced in
            tgt = slot if write_mask is None else jnp.where(write_mask, slot, ring)
            rows = jnp.arange(B)
            kc = cache["k"].at[rows, tgt].set(k[:, 0].astype(cache["k"].dtype),
                                              mode="drop")
            vc = cache["v"].at[rows, tgt].set(v[:, 0].astype(cache["v"].dtype),
                                              mode="drop")
        kc = shard(kc, "batch", "kv_seq", "kv_heads", None)
        vc = shard(vc, "batch", "kv_seq", "kv_heads", None)
        valid = jnp.minimum(ci + 1, ring)
        # scalar per-batch position (M-RoPE decode uses the temporal stream)
        qp = pos[:, 0] if pos.ndim == 2 else pos[:, 0, 0]
        out = decode_attention(
            q, kc, vc,
            q_pos=(jnp.full((B,), ring - 1, jnp.int32) if window else qp),
            kv_len=jnp.broadcast_to(valid.astype(jnp.int32), (B,)),
        )
        new_cache = {"k": kc, "v": vc}
    elif mode == "decode":
        out = decode_attention(
            q, k, v,
            q_pos=jnp.full((B,), k.shape[1] - 1, jnp.int32),
            kv_len=jnp.full((B,), k.shape[1], jnp.int32),
        )
    else:
        qpos = pos if pos.ndim == 2 else pos[..., 0]
        if cross_kv is not None:  # enc-dec cross attention: kv has its own axis
            kpos = jnp.broadcast_to(
                jnp.arange(k.shape[1], dtype=jnp.int32)[None], (B, k.shape[1])
            )
        else:
            kpos = qpos
        if prompt_lens is not None and cross_kv is None:
            # pad-mask prefill: push right-pad key positions outside every
            # causal window, so real queries attend only to real tokens
            # (pad queries produce garbage rows that nothing reads — the
            # engine samples at each slot's last *real* position)
            idx = jnp.arange(S, dtype=jnp.int32)[None, :]
            kpos = jnp.where(idx < prompt_lens[:, None], kpos, 2 ** 30)
        out = chunked_attention(
            q, k, v, qpos, kpos, causal=causal, window=window,
            q_chunk=q_chunk, kv_chunk=kv_chunk,
        )
        if mode == "prefill" and cross_kv is None:
            if window:
                assert prompt_lens is None, (
                    "pad-mask prefill: ring (sliding-window) caches hold the "
                    "last `window` positions including pads; token-granular "
                    "serving supports full-attention cache layouts only")
                ring = min(window, max_cache_len)
                take = min(ring, S)
                import numpy as _np

                last_pos = _np.arange(S - take, S)
                slots = _np.mod(last_pos, ring)
                kc = jnp.zeros((B, ring, cfg.n_kv_heads, hd), cdtype)
                vc = jnp.zeros((B, ring, cfg.n_kv_heads, hd), cdtype)
                kc = kc.at[:, slots].set(k[:, -take:].astype(cdtype))
                vc = vc.at[:, slots].set(v[:, -take:].astype(cdtype))
            else:
                pad = max_cache_len - S
                kc = jnp.pad(k.astype(cdtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v.astype(cdtype), ((0, 0), (0, pad), (0, 0), (0, 0)))
            kc = shard(kc, "batch", "kv_seq", "kv_heads", None)
            vc = shard(vc, "batch", "kv_seq", "kv_heads", None)
            new_cache = {"k": kc, "v": vc}

    out = out.reshape(B, S, cfg.n_heads * hd)
    return dense(out, p["o"], ax, "attn_out"), new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model, d_ff, act, dtype, bias=False):
    ks = jax.random.split(key, 3)
    p = {"in": {"w": ninit(ks[0], (d_model, d_ff), dtype)},
         "out": {"w": ninit(ks[1], (d_ff, d_model), dtype)}}
    if act == "silu":  # swiglu
        p["gate"] = {"w": ninit(ks[2], (d_model, d_ff), dtype)}
    if bias:
        p["in"]["b"] = jnp.zeros((d_ff,), dtype)
        p["out"]["b"] = jnp.zeros((d_model,), dtype)
    return p


def mlp_apply(p, x, act, ax: Optional[AxPolicy] = None):
    h = dense(x, p["in"], ax, "mlp")
    if act == "silu":
        h = jax.nn.silu(dense(x, p["gate"], ax, "mlp")) * h
    else:
        h = jax.nn.gelu(h)
    return dense(h, p["out"], ax, "mlp")
