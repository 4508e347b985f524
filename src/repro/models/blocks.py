"""Non-dense temporal / FFN blocks: MoE (token-choice top-k with capacity,
expert-parallel), RG-LRU (RecurrentGemma), and Mamba2 SSD (chunked
state-space duality).  All are jit/scan/vmap-safe and provide decode paths
with O(1) state."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.launch.sharding import shard

from .layers import dense, ninit

__all__ = [
    "moe_init", "moe_apply",
    "rglru_init", "rglru_apply",
    "ssd_init", "ssd_apply",
]

CAPACITY_FACTOR = 1.25


# ===========================================================================
# Mixture of Experts — token-choice top-k, capacity-bounded scatter dispatch,
# experts sharded over the 'model' axis (EP).
# ===========================================================================

def moe_init(key, cfg: ModelConfig, dtype):
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": {"w": ninit(ks[0], (D, E), jnp.float32)},
        "experts": {
            "in": {"w": ninit(ks[1], (E, D, F), dtype, scale=1.0 / math.sqrt(D))},
            "gate": {"w": ninit(ks[2], (E, D, F), dtype, scale=1.0 / math.sqrt(D))},
            "out": {"w": ninit(ks[3], (E, F, D), dtype, scale=1.0 / math.sqrt(F))},
        },
    }
    if cfg.n_shared_experts:
        from .layers import mlp_init

        p["shared"] = mlp_init(ks[4], D, cfg.n_shared_experts * cfg.moe_d_ff,
                               "silu", dtype)
    return p


def _dispatch(flat, topi, k, E, C, dtype):
    """Capacity-bounded scatter dispatch: running per-expert slot counters.
    Returns (buf (E,C,D), slots [T]xk, keeps [T]xk)."""
    T, D = flat.shape
    buf = jnp.zeros((E, C, D), dtype)
    slots, keeps = [], []
    counts = jnp.zeros((E,), jnp.int32)
    for j in range(k):
        oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.int32)    # (T, E)
        pos = jnp.cumsum(oh, axis=0) - oh + counts[None, :]
        counts = counts + oh.sum(0)
        slot = (pos * oh).sum(-1)                              # (T,)
        keep = slot < C
        slots.append(jnp.where(keep, slot, C - 1))
        keeps.append(keep)
        buf = buf.at[topi[:, j], slots[-1]].add(
            flat * keep[:, None].astype(flat.dtype), mode="drop"
        )
    return buf, jnp.stack(slots, 1), jnp.stack(keeps, 1)


def _dispatch_distributed(flat, topi, k, E, C_loc, dtype, mesh, batch_axes):
    """Per-data-shard capacity dispatch via shard_map (the production EP
    pattern).  A global-cumsum scatter would force GSPMD to all-reduce the
    whole (E,C,D) buffer across data shards every layer (measured: ~70 GB/dev
    per step on granite); giving every data shard its own capacity slice
    turns that into an all-to-all-sized reshard (EXPERIMENTS.md §Perf)."""
    from jax.sharding import PartitionSpec as P

    axes = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,)

    def local(fl, ti):
        buf, slots, keeps = _dispatch(fl, ti, k, E, C_loc, dtype)
        return buf, slots, keeps

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None), P(axes, None)),
        out_specs=(P(None, axes, None), P(axes, None), P(axes, None)),
        check_vma=False,
    )
    return fn(flat, topi)


def moe_apply(p, x, cfg: ModelConfig):
    from repro.launch.sharding import _ctx

    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    flat = x.reshape(T, D)

    logits = (flat.astype(jnp.float32) @ p["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                      # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    st = getattr(_ctx, "state", None)
    mesh = st[0] if st else None
    token_axes = None
    n_shards = 1
    if mesh is not None and st[1]["batch"]:
        b = st[1]["batch"]
        token_axes = b if isinstance(b, tuple) else (b,)
        # tokens (B,S,D)->(T,D): the flattened T dim carries the composite
        # batch x seq sharding (row-major), so dispatch over both
        if st[1].get("seq"):
            token_axes = token_axes + (st[1]["seq"],)
        n_shards = int(np.prod([mesh.shape[a] for a in token_axes]))

    win, wg, wout = (p["experts"][n]["w"] for n in ("in", "gate", "out"))

    def expert_ffn(buf):
        # expert FFN (swiglu), batched over E — EP over the 'model' axis with
        # the capacity dim kept sharded over the data axes, so the
        # tokens->experts reshard is an all-to-all (NOT buffer replication)
        buf = shard(buf, "experts", "batch", None)             # dispatch a2a
        h = jnp.einsum("ecd,edf->ecf", buf, win.astype(buf.dtype))
        g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wout.astype(buf.dtype))
        return shard(y, "experts", "batch", None)

    if n_shards > 1 and T % n_shards == 0:
        # distributed: per-token-shard capacity.  Dispatch scatter and the
        # combine gather run shard-LOCALLY (shard_map) against each shard's
        # own capacity slice; the only cross-device movement is the
        # (E,C,D) buffer resharding tokens<->experts — a true all-to-all.
        from jax.sharding import NamedSharding, PartitionSpec as P

        flat_c = jax.lax.with_sharding_constraint(
            flat, NamedSharding(mesh, P(token_axes, None)))
        topi_c = jax.lax.with_sharding_constraint(
            topi, NamedSharding(mesh, P(token_axes, None)))
        T_loc = T // n_shards
        C_loc = int(np.ceil(T_loc * k / E * cfg.moe_capacity))
        C_loc = min(max(C_loc, 8), T_loc)
        buf, slots, keeps = _dispatch_distributed(
            flat_c, topi_c, k, E, C_loc, x.dtype, mesh, token_axes
        )

        y = expert_ffn(buf)
        # combine all-to-all: bring each shard's capacity slice home
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(None, token_axes, None)))

        def local_combine(y_loc, ti, tv, sl, kp):
            yf = y_loc.reshape(E * C_loc, D)
            o = jnp.zeros((ti.shape[0], D), x.dtype)
            for j in range(k):
                idx = ti[:, j].astype(jnp.int32) * C_loc + sl[:, j]
                w = (tv[:, j] * kp[:, j].astype(jnp.float32)).astype(x.dtype)
                o = o + jnp.take(yf, idx, axis=0) * w[:, None]
            return o

        tok_spec = P(token_axes, None)
        out = jax.shard_map(
            local_combine, mesh=mesh,
            in_specs=(P(None, token_axes, None), tok_spec, tok_spec, tok_spec,
                      tok_spec),
            out_specs=tok_spec,
            check_vma=False,
        )(y, topi_c, topv, slots, keeps)
    else:
        # reference path (single device / tests): global capacity
        C_tot = int(np.ceil(T * k / E * cfg.moe_capacity))
        C_tot = min(max(C_tot, 8), T)
        buf, slots, keeps = _dispatch(flat, topi, k, E, C_tot, x.dtype)
        y = expert_ffn(buf)
        out = jnp.zeros((T, D), x.dtype)
        yflat = y.reshape(-1, D)
        for j in range(k):
            idx = topi[:, j].astype(jnp.int32) * C_tot + slots[:, j]
            gathered = jnp.take(yflat, idx, axis=0)
            w = (topv[:, j] * keeps[:, j].astype(jnp.float32)).astype(x.dtype)
            out = out + gathered * w[:, None]

    if "shared" in p:
        from .layers import mlp_apply

        out = out + mlp_apply(p["shared"], flat, "silu", cfg.ax).reshape(T, D)
    # aux load-balancing loss term is returned by the caller via probs stats
    aux = E * jnp.mean(
        jnp.mean(probs, axis=0) * jnp.mean(jax.nn.one_hot(topi[:, 0], E), axis=0)
    )
    return out.reshape(B, S, D), aux


# ===========================================================================
# RG-LRU (RecurrentGemma / Griffin)
# ===========================================================================

_LRU_C = 8.0


def rglru_init(key, cfg: ModelConfig, dtype):
    D, R = cfg.d_model, cfg.d_rnn
    ks = jax.random.split(key, 6)
    # Lambda parametrizes the per-channel decay a = exp(-c*softplus(lam)*r);
    # init spreads decays across the (0.9, 0.999)-ish band (Griffin recipe).
    lam = jnp.asarray(np.random.default_rng(0).uniform(0.3, 0.8, R), jnp.float32)
    return {
        "in": {"w": ninit(ks[0], (D, R), dtype)},
        "gate": {"w": ninit(ks[1], (D, R), dtype)},
        "conv": {"w": ninit(ks[2], (4, R), dtype, scale=0.5)},
        "wa": {"w": ninit(ks[3], (R, R), dtype)},
        "wx": {"w": ninit(ks[4], (R, R), dtype)},
        "lam": lam,
        "out": {"w": ninit(ks[5], (R, D), dtype)},
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv, width W.  x (B,S,Ch), w (W,Ch).
    state (B, W-1, Ch) for decode; returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
        xp = jnp.concatenate([pad, x], axis=1)
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(W))
    new_state = xp[:, -(W - 1) :] if W > 1 else None
    return y, new_state


def rglru_apply(p, x, cfg: ModelConfig, cache: Optional[dict] = None):
    """Returns (y, new_cache).  cache = {'h': (B,R) f32, 'conv': (B,3,R)}."""
    B, S, D = x.shape
    xr = dense(x, p["in"], cfg.ax, "mlp")
    gate = dense(x, p["gate"], cfg.ax, "mlp")
    conv_state = cache["conv"] if cache is not None else None
    xc, new_conv = _causal_conv(xr, p["conv"]["w"].astype(xr.dtype), conv_state)

    xf = xc.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ p["wa"]["w"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf @ p["wx"]["w"].astype(jnp.float32))
    log_a = -_LRU_C * jax.nn.softplus(p["lam"]) * r            # (B,S,R)
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * (i * xf)

    if cache is None or S > 1:
        h0 = cache["h"][:, None, :] if cache is not None else None

        def comb(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2

        aa, bb = jax.lax.associative_scan(comb, (a, b), axis=1)
        h = bb if h0 is None else bb + aa * h0
        h_last = h[:, -1, :]
    else:
        h = (a[:, 0] * cache["h"] + b[:, 0])[:, None, :]
        h_last = h[:, 0]

    y = (h.astype(x.dtype)) * jax.nn.gelu(gate)
    out = dense(y, p["out"], cfg.ax, "mlp")
    new_cache = None
    if cache is not None:
        new_cache = {"h": h_last, "conv": new_conv}
    return out, new_cache


# ===========================================================================
# Mamba2 SSD (state-space duality, chunked)
# ===========================================================================

def ssd_init(key, cfg: ModelConfig, dtype):
    D = cfg.d_model
    din = cfg.ssm_expand * D
    H = din // cfg.ssm_head_dim
    N = cfg.ssm_state
    ks = jax.random.split(key, 8)
    return {
        "in": {"w": ninit(ks[0], (D, din), dtype)},
        "gate": {"w": ninit(ks[1], (D, din), dtype)},
        "wb": {"w": ninit(ks[2], (D, N), dtype)},
        "wc": {"w": ninit(ks[3], (D, N), dtype)},
        "wdt": {"w": ninit(ks[4], (D, H), dtype)},
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "a_log": jnp.asarray(np.log(np.linspace(1.0, 16.0, H)), jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "conv": {"w": ninit(ks[5], (4, din + 2 * N), dtype, scale=0.5)},
        "out": {"w": ninit(ks[6], (din, D), dtype)},
    }


def ssd_apply(p, x, cfg: ModelConfig, cache: Optional[dict] = None):
    """Chunked SSD.  cache = {'h': (B,H,hd,N) f32, 'conv': (B,3,Ch)}."""
    B, S, D = x.shape
    hd = cfg.ssm_head_dim
    din = cfg.ssm_expand * D
    H = din // hd
    N = cfg.ssm_state
    ax = cfg.ax

    xin = dense(x, p["in"], ax, "mlp")
    z = dense(x, p["gate"], ax, "mlp")
    Bc = dense(x, p["wb"], None, "")
    Cc = dense(x, p["wc"], None, "")
    dt = jax.nn.softplus(
        (x @ p["wdt"]["w"].astype(x.dtype)).astype(jnp.float32) + p["dt_bias"]
    )                                                           # (B,S,H)

    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    conv_out, new_conv = _causal_conv(conv_in, p["conv"]["w"].astype(x.dtype), conv_state)
    conv_out = jax.nn.silu(conv_out)
    xin = conv_out[..., :din]
    Bc = conv_out[..., din : din + N].astype(jnp.float32)
    Cc = conv_out[..., din + N :].astype(jnp.float32)

    a = jnp.exp(-jnp.exp(p["a_log"]) * dt)                      # (B,S,H) in (0,1)
    xh = xin.reshape(B, S, H, hd).astype(jnp.float32)
    dx = dt[..., None] * xh                                     # (B,S,H,hd)

    if cache is not None and S == 1:
        h0 = cache["h"]                                         # (B,H,hd,N)
        h = a[:, 0, :, None, None] * h0 + dx[:, 0, :, :, None] * Bc[:, 0, None, None, :]
        y = jnp.einsum("bhdn,bn->bhd", h, Cc[:, 0])
        y = y + p["d_skip"][None, :, None] * xh[:, 0]
        y = y.reshape(B, 1, din)
        out = dense((y.astype(x.dtype)) * jax.nn.silu(z), p["out"], ax, "mlp")
        return out, {"h": h, "conv": new_conv}

    # ---- chunked scan over sequence --------------------------------------
    L = min(cfg.ssm_chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    def r(t, *shape):
        return t.reshape(B, nc, L, *shape)

    a_c = r(a, H)
    la = jnp.cumsum(jnp.log(jnp.maximum(a_c, 1e-30)), axis=2)   # (B,nc,L,H)
    dx_c = r(dx, H, hd)
    B_c = r(Bc, N)
    C_c = r(Cc, N)

    # intra-chunk (attention-like): Y1[j] = sum_{i<=j} (C_j.B_i) decay(i->j) dx_i
    sbc = jnp.einsum("bnjs,bnis->bnij", C_c, B_c)               # [..., i, j]
    diff = la[:, :, :, None, :] - la[:, :, None, :, :]          # [..., j, i, H]
    mask = (jnp.arange(L)[:, None] >= jnp.arange(L)[None, :])[None, None, :, :, None]
    # mask BEFORE exp: exp(+large) on the upper triangle would be inf and
    # poison gradients through the where (inf * 0 = nan in the vjp)
    w_ji = jnp.exp(jnp.where(mask, diff, -1e30))                # (B,nc,j,i,H)
    y_intra = jnp.einsum("bnij,bnjih,bnihd->bnjhd", sbc, w_ji, dx_c)

    # chunk summaries: T_n = sum_i decay(i->end) dx_i B_i^T   (B,nc,H,hd,N)
    dec_end = jnp.exp(la[:, :, -1:, :] - la)                    # (B,nc,L,H)
    Tn = jnp.einsum("bnlh,bnlhd,bnls->bnhds", dec_end, dx_c, B_c)
    A_n = jnp.exp(la[:, :, -1, :])                              # (B,nc,H)

    # cross-chunk scan
    h_init = cache["h"] if cache is not None else jnp.zeros((B, H, hd, N), jnp.float32)

    def chunk_step(h, blk):
        A_k, T_k = blk                                           # (B,H), (B,H,hd,N)
        h_new = A_k[:, :, None, None] * h + T_k
        return h_new, h
    h_last, h_prev = jax.lax.scan(
        chunk_step, h_init, (A_n.swapaxes(0, 1), Tn.swapaxes(0, 1))
    )
    h_prev = h_prev.swapaxes(0, 1)                               # (B,nc,H,hd,N) state BEFORE chunk

    # inter-chunk: Y2[j] = C_j . (decay(start->j) * h_prev)
    dec_from_start = jnp.exp(la)                                 # (B,nc,L,H)
    y_inter = jnp.einsum("bnls,bnlh,bnhds->bnlhd", C_c, dec_from_start, h_prev)

    y = (y_intra + y_inter).reshape(B, S, H, hd)
    y = y + p["d_skip"][None, None, :, None] * xh
    y = y.reshape(B, S, din).astype(x.dtype) * jax.nn.silu(z)
    out = dense(y, p["out"], ax, "mlp")
    new_cache = {"h": h_last, "conv": new_conv} if cache is not None else None
    return out, new_cache
