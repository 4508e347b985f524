"""The plain reference: the served model's forward pass in float32
``jax.numpy``, written from the configuration file and the multiplier's
definition.  It imports nothing of the program.  It reads the weights the
benchmark made (``harness/weights.py``) by their place in the parameter
tree, and computes everything else itself.

The model (qwen2 and starcoder2 as configured): token embedding; per layer
RMSNorm (gain ``1 + scale``), q/k/v projections (+ bias), rotary position
embedding on the two halves of each head, causal grouped-query attention,
output projection, residual; RMSNorm, MLP (SwiGLU, or tanh-GELU with
biases), residual; final RMSNorm and an untied LM head.

The approximate projections (the configuration's ``approx.targets``):
each row of the input and each column of the weight is quantized to int8
(symmetric, scale = max|.|/127, round half to even), the int8 products are
taken by the approximate multiplier ``mul8s_trunc{ka}_{kb}`` -- sign times
(|a| with its low ``ka`` bits cleared) times (|b| with its low ``kb`` bits
cleared), ``m(a, b) = f(a) g(b)`` -- summed exactly in int32, and scaled
back.  Under a SWAPPER triple (op_is_a, bit, value) a product whose
decision operand has ``bit`` equal to ``value`` takes its operands the
other way round; ``value`` 2 never matches (no swap).  So, with ``s`` the
decision mask, a decision on A gives ``(s g(A)) @ f(B) + ((1-s) f(A)) @
g(B)`` and a decision on B ``g(A) @ (s f(B)) + f(A) @ ((1-s) g(B))``.
Each row carries the triple it was served under.

The reference keeps every tensor in float32 and multiplies at the highest
matmul precision.  The control (``store="fp8"``) is the same computation
with every tensor the model stores (norm outputs, projection outputs,
rotated q and k, attention probabilities and output, residual sums, MLP
activations), and the weights of the floating-point products, rounded to
float8 e4m3 with one scale per row (per column for weights): the step
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 256             # query rows per attention block
VOCAB_CHUNKS = 8          # the LM head runs over this many vocabulary slices


def model_spec(config: dict) -> tuple:
    """The hashable shape description the jitted pieces take, from a
    configuration file."""
    act = config["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"reference: no activation {act!r}")
    return (("heads", config["num_attention_heads"]),
            ("kv_heads", config["num_key_value_heads"]),
            ("head_dim", config["head_dim"]),
            ("theta", float(config["rope_theta"])),
            ("eps", float(config["rms_norm_eps"])),
            ("attn_bias", bool(config["attention_bias"])),
            ("mlp_bias", bool(config["mlp_bias"])),
            ("gated", act == "silu"))


def multiplier_bits(name: str) -> tuple:
    """``mul8s_trunc{ka}_{kb}`` -> (ka, kb); other circuits are refused."""
    head, _, tail = name.partition("_trunc")
    if head != "mul8s" or not tail:
        raise ValueError(f"reference: multiplier {name!r} is not mul8s_trunc")
    ka, kb = (int(v) for v in tail.split("_"))
    return ka, kb


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _st(x, store):
    """A tensor as the reference keeps it: float32 as is; the control
    rounds it to fp8 (e4m3, one scale per row)."""
    return _fp8(x, -1) if store == "fp8" else x


def _dot(x, w, store):
    """x (S, K) @ w (K, N) in float32; the fp8 control also stores ``w``
    in fp8, one scale per column."""
    w = w.astype(jnp.float32)
    if store == "fp8":
        w = _fp8(w, 0)
    return _st(_mm(x.astype(jnp.float32), w), store)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale.astype(jnp.float32))


def _rope(x, theta):
    """x (S, heads, hd): rotate the two halves of each head by position."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(0, hd, 2) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _leaf(params, path, l):
    node = params["layers"]["p0"]
    for k in path:
        node = node[k]
    return jax.lax.dynamic_index_in_dim(node, l, keepdims=False)


def _signmag(bits):
    """Clear the low ``bits`` bits of each int8's magnitude."""
    mask = jnp.int8(~((1 << bits) - 1))

    def f(x):
        mag = jnp.abs(x) & mask
        return jnp.where(x < 0, -mag, mag)

    return f


def _quant(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _imm(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.int32)


# ---------------------------------------------------------------------------
# jitted pieces: one compile per padded length, shared by every request
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "store"))
def _embed(params, tokens, *, m, store):
    del m, store
    return jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "store"))
def _attention(params, l, x, *, m, store):
    """RMSNorm, q/k/v (+bias), RoPE, causal GQA attention -> (S, H*hd)."""
    m = dict(m)
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    S = x.shape[0]
    h = _st(_rmsnorm(x, _leaf(params, ("ln1", "scale"), l), m["eps"]), store)

    def proj(name, n):
        y = _dot(h, _leaf(params, ("attn", name, "w"), l), store)
        if m["attn_bias"]:
            y = _st(y + _leaf(params, ("attn", name, "b"), l)
                    .astype(jnp.float32), store)
        return y.reshape(S, n, hd)

    q = _st(_rope(proj("q", H), m["theta"]), store)
    k = jnp.repeat(_st(_rope(proj("k", KV), m["theta"]), store), H // KV, axis=1)
    v = jnp.repeat(proj("v", KV), H // KV, axis=1)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_CHUNK, Q_CHUNK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / math.sqrt(hd)
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = _st(jax.nn.softmax(s, axis=-1), store)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // Q_CHUNK)).reshape(S, H * hd)
    return _st(out, store)


@functools.partial(jax.jit, static_argnames=("path", "store"))
def _exact(params, l, x, *, path, store):
    """A projection the configuration leaves exact (no bias)."""
    return _dot(x, _leaf(params, path, l), store)


@functools.partial(jax.jit, static_argnames=("path", "ka", "kb", "on_a"))
def _approx(params, l, x, bit, value, rows, *, path, ka, kb, on_a):
    """The approximate projection of the rows selected by ``rows`` under one
    SWAPPER triple (``on_a``, ``bit``, ``value``); other rows are 0."""
    A, sa = _quant(x.astype(jnp.float32), -1)
    B, sb = _quant(_leaf(params, path, l).astype(jnp.float32), 0)
    f, g = _signmag(ka), _signmag(kb)
    if on_a:
        s = (((A >> bit.astype(jnp.int8)) & 1) == value).astype(jnp.int8)
        acc = _imm(s * g(A), f(B)) + _imm((1 - s) * f(A), g(B))
    else:
        s = (((B >> bit.astype(jnp.int8)) & 1) == value).astype(jnp.int8)
        acc = _imm(g(A), s * f(B)) + _imm(f(A), (1 - s) * g(B))
    y = acc.astype(jnp.float32) * sa * sb
    return jnp.where(rows[:, None], y, 0.0)


@functools.partial(jax.jit, static_argnames=("m", "store"))
def _norm2(params, l, x, *, m, store):
    return _st(_rmsnorm(x, _leaf(params, ("ln2", "scale"), l),
                        dict(m)["eps"]), store)


@functools.partial(jax.jit, static_argnames=("gated", "store"))
def _act(up, gate, *, gated, store):
    if gated:
        return _st(_st(jax.nn.silu(gate), store) * up, store)
    return _st(_gelu_tanh(up), store)


@functools.partial(jax.jit, static_argnames=("store",))
def _add(x, y, *, store):
    return _st(x + y, store)


@functools.partial(jax.jit, static_argnames=("low",))
def _head_slice(params, x, x_low, nxt, lo, *, low):
    """One vocabulary slice of the LM head: per row the slice's largest
    reference logit, the reference logit of ``nxt`` where it falls in the
    slice, and, with ``x_low``, the control's best logit here and the
    reference logit at that token."""
    n = params["lm_head"]["w"].shape[0] // VOCAB_CHUNKS
    w = jax.lax.dynamic_slice_in_dim(params["lm_head"]["w"], lo, n, 0)
    ref = _mm(x, w.astype(jnp.float32).T)
    ids = lo + jnp.arange(n)
    at_nxt = jnp.max(jnp.where(ids[None, :] == nxt[:, None], ref, -jnp.inf), -1)
    out = dict(best=jnp.max(ref, -1), at_nxt=at_nxt)
    if low:
        ctl = _mm(x_low, _fp8(w.astype(jnp.float32), -1).T)
        j = jnp.argmax(ctl, -1)
        out.update(ctl_best=jnp.max(ctl, -1), ctl_ref=jnp.take_along_axis(
            ref, j[:, None], -1)[:, 0])
    return out


@jax.jit
def _head_count(params, x, thresholds, lo):
    """Per row and threshold, how many reference logits of one vocabulary
    slice lie above the threshold (the same products as ``_head_slice``)."""
    n = params["lm_head"]["w"].shape[0] // VOCAB_CHUNKS
    w = jax.lax.dynamic_slice_in_dim(params["lm_head"]["w"], lo, n, 0)
    ref = _mm(x, w.astype(jnp.float32).T)
    return jnp.sum(ref[:, None, :] > thresholds[:, :, None], -1)


# ---------------------------------------------------------------------------
# the forward pass over one request
# ---------------------------------------------------------------------------

class Reference:
    """The reference model over the benchmark's weights ``params``."""

    def __init__(self, params, config: dict):
        self.params = params
        self.m = model_spec(config)
        self.layers = int(config["num_hidden_layers"])
        self.ka, self.kb = multiplier_bits(config["approx"]["multiplier"])
        self.targets = tuple(config["approx"]["targets"])
        self.eps = float(config["rms_norm_eps"])

    def _proj(self, target, path, l, x, triples, store):
        if target not in self.targets:
            return _exact(self.params, l, x, path=path, store=store)
        y = 0.0
        for t in np.unique(triples, axis=0):
            rows = jnp.asarray((triples == t).all(-1))
            y = y + _approx(self.params, l, x, jnp.int32(t[1]), jnp.int32(t[2]),
                            rows, path=path, ka=self.ka, kb=self.kb,
                            on_a=bool(t[0] == 1))
        return _add(y, 0.0, store=store)

    def _bias(self, path, l, y, store):
        node = self.params["layers"]["p0"]
        for k in path:
            node = node[k]
        return _add(y, node[l].astype(jnp.float32), store=store)

    def hidden(self, tokens: np.ndarray, triples: Dict[str, np.ndarray],
               store: str = "f32"):
        """Final-normed hidden states (S, d) of ``tokens`` (a multiple of
        ``Q_CHUNK`` rows); ``triples[target]`` is (S, 3), the triple each
        row was served under; ``store="fp8"`` is the control."""
        m, p = self.m, self.params
        md = dict(m)
        x = _embed(p, jnp.asarray(tokens), m=m, store=store)
        for l in range(self.layers):
            li = jnp.int32(l)
            a = _attention(p, li, x, m=m, store=store)
            x = _add(x, self._proj("attn_out", ("attn", "o", "w"), li, a,
                                   triples.get("attn_out"), store), store=store)
            h = _norm2(p, li, x, m=m, store=store)
            up = self._proj("mlp", ("mlp", "in", "w"), li, h,
                            triples.get("mlp"), store)
            if md["mlp_bias"]:
                up = self._bias(("mlp", "in", "b"), l, up, store)
            gate = (self._proj("mlp", ("mlp", "gate", "w"), li, h,
                               triples.get("mlp"), store)
                    if md["gated"] else up)
            y = self._proj("mlp", ("mlp", "out", "w"), li,
                           _act(up, gate, gated=md["gated"], store=store),
                           triples.get("mlp"), store)
            if md["mlp_bias"]:
                y = self._bias(("mlp", "out", "b"), l, y, store)
            x = _add(x, y, store=store)
        return _final_norm(p, x, eps=self.eps, store=store)

    def gaps(self, tokens, nxt, triples, control: bool = False) -> dict:
        """Per row: ``gap`` = best reference logit - reference logit of
        ``nxt`` (the token served after that row), and ``rank`` = 1 + the
        number of tokens the reference puts above it; with ``control``,
        ``control_gap`` and ``control_rank`` of the token the fp8 control
        puts first."""
        x = self.hidden(tokens, triples)
        x_low = self.hidden(tokens, triples, store="fp8") if control else x
        nxt = jnp.asarray(nxt, jnp.int32)
        n = self.params["lm_head"]["w"].shape[0] // VOCAB_CHUNKS
        los = [jnp.int32(i * n) for i in range(VOCAB_CHUNKS)]
        parts = jax.device_get([_head_slice(self.params, x, x_low, nxt, lo,
                                            low=control) for lo in los])
        best = np.max([q["best"] for q in parts], 0)
        served = np.max([q["at_nxt"] for q in parts], 0)
        thr = [served]
        if control:
            pick = np.argmax([q["ctl_best"] for q in parts], 0)
            ctl_ref = np.asarray([q["ctl_ref"] for q in parts])
            thr.append(ctl_ref[pick, np.arange(len(pick))])
        thr = np.stack(thr, 1)
        above = sum(np.asarray(_head_count(self.params, x, jnp.asarray(thr), lo))
                    for lo in los)
        out = dict(gap=best - thr[:, 0], rank=above[:, 0] + 1)
        if control:
            out.update(control_gap=best - thr[:, 1], control_rank=above[:, 1] + 1)
        return out


@functools.partial(jax.jit, static_argnames=("eps", "store"))
def _final_norm(params, x, *, eps, store):
    return _st(_rmsnorm(x, params["ln_f"]["scale"], eps), store)
