"""Plain reference ``starcoder2``: StarCoder2's published decoder block
(arXiv:2402.19173; the ``Starcoder2`` modeling code of ``transformers``)
in float32 ``jax.numpy``, written from the configuration file (shared
parts in ``harness/reference.py``).  It imports nothing of the program.
It reads the weights the benchmark made (``harness/weights.py``) by their
place in the parameter tree, and computes everything else itself.

The block: token embedding; per layer LayerNorm with bias (gain ``1 +
scale``, bias ``b``, ``norm_epsilon``), q/k/v projections with biases
(``use_bias``), rotary position embedding on the two halves of each head,
causal grouped-query attention clipped to the last ``sliding_window``
positions, output projection with bias, residual; LayerNorm, tanh-GELU MLP
over two matrices with biases, residual; final LayerNorm and the LM head.

Attention is the full causal mask clipped to the window, computed from
positions, with no ring and no cache: each block of ``ROWS`` queries
scores the keys from a whole number of blocks at least a window before
its first row up to its last row; every key left out lies outside the
window of every query of the block.

The control (``store="fp8"``) rounds every tensor the block stores: norm
outputs, projection outputs, rotated q and k, attention probabilities and
output, residual sums, MLP activations.

The work count (``params``, ``per_token``, ``prefill``): a multiply-add
counts 2.  Counted per real token at its position: the projections,
attention over the ``min(position + 1, sliding_window)`` keys it sees (QK^T
and PV), and the LM head once per generated token.  Not counted: the 2K
inner dimension of the SWAPPER factorization, bucket padding, norms, biases
and elementwise work, and the LM head rows that prefill computes but no
sample reads.  ``window_bytes`` counts the K and V bytes decode attention
must read at a position.  So each count is the same whatever implements
it.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.reference import (HIGHEST, add, approx, dot, exact,
                               multiplier_bits, st)

NAME = "starcoder2"
ROWS = 256                # query rows per attention block; replays pad to it

_ACT = {"gelu": "gelu_pytorch_tanh"}
_NORM = {"layer": "layer_norm", "rms": "rms_norm"}

# each configuration-file key this module reads, under its published name
# -> the program's ModelConfig field, or a function of the ModelConfig
# giving the value in the file's terms where the two differ
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim_",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "norm_type": lambda c: _NORM.get(c.norm, c.norm),
    "norm_epsilon": "norm_eps",
    # a bias on every linear: q/k/v, o, and the MLP, which the program
    # gives its biases where q/k/v have theirs and the MLP is GELU
    "use_bias": lambda c: c.qkv_bias and c.o_bias and c.act == "gelu",
    "sliding_window": "local_window",
    "hidden_act": lambda c: _ACT.get(c.act, c.act),
    "tie_word_embeddings": "tie_embeddings",
}

# the program's fields for what no file key states: one kind of layer,
# every one with the 4,096-token window; no experts, recurrence, encoder or
# multimodal positions; an unpadded vocabulary
PROGRAM_FIXED = {
    "family": "dense", "pattern": ("local",), "local_window": 4096,
    "first_dense": 0, "n_experts": 0, "d_rnn": 0, "n_enc_layers": 0,
    "mrope": False, "pad_vocab_multiple": 1,
}


def model_spec(config: dict) -> tuple:
    """The hashable shape description the jitted pieces take, from a
    configuration file."""
    if config["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError(f"reference: no activation {config['hidden_act']!r}")
    if config["norm_type"] != "layer_norm":
        raise ValueError(f"reference: no norm {config['norm_type']!r}")
    return (("heads", config["num_attention_heads"]),
            ("kv_heads", config["num_key_value_heads"]),
            ("head_dim", config["head_dim"]),
            ("theta", float(config["rope_theta"])),
            ("eps", float(config["norm_epsilon"])),
            ("window", int(config["sliding_window"])),
            ("bias", bool(config["use_bias"])))


def _layernorm(x, scale, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * (1.0 + scale.astype(jnp.float32)) + b.astype(jnp.float32))


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


def _stacked(params, path):
    """A leaf of the program's stacked layers, all layers."""
    node = params["layers"]["p0"]
    for k in path:
        node = node[k]
    return node


def _leaf(params, path, l):
    return jax.lax.dynamic_index_in_dim(_stacked(params, path), l,
                                        keepdims=False)


def _norm(params, name, l, x, eps):
    return _layernorm(x, _leaf(params, (name, "scale"), l),
                      _leaf(params, (name, "b"), l), eps)


# ---------------------------------------------------------------------------
# jitted pieces: one compile per padded length, shared by every request
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "store"))
def _embed(params, tokens, *, m, store):
    del m, store
    return jnp.take(params["embed"]["w"], tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "store"))
def _attention(params, l, x, *, m, store):
    """LayerNorm, q/k/v (+bias), RoPE, windowed causal GQA attention ->
    (S, H*hd)."""
    m = dict(m)
    H, KV, hd, W = m["heads"], m["kv_heads"], m["head_dim"], m["window"]
    S = x.shape[0]
    h = st(_norm(params, "ln1", l, x, m["eps"]), store)

    def proj(name, n):
        y = dot(h, _leaf(params, ("attn", name, "w"), l), store)
        if m["bias"]:
            y = st(y + _leaf(params, ("attn", name, "b"), l)
                   .astype(jnp.float32), store)
        return y.reshape(S, n, hd)

    q = st(_rope(proj("q", H), m["theta"]), store)
    k = jnp.repeat(st(_rope(proj("k", KV), m["theta"]), store), H // KV, axis=1)
    v = jnp.repeat(proj("v", KV), H // KV, axis=1)
    lead = -(-W // ROWS) * ROWS          # keys scored before a block's first row
    kp = jnp.pad(k, ((lead, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((lead, 0), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * ROWS, ROWS, 0)
        kb = jax.lax.dynamic_slice_in_dim(kp, i * ROWS, lead + ROWS, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, i * ROWS, lead + ROWS, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HIGHEST) / math.sqrt(hd)
        qpos = i * ROWS + jnp.arange(ROWS)
        kpos = i * ROWS - lead + jnp.arange(lead + ROWS)
        d = qpos[:, None] - kpos[None, :]
        seen = (kpos[None, :] >= 0) & (d >= 0) & (d < W)
        s = jnp.where(seen[None], s, -jnp.inf)
        p = st(jax.nn.softmax(s, axis=-1), store)
        return jnp.einsum("hqk,khd->qhd", p, vb, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // ROWS)).reshape(S, H * hd)
    return st(out, store)


@functools.partial(jax.jit, static_argnames=("m", "store"))
def _norm2(params, l, x, *, m, store):
    return st(_norm(params, "ln2", l, x, dict(m)["eps"]), store)


@functools.partial(jax.jit, static_argnames=("store",))
def _act(up, *, store):
    return st(_gelu_tanh(up), store)


@functools.partial(jax.jit, static_argnames=("eps", "store"))
def _final_norm(params, x, *, eps, store):
    return st(_layernorm(x, params["ln_f"]["scale"], params["ln_f"]["b"],
                         eps), store)


# ---------------------------------------------------------------------------
# the forward pass over one request
# ---------------------------------------------------------------------------

class Reference:
    """The reference model over the benchmark's weights ``params``."""

    def __init__(self, params, config: dict):
        self.params = params
        self.m = model_spec(config)
        self.layers = int(config["num_hidden_layers"])
        self.bits = multiplier_bits(config["approx"]["multiplier"])
        self.targets = tuple(config["approx"]["targets"])
        self.eps = float(config["norm_epsilon"])
        self.tied = bool(config["tie_word_embeddings"])

    @property
    def head(self):
        """The LM head (vocab, d) the logits come from."""
        return self.params["embed" if self.tied else "lm_head"]["w"]

    def _proj(self, target, path, l, x, triples, store):
        w = _stacked(self.params, path + ("w",))
        if target not in self.targets:
            y = exact(w, l, x, store=store)
        else:
            y = approx(w, l, x, triples, self.bits, store)
        if not dict(self.m)["bias"]:
            return y
        return add(y, _stacked(self.params, path + ("b",))[l]
                   .astype(jnp.float32), store=store)

    def hidden(self, tokens: np.ndarray, triples: Dict[str, np.ndarray],
               store: str = "f32"):
        """Final-normed hidden states (S, d) of ``tokens`` (a multiple of
        ``ROWS`` rows); ``triples[target]`` is (S, 3), the triple each row
        was served under; ``store="fp8"`` is the control."""
        m, p = self.m, self.params
        x = _embed(p, jnp.asarray(tokens), m=m, store=store)
        for l in range(self.layers):
            li = jnp.int32(l)
            a = _attention(p, li, x, m=m, store=store)
            x = add(x, self._proj("attn_out", ("attn", "o"), li, a,
                                  triples.get("attn_out"), store), store=store)
            h = _norm2(p, li, x, m=m, store=store)
            up = self._proj("mlp", ("mlp", "in"), li, h, triples.get("mlp"),
                            store)
            y = self._proj("mlp", ("mlp", "out"), li, _act(up, store=store),
                           triples.get("mlp"), store)
            x = add(x, y, store=store)
        return _final_norm(p, x, eps=self.eps, store=store)


# ---------------------------------------------------------------------------
# the work count
# ---------------------------------------------------------------------------

def _sizes(c: dict) -> dict:
    return dict(d=c["hidden_size"], ff=c["intermediate_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                hd=c["head_dim"], V=c["vocab_size"], L=c["num_hidden_layers"],
                W=c["sliding_window"], bias=c["use_bias"],
                tied=c["tie_word_embeddings"],
                targets=tuple(c["approx"]["targets"]))


def params(c: dict) -> int:
    """Parameters of the model as the program lays it out (each LayerNorm
    holds a gain and a bias per width)."""
    s = _sizes(c)
    d, ff, H, KV, hd = s["d"], s["ff"], s["H"], s["KV"], s["hd"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    mlp = 2 * d * ff
    if s["bias"]:
        attn += H * hd + 2 * KV * hd + d
        mlp += ff + d
    layer = attn + mlp + 2 * 2 * d
    head = 0 if s["tied"] else s["V"] * d
    return s["L"] * layer + s["V"] * d + head + 2 * d


def per_token(c: dict, position: int, sampled: bool) -> dict:
    """Operations for one token at ``position`` (0-based; it attends to
    ``min(position + 1, window)`` keys): ``int8`` on the approximated
    projections, ``flops`` elsewhere; ``sampled`` adds the LM head."""
    s = _sizes(c)
    d, ff, H, KV, hd = s["d"], s["ff"], s["H"], s["KV"], s["hd"]
    proj = dict(attn_qkv=d * (H + 2 * KV) * hd, attn_out=H * hd * d,
                mlp=2 * d * ff)
    int8 = sum(2 * v for k, v in proj.items() if k in s["targets"])
    flops = sum(2 * v for k, v in proj.items() if k not in s["targets"])
    flops += 4 * min(position + 1, s["W"]) * H * hd        # QK^T and PV
    out = dict(int8=s["L"] * int8, flops=s["L"] * flops)
    if sampled:
        out["flops"] += 2 * d * s["V"]
    return out


def prefill(c: dict, prompt_len: int) -> dict:
    """A prompt of ``prompt_len`` real tokens, sampling its last."""
    s = _sizes(c)
    d, H, hd, W, P = s["d"], s["H"], s["hd"], s["W"], prompt_len
    one = per_token(c, 0, False)
    near = min(P, W)                     # keys seen: 1, 2, ..., W, W, ...
    keys = near * (near + 1) // 2 + (P - near) * W
    base = one["flops"] - 4 * H * hd * s["L"]
    return dict(int8=one["int8"] * P,
                flops=base * P + 4 * H * hd * s["L"] * keys + 2 * d * s["V"])


def window_bytes(c: dict, position: int) -> int:
    """The K and V bytes (bfloat16) that decode attention must read at
    ``position``: ``min(position, window)`` cached positions per layer."""
    s = _sizes(c)
    return (min(position, s["W"]) * s["L"] * 2 * s["KV"] * s["hd"] * 2)
