"""Plain reference ``dense_gqa``: a dense decoder with grouped-query
attention, in float32 ``jax.numpy``, written from the configuration file
(shared parts in ``harness/reference.py``).  It imports nothing of the
program.  It reads the weights the benchmark made (``harness/weights.py``)
by their place in the parameter tree, and computes everything else itself.

The block: token embedding; per layer RMSNorm (gain ``1 + scale``), q/k/v
projections (+ bias with ``attention_bias``), rotary position embedding on
the two halves of each head, causal grouped-query attention over every
earlier position, output projection (no bias), residual; RMSNorm, MLP
(SwiGLU for ``silu``, or tanh-GELU over two matrices, with biases where
``mlp_bias``), residual; final RMSNorm and an untied LM head.

The control (``store="fp8"``) rounds every tensor the block stores: norm
outputs, projection outputs, rotated q and k, attention probabilities and
output, residual sums, MLP activations.

The work count (``params``, ``per_token``, ``prefill``): a multiply-add
counts 2.  Counted per real token at its position: the projections,
attention over the real context (QK^T and PV), and the LM head once per
generated token.  Not counted: the 2K inner dimension of the SWAPPER
factorization, bucket padding, norms and elementwise work, and the LM head
rows that prefill computes but no sample reads.  So the count is the same
whatever implements it.
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

NAME = "dense_gqa"
ROWS = 256                # query rows per attention block; replays pad to it

_ACT = {"silu": "silu", "gelu": "gelu_pytorch_tanh"}

# each configuration-file key this module reads -> the program's
# ModelConfig field, or a function of the ModelConfig giving the value in
# the file's terms where the two differ
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim_",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "attention_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "hidden_act": lambda c: _ACT.get(c.act, c.act),
    # the program gives the MLP its biases where it gives q/k/v theirs
    # and the MLP is GELU (models/transformer.block_init)
    "mlp_bias": lambda c: c.qkv_bias and c.act == "gelu",
}

# the program's fields for what this block leaves out, which no file key
# states: one kind of layer with full attention, no experts, recurrence,
# encoder or multimodal positions, an unpadded vocabulary
PROGRAM_FIXED = {
    "family": "dense", "pattern": (), "local_window": 0, "first_dense": 0,
    "n_experts": 0, "d_rnn": 0, "n_enc_layers": 0, "mrope": False,
    "pad_vocab_multiple": 1,
}


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


def _stacked(params, path):
    """A leaf of the program's stacked layers, all layers."""
    node = params["layers"]["p0"]
    for k in path:
        node = node[k]
    return node


def _leaf(params, path, l):
    return jax.lax.dynamic_index_in_dim(_stacked(params, path), l,
                                        keepdims=False)


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
    h = st(_rmsnorm(x, _leaf(params, ("ln1", "scale"), l), m["eps"]), store)

    def proj(name, n):
        y = dot(h, _leaf(params, ("attn", name, "w"), l), store)
        if m["attn_bias"]:
            y = st(y + _leaf(params, ("attn", name, "b"), l)
                   .astype(jnp.float32), store)
        return y.reshape(S, n, hd)

    q = st(_rope(proj("q", H), m["theta"]), store)
    k = jnp.repeat(st(_rope(proj("k", KV), m["theta"]), store), H // KV, axis=1)
    v = jnp.repeat(proj("v", KV), H // KV, axis=1)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * ROWS, ROWS, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / math.sqrt(hd)
        qpos = i * ROWS + jnp.arange(ROWS)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = st(jax.nn.softmax(s, axis=-1), store)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // ROWS)).reshape(S, H * hd)
    return st(out, store)


@functools.partial(jax.jit, static_argnames=("m", "store"))
def _norm2(params, l, x, *, m, store):
    return st(_rmsnorm(x, _leaf(params, ("ln2", "scale"), l),
                       dict(m)["eps"]), store)


@functools.partial(jax.jit, static_argnames=("gated", "store"))
def _act(up, gate, *, gated, store):
    if gated:
        return st(st(jax.nn.silu(gate), store) * up, store)
    return st(_gelu_tanh(up), store)


@functools.partial(jax.jit, static_argnames=("eps", "store"))
def _final_norm(params, x, *, eps, store):
    return st(_rmsnorm(x, params["ln_f"]["scale"], eps), store)


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
        self.eps = float(config["rms_norm_eps"])

    @property
    def head(self):
        """The LM head (vocab, d) the logits come from."""
        return self.params["lm_head"]["w"]

    def _proj(self, target, path, l, x, triples, store):
        w = _stacked(self.params, path)
        if target not in self.targets:
            return exact(w, l, x, store=store)
        return approx(w, l, x, triples, self.bits, store)

    def _bias(self, path, l, y, store):
        return add(y, _stacked(self.params, path)[l].astype(jnp.float32),
                   store=store)

    def hidden(self, tokens: np.ndarray, triples: Dict[str, np.ndarray],
               store: str = "f32"):
        """Final-normed hidden states (S, d) of ``tokens`` (a multiple of
        ``ROWS`` rows); ``triples[target]`` is (S, 3), the triple each row
        was served under; ``store="fp8"`` is the control."""
        m, p = self.m, self.params
        md = dict(m)
        x = _embed(p, jnp.asarray(tokens), m=m, store=store)
        for l in range(self.layers):
            li = jnp.int32(l)
            a = _attention(p, li, x, m=m, store=store)
            x = add(x, self._proj("attn_out", ("attn", "o", "w"), li, a,
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
            x = add(x, y, store=store)
        return _final_norm(p, x, eps=self.eps, store=store)


# ---------------------------------------------------------------------------
# the work count
# ---------------------------------------------------------------------------

def _sizes(c: dict) -> dict:
    return dict(d=c["hidden_size"], ff=c["intermediate_size"],
                H=c["num_attention_heads"], KV=c["num_key_value_heads"],
                hd=c["head_dim"], V=c["vocab_size"], L=c["num_hidden_layers"],
                gated=c["hidden_act"] == "silu", qkv_bias=c["attention_bias"],
                mlp_bias=c["mlp_bias"], tied=c["tie_word_embeddings"],
                targets=tuple(c["approx"]["targets"]))


def params(c: dict) -> int:
    """Parameters of the model as the program lays it out (norm gains
    included, one per norm and width)."""
    s = _sizes(c)
    d, ff, H, KV, hd = s["d"], s["ff"], s["H"], s["KV"], s["hd"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    if s["qkv_bias"]:
        attn += H * hd + 2 * KV * hd
    mlp = (3 if s["gated"] else 2) * d * ff
    if s["mlp_bias"]:
        mlp += ff + d
    layer = attn + mlp + 2 * d
    head = 0 if s["tied"] else s["V"] * d
    return s["L"] * layer + s["V"] * d + head + d


def per_token(c: dict, position: int, sampled: bool) -> dict:
    """Operations for one token at ``position`` (0-based; it attends to
    ``position + 1`` keys): ``int8`` on the approximated projections,
    ``flops`` elsewhere; ``sampled`` adds the LM head."""
    s = _sizes(c)
    d, ff, H, KV, hd = s["d"], s["ff"], s["H"], s["KV"], s["hd"]
    proj = dict(attn_qkv=d * (H + 2 * KV) * hd, attn_out=H * hd * d,
                mlp=(3 if s["gated"] else 2) * d * ff)
    int8 = sum(2 * v for k, v in proj.items() if k in s["targets"])
    flops = sum(2 * v for k, v in proj.items() if k not in s["targets"])
    flops += 4 * (position + 1) * H * hd                 # QK^T and PV
    out = dict(int8=s["L"] * int8, flops=s["L"] * flops)
    if sampled:
        out["flops"] += 2 * d * s["V"]
    return out


def prefill(c: dict, prompt_len: int) -> dict:
    """A prompt of ``prompt_len`` real tokens, sampling its last."""
    s = _sizes(c)
    d, H, hd = s["d"], s["H"], s["hd"]
    one = per_token(c, 0, False)
    attn = 4 * H * hd * s["L"] * prompt_len * (prompt_len + 1) // 2
    base = one["flops"] - 4 * H * hd * s["L"]
    return dict(int8=one["int8"] * prompt_len,
                flops=base * prompt_len + attn + 2 * d * s["V"])
