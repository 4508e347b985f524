"""What every plain reference shares.  Each model's forward pass and work
count is a module of its own, ``bench/references/<name>.py``, that a
configuration file names (``"reference"``); this module holds the parts
no model changes: the approximate multiplier and the SWAPPER triple, the
fp8 control's rounding, and the sliced LM head that gives each served
token's gap and rank.  It imports nothing of the program.

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

A reference keeps every tensor in float32 and multiplies at the highest
matmul precision.  The control (``store="fp8"``) is the same computation
with every tensor the model stores, and the weights of the floating-point
products, rounded to float8 e4m3 with one scale per row (per column for
weights): the step below the configurations' bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
VOCAB_CHUNKS = 8          # the LM head runs over this many vocabulary slices


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


def st(x, store):
    """A tensor as the reference keeps it: float32 as is; the control
    rounds it to fp8 (e4m3, one scale per row)."""
    return _fp8(x, -1) if store == "fp8" else x


def dot(x, w, store):
    """x (S, K) @ w (K, N) in float32; the fp8 control also stores ``w``
    in fp8, one scale per column."""
    w = w.astype(jnp.float32)
    if store == "fp8":
        w = _fp8(w, 0)
    return st(_mm(x.astype(jnp.float32), w), store)


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
# jitted pieces: one compile per padded length, shared by every request.
# ``w`` is a weight stacked over the layers, ``l`` the layer.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("store",))
def exact(w, l, x, *, store):
    """A projection the configuration leaves exact (no bias)."""
    return dot(x, jax.lax.dynamic_index_in_dim(w, l, keepdims=False), store)


@functools.partial(jax.jit, static_argnames=("ka", "kb", "on_a"))
def _approx(w, l, x, bit, value, rows, *, ka, kb, on_a):
    """The approximate projection of the rows selected by ``rows`` under one
    SWAPPER triple (``on_a``, ``bit``, ``value``); other rows are 0."""
    A, sa = _quant(x.astype(jnp.float32), -1)
    B, sb = _quant(jax.lax.dynamic_index_in_dim(w, l, keepdims=False)
                   .astype(jnp.float32), 0)
    f, g = _signmag(ka), _signmag(kb)
    if on_a:
        s = (((A >> bit.astype(jnp.int8)) & 1) == value).astype(jnp.int8)
        acc = _imm(s * g(A), f(B)) + _imm((1 - s) * f(A), g(B))
    else:
        s = (((B >> bit.astype(jnp.int8)) & 1) == value).astype(jnp.int8)
        acc = _imm(g(A), s * f(B)) + _imm(f(A), (1 - s) * g(B))
    y = acc.astype(jnp.float32) * sa * sb
    return jnp.where(rows[:, None], y, 0.0)


def approx(w, l, x, triples: np.ndarray, bits: tuple, store):
    """An approximated projection by the multiplier ``bits`` = (ka, kb),
    each row under the triple ``triples`` (S, 3) gives it."""
    y = 0.0
    for t in np.unique(triples, axis=0):
        rows = jnp.asarray((triples == t).all(-1))
        y = y + _approx(w, l, x, jnp.int32(t[1]), jnp.int32(t[2]), rows,
                        ka=bits[0], kb=bits[1], on_a=bool(t[0] == 1))
    return add(y, 0.0, store=store)


@functools.partial(jax.jit, static_argnames=("store",))
def add(x, y, *, store):
    return st(x + y, store)


# ---------------------------------------------------------------------------
# the LM head, sliced over the vocabulary: gap and rank of each served token
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("low",))
def _head_slice(head, x, x_low, nxt, lo, *, low):
    """One vocabulary slice of the LM head: per row the slice's largest
    reference logit, the reference logit of ``nxt`` where it falls in the
    slice, and, with ``x_low``, the control's best logit here and the
    reference logit at that token."""
    n = head.shape[0] // VOCAB_CHUNKS
    w = jax.lax.dynamic_slice_in_dim(head, lo, n, 0)
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
def _head_count(head, x, thresholds, lo):
    """Per row and threshold, how many reference logits of one vocabulary
    slice lie above the threshold (the same products as ``_head_slice``)."""
    n = head.shape[0] // VOCAB_CHUNKS
    w = jax.lax.dynamic_slice_in_dim(head, lo, n, 0)
    ref = _mm(x, w.astype(jnp.float32).T)
    return jnp.sum(ref[:, None, :] > thresholds[:, :, None], -1)


def gaps(ref, tokens, nxt, triples, control: bool = False) -> dict:
    """Per row of a reference ``ref`` (a module's ``Reference``): ``gap`` =
    best reference logit - reference logit of ``nxt`` (the token served
    after that row), and ``rank`` = 1 + the number of tokens the reference
    puts above it; with ``control``, ``control_gap`` and ``control_rank``
    of the token the fp8 control puts first."""
    x = ref.hidden(tokens, triples)
    x_low = ref.hidden(tokens, triples, store="fp8") if control else x
    nxt = jnp.asarray(nxt, jnp.int32)
    head = ref.head
    n = head.shape[0] // VOCAB_CHUNKS
    los = [jnp.int32(i * n) for i in range(VOCAB_CHUNKS)]
    parts = jax.device_get([_head_slice(head, x, x_low, nxt, lo, low=control)
                            for lo in los])
    best = np.max([q["best"] for q in parts], 0)
    served = np.max([q["at_nxt"] for q in parts], 0)
    thr = [served]
    if control:
        pick = np.argmax([q["ctl_best"] for q in parts], 0)
        ctl_ref = np.asarray([q["ctl_ref"] for q in parts])
        thr.append(ctl_ref[pick, np.arange(len(pick))])
    thr = np.stack(thr, 1)
    above = sum(np.asarray(_head_count(head, x, jnp.asarray(thr), lo))
                for lo in los)
    out = dict(gap=best - thr[:, 0], rank=above[:, 0] + 1)
    if control:
        out.update(control_gap=best - thr[:, 1], control_rank=above[:, 1] + 1)
    return out
