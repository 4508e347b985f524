"""The work the algorithm needs, from a configuration file's sizes: the
parameter count, and per token the int8 operations of the approximated
projections and the floating-point operations of everything else.

A multiply-add counts 2.  Counted per real token at its position: the
projections, attention over the real context (QK^T and PV), and the LM
head once per generated token.  Not counted: the 2K inner dimension of
the SWAPPER factorization, bucket padding, norms and elementwise work, and
the LM head rows that prefill computes but no sample reads.  So the count
is the same whatever implements it."""
from __future__ import annotations


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
    d, ff, H, KV, hd = s["d"], s["ff"], s["H"], s["KV"], s["hd"]
    one = per_token(c, 0, False)
    attn = 4 * H * hd * s["L"] * prompt_len * (prompt_len + 1) // 2
    base = one["flops"] - 4 * H * hd * s["L"]
    return dict(int8=one["int8"] * prompt_len,
                flops=base * prompt_len + attn + 2 * d * s["V"])


def seconds_at_peak(ops: dict, peaks: dict) -> float:
    """The least time the chip could take: int8 ops at the int8 peak plus
    the rest at the bf16 peak."""
    return (ops["int8"] / peaks["int8_ops_per_s"]
            + ops["flops"] / peaks["bf16_flops_per_s"])
