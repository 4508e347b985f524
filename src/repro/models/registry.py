"""Model registry: init / loss / prefill / decode entry points per family,
plus ``input_specs`` (ShapeDtypeStruct stand-ins for the dry-run — weak-type
correct, shardable, zero allocation)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ModelConfig, ParallelConfig, ShapeConfig

from . import transformer, whisper

__all__ = ["init_params", "train_loss", "prefill", "decode_step", "input_specs", "init_cache"]


def _mod(cfg: ModelConfig):
    return whisper if cfg.family == "encdec" else transformer


def init_params(key, cfg: ModelConfig, dtype=None):
    return _mod(cfg).init_params(key, cfg, dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0):
    if cfg.family == "encdec":
        return whisper.init_cache(cfg, batch, max_len, enc_len or max_len)
    return transformer.init_cache(cfg, batch, max_len)


def train_loss(params, batch, cfg: ModelConfig, par: Optional[ParallelConfig] = None):
    """Next-token (or seq2seq) CE + MoE aux; returns (loss, metrics)."""
    logits, _, aux = _mod(cfg).forward(params, batch, cfg, par, mode="train")
    labels = batch["labels"]
    if cfg.padded_vocab != cfg.vocab:
        # mask the padded tail out of the softmax (ids never reference it)
        pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab).astype(jnp.float32)
        logits = logits.astype(jnp.float32) - 1e9 * pad_mask
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"ce": loss, "aux": aux}


def prefill(params, batch, cfg: ModelConfig, par=None, *, max_cache_len: int,
            prompt_lens=None):
    """``prompt_lens`` — optional (B,) int32 of real prompt lengths: the
    pad-mask prefill path (right-padded prompts attend only to real tokens;
    attention-only stacks, full or sliding-window — see
    ``transformer.forward``)."""
    if prompt_lens is not None:
        assert cfg.family != "encdec", "pad-mask prefill: encdec unsupported"
    logits, cache, _ = _mod(cfg).forward(
        params, batch, cfg, par, mode="prefill", max_cache_len=max_cache_len,
        **({} if prompt_lens is None else {"prompt_lens": prompt_lens})
    )
    return logits, cache


def decode_step(params, cache, tokens, cache_index, cfg: ModelConfig, par=None,
                write_mask=None):
    """One serving step: tokens (B, 1) at position ``cache_index`` — a
    scalar (whole batch) or an int32 (B,) vector of per-slot positions.
    ``write_mask`` (B,) bool gates per-slot cache writes (vector path;
    transformer families only — encdec decode has no per-slot plumbing)."""
    if write_mask is not None:
        assert cfg.family != "encdec", "per-slot decode: encdec unsupported"
    batch = {"tokens": tokens}
    logits, new_cache, _ = _mod(cfg).forward(
        params, batch, cfg, par, mode="decode", cache=cache,
        cache_index=cache_index,
        **({} if write_mask is None else {"write_mask": write_mask})
    )
    return logits, new_cache


# ---------------------------------------------------------------------------
# dry-run input specs (ShapeDtypeStruct only)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """Abstract inputs for (arch x shape).  No device allocation."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.dtype(cfg.compute_dtype)

    def tok(shp):
        return jax.ShapeDtypeStruct(shp, i32)

    if shape.kind == "train":
        if cfg.family == "encdec":
            # audio: precomputed frame embeddings (stub frontend) + text
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16),
                "tokens": tok((B, min(S, 448))),
                "labels": tok((B, min(S, 448))),
            }
        if cfg.family == "vlm":
            return {
                "embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16),
                "pos": tok((B, S, 3)),
                "labels": tok((B, S)),
            }
        return {"tokens": tok((B, S)), "labels": tok((B, S))}

    if shape.kind == "prefill":
        if cfg.family == "encdec":
            return {
                "frames": jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16),
                "tokens": tok((B, min(S, 448))),
            }
        if cfg.family == "vlm":
            return {
                "embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), bf16),
                "pos": tok((B, S, 3)),
            }
        return {"tokens": tok((B, S))}

    # decode: one token against a cache of size S
    specs = {"tokens": tok((B, 1))}
    cache = jax.eval_shape(
        lambda: init_cache(cfg, B, S, enc_len=min(S, 1500) if cfg.family == "encdec" else 0)
    )
    return {"tokens": specs["tokens"], "cache": cache}
