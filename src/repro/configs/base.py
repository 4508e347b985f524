"""Model / shape / parallelism configuration dataclasses.

Every assigned architecture is a ``ModelConfig`` in this package; input-shape
cells are ``ShapeConfig``s; ``ParallelConfig`` captures the distribution
strategy knobs that the perf loop (EXPERIMENTS.md §Perf) iterates on.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ModelConfig", "ShapeConfig", "ParallelConfig", "AxPolicy", "SHAPES"]


@dataclasses.dataclass(frozen=True)
class AxPolicy:
    """SWAPPER approximate-matmul policy (the paper's technique as a
    first-class framework feature; DESIGN.md §5).

    backend:
      'mxu'    — closed-form factorization of the truncation family into two
                 exact int8 matmuls (MXU-friendly; production path at scale)
      'kernel' — the Pallas ax_matmul VPU kernel (arbitrary families)
      'emul'   — pure-jnp reference (tests)
    """

    mult_name: str = "mul8s_trunc0_4"
    swap_operand: str = "A"        # flattened SwapConfig (keeps dataclass hashable)
    swap_bit: int = 3
    swap_value: int = 0
    swap_enabled: bool = True
    backend: str = "mxu"
    targets: Tuple[str, ...] = ("mlp", "attn_out")  # which projections to approximate

    @property
    def swap(self):
        from repro.core.swapper import SwapConfig

        if not self.swap_enabled:
            return None
        return SwapConfig(self.swap_operand, self.swap_bit, self.swap_value)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    o_bias: bool = False        # a bias on the attention output projection
    rope_theta: float = 1e4
    norm: str = "rms"           # rms (gain 1 + scale) | layer (LayerNorm, + bias)
    norm_eps: float = 1e-6
    act: str = "silu"           # silu (swiglu) | gelu (plain 2-mat mlp)
    tie_embeddings: bool = False
    # --- local/global attention pattern (gemma3 / recurrentgemma) -------
    local_window: int = 0       # sliding-window size for local layers
    pattern: Tuple[str, ...] = ()  # per-period layer kinds, e.g. 5x local + global
    # --- MoE --------------------------------------------------------------
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_dense: int = 0        # leading dense layers (deepseek-moe)
    moe_capacity: float = 1.25  # capacity factor (reduced configs use a high
    #                             value so train/decode paths drop no tokens)
    # --- RG-LRU hybrid ----------------------------------------------------
    d_rnn: int = 0
    # --- SSM (mamba2) -------------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    # --- encoder-decoder (whisper) -----------------------------------------
    n_enc_layers: int = 0
    # --- VLM (qwen2-vl) -----------------------------------------------------
    mrope: bool = False
    # --- numerics -----------------------------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    ax: Optional[AxPolicy] = None
    # pad the embedding/logits vocab dim to a multiple (perf knob: enables
    # vocab-parallel logits when the raw vocab does not divide the mesh;
    # padded ids are masked to -inf in the loss)
    pad_vocab_multiple: int = 1

    @property
    def padded_vocab(self) -> int:
        m = self.pad_vocab_multiple
        return -(-self.vocab // m) * m if m > 1 else self.vocab

    @property
    def head_dim_(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    def layer_kinds(self) -> Tuple[str, ...]:
        """Resolved per-layer kind list of length n_layers."""
        if self.family == "ssm":
            return ("ssm",) * self.n_layers
        kinds = []
        if self.first_dense:
            kinds += ["dense_ffn"] * self.first_dense
        period = self.pattern or ("global",)
        i = 0
        while len(kinds) < self.n_layers:
            kinds.append(period[i % len(period)])
            i += 1
        return tuple(kinds[: self.n_layers])


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Distribution strategy — the §Perf hillclimbing surface."""

    fsdp: bool = True            # shard weight d_model dim over 'data'
    seq_shard: bool = True       # Megatron-style sequence parallel residual
    remat: str = "layer"         # 'none' | 'layer' | 'dots'
    grad_accum: int = 1
    donate: bool = True
    grad_compress: str = "none"  # 'none' | 'bf16' (all-reduce compression)
    scan_layers: bool = True
    ep: bool = True              # expert parallelism over 'model'
    dp_only: bool = False        # no TP: 'model' axis joins the batch (small models)
