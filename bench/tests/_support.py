"""Configurations the tests read by name."""
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# A configuration no cell runs, kept to exercise the paths of the
# ``dense_gqa`` reference and work count that the cells' configuration does
# not: a plain two-matrix GELU MLP with biases, 12:1 grouped-query
# attention.  It is StarCoder2-15B's widths (arXiv:2402.19173, cut to 8 of
# 40 layers) under the ``dense_gqa`` block -- RMSNorm, full attention, no
# bias on the attention output -- as the program's ``starcoder2-15b`` runs
# it; not StarCoder2's published block (LayerNorm with bias, a bias on
# every linear, a 4,096-token sliding window).
STARCODER2 = {
    "name": "starcoder2-15b-8l", "reference": "dense_gqa", "hidden_size": 6144,
    "intermediate_size": 24576, "num_attention_heads": 48,
    "num_key_value_heads": 4, "head_dim": 128, "vocab_size": 49152,
    "num_hidden_layers": 8, "rope_theta": 100000.0, "rms_norm_eps": 1e-06,
    "hidden_act": "gelu_pytorch_tanh", "attention_bias": True,
    "mlp_bias": True, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "approx": {"multiplier": "mul8s_trunc0_4", "backend": "mxu",
               "targets": ["mlp", "attn_out"], "swap": [1, 3, 2]},
    "program": {"arch": "starcoder2-15b"},
    "correct": {"statistic": "gap_mean", "limit": None},
}


def config(name):
    """A configuration by name: a file of ``bench/configs/`` or
    ``STARCODER2``."""
    if name == STARCODER2["name"]:
        return json.loads(json.dumps(STARCODER2))
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
