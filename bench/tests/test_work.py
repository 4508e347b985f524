"""Work accounting (the ``dense_gqa`` reference module's count):
parameter counts tie to the program's own layout, at the reduced size and
at full size (shapes only); and the program check that ties a
configuration file to the program's ModelConfig."""
import dataclasses

import pytest

from _support import config as _config
from harness import readers, serving, spec
from repro.configs import reduced
from repro.launch.smoke import footprint

CONFIGS = ("qwen2-72b-2l-noswap", "starcoder2-15b-8l")
work = spec.load_reference("dense_gqa")


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("small", [True, False])
def test_params_match_footprint(name, small):
    config = _config(name)
    cfg = serving.program_config(config, work, reduced if small else None)
    assert (work.params(serving.as_run(config, cfg, work))
            == footprint(cfg, 1, 8)["params"])


def test_published_counts():
    q = work.params(_config("qwen2-72b-2l-noswap"))
    s = work.params(_config("starcoder2-15b-8l"))
    assert q == pytest.approx(4.247e9, rel=1e-3)
    assert s == pytest.approx(3.674e9, rel=1e-3)


def test_per_token_counts():
    c = _config("qwen2-72b-2l-noswap")
    d, ff, L = 8192, 29568, 2
    one = work.per_token(c, 0, False)
    assert one["int8"] == L * 2 * (8192 * 8192 + 3 * d * ff)
    sampled = work.per_token(c, 99, True)
    assert sampled["flops"] - one["flops"] == 2 * d * 152064 + L * 4 * 99 * 64 * 128
    pre = work.prefill(c, 3)
    toks = [work.per_token(c, p, p == 2) for p in range(3)]
    assert pre["int8"] == sum(t["int8"] for t in toks)
    assert pre["flops"] == sum(t["flops"] for t in toks)


def test_seconds_at_peak():
    peaks = dict(int8_ops_per_s=400.0, bf16_flops_per_s=200.0)
    assert readers.seconds_at_peak(dict(int8=400, flops=100), peaks) == 1.5


# The integers harness/work.py gave before the count moved into
# references/dense_gqa.py (computed by the code of that parent commit):
# params, per_token at (position, sampled) = (0, no), (0, yes), (511, no),
# (2560, yes), and prefill of 1, 128 and 2,048 tokens, each (int8, flops).
PINNED = {
    "qwen2-72b-2l-noswap": dict(
        params=4246794240,
        per_token=[(3175088128, 335609856), (3175088128, 2827026432),
                   (3175088128, 369098752), (3175088128, 2994798592)],
        prefill=[(3175088128, 2827026432), (406411280384, 45982154752),
                 (6502580486144, 827192246272)]),
    "starcoder2-15b-8l": dict(
        params=3674617856,
        per_token=[(5435817984, 704839680), (5435817984, 1308819456),
                   (5435817984, 805306368), (5435817984, 1812135936)],
        prefill=[(5435817984, 1308819456), (695784701952, 92421488640),
                 (11132555231232, 1856231178240)]),
}


def _ops(d):
    return (d["int8"], d["flops"])


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("what", ["params", "per_token", "prefill"])
def test_moved_count_equals_the_old(name, what):
    c = _config(name)
    got = dict(
        params=lambda: work.params(c),
        per_token=lambda: [_ops(work.per_token(c, p, s)) for p, s in
                           ((0, False), (0, True), (511, False),
                            (2560, True))],
        prefill=lambda: [_ops(work.prefill(c, n)) for n in (1, 128, 2048)])
    assert got[what]() == PINNED[name][what]


def test_program_config_refuses_a_differing_width():
    config = dict(_config("qwen2-72b-2l-noswap"), hidden_size=4096)
    with pytest.raises(ValueError):
        serving.program_config(config, work)
    cfg = serving.program_config(_config("qwen2-72b-2l-noswap"), work)
    assert dataclasses.asdict(cfg.ax)["targets"] == ("mlp", "attn_out")


@pytest.mark.parametrize("key,value", [
    ("rope_theta", 10000.0), ("rms_norm_eps", 1e-05),
    ("attention_bias", False), ("mlp_bias", True),
    ("hidden_act", "gelu_pytorch_tanh"), ("tie_word_embeddings", True),
    ("num_key_value_heads", 4), ("head_dim", 64), ("vocab_size", 151936)])
def test_program_config_refuses_a_mapped_key_that_differs(key, value):
    """Every key the reference maps is compared, not only the widths."""
    config = dict(_config("qwen2-72b-2l-noswap"), **{key: value})
    with pytest.raises(ValueError, match=key):
        serving.program_config(config, work)


@pytest.mark.parametrize("key", ["sliding_window", "norm_type", "use_bias"])
def test_program_config_refuses_an_unmapped_key(key):
    """A file key that neither the harness nor the reference maps to the
    program is refused, not ignored."""
    config = dict(_config("qwen2-72b-2l-noswap"), **{key: 4096})
    with pytest.raises(ValueError, match=key):
        serving.program_config(config, work)


def test_program_config_refuses_a_key_the_reference_reads_and_the_file_lacks():
    config = _config("qwen2-72b-2l-noswap")
    del config["mlp_bias"]
    with pytest.raises(ValueError, match="mlp_bias"):
        serving.program_config(config, work)


def test_program_config_refuses_a_program_outside_the_block(monkeypatch):
    """A program whose block holds what the reference leaves out (here a
    sliding window) differs from the file though every file key agrees."""
    monkeypatch.setitem(serving.ARCHS, "qwen2-72b", dataclasses.replace(
        serving.ARCHS["qwen2-72b"], local_window=4096, pattern=("local",)))
    with pytest.raises(ValueError, match="local_window"):
        serving.program_config(_config("qwen2-72b-2l-noswap"), work)
