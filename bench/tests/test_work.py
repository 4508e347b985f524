"""Work accounting: parameter counts tie to the program's own layout, at
the reduced size and at full size (shapes only)."""
import dataclasses

import pytest

from _support import config as _config
from harness import serving, work
from repro.configs import reduced
from repro.launch.smoke import footprint

CONFIGS = ("qwen2-72b-2l-noswap", "starcoder2-15b-8l")


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("small", [True, False])
def test_params_match_footprint(name, small):
    config = _config(name)
    cfg = serving.program_config(config, reduced if small else None)
    assert work.params(serving.as_run(config, cfg)) == footprint(cfg, 1, 8)["params"]


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
    assert work.seconds_at_peak(dict(int8=400, flops=100), peaks) == 1.5


def test_program_config_refuses_a_differing_width():
    config = dict(_config("qwen2-72b-2l-noswap"), hidden_size=4096)
    with pytest.raises(ValueError):
        serving.program_config(config)
    cfg = serving.program_config(_config("qwen2-72b-2l-noswap"))
    assert dataclasses.asdict(cfg.ax)["targets"] == ("mlp", "attn_out")
