"""The chip smoke test's functions (``repro.launch.smoke``) end to end on the
CPU at ``reduced(qwen2-72b)`` size, with the smoke's own cuts: serving
through ``launch/serve._run_fleet``, the completion checks, the approximate
vs exact prefill comparison, the int8 matmul against the ``emul``
reference, and (4 forced CPU devices, in a subprocess) the
4-replica fleet against one device.  ``chip_smoke.py`` itself must refuse
to run without a TPU, and without the rest of the repository."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.launch import smoke as S

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(ROOT, "src")
TRAFFIC = dict(requests=5, prompt_len=32, new_tokens=6, slots=4)


def _cfg():
    return S.smoke_config(reduced(ARCHS["qwen2-72b"]))


def test_smoke_serves_every_request_one_device():
    cfg = _cfg()
    assert cfg.n_layers == S.CUTS["n_layers"]
    assert cfg.param_dtype == "bfloat16" and cfg.ax.backend == "mxu"
    res = S.serve(cfg, 1, S.CompileClock(), **TRAFFIC)
    n_tok = S.check_served(res, cfg)
    assert n_tok == sum(r.max_new for r in res["requests"])
    assert len(res["requests"]) == TRAFFIC["requests"]
    assert res["stats"]["decode_retraces_post_warmup"] == 0
    assert res["compile_s"] > 0


def test_smoke_check_rejects_bad_completions():
    cfg = _cfg()
    res = S.serve(cfg, 1, S.CompileClock(), **TRAFFIC)
    short = dict(res, done=res["done"][1:])
    with pytest.raises(S.SmokeFailure, match="completed rids"):
        S.check_served(short, cfg)
    c = res["done"][0]
    oov = dataclasses.replace(c, tokens=np.full_like(c.tokens, cfg.vocab))
    bad = dict(res, done=[oov] + res["done"][1:])
    with pytest.raises(S.SmokeFailure, match="outside the vocabulary"):
        S.check_served(bad, cfg)
    retraced = dict(res, stats={**res["stats"],
                                "decode_retraces_post_warmup": 1})
    with pytest.raises(S.SmokeFailure, match="retraces"):
        S.check_served(retraced, cfg)


def test_smoke_prefill_logit_error_is_finite_and_nonzero():
    out = S.prefill_logit_error(_cfg(), TRAFFIC["prompt_len"])
    assert out["finite"]
    # the mxu policy truncates operand bits: approximate, so never exact
    assert 0.0 < out["rel_err"] < 10.0
    assert 0.0 <= out["top1"] <= 1.0


def test_smoke_matmul_matches_emul_reference():
    assert S.matmul_reference_check(_cfg()) == ["mxu", "kernel"]


def test_footprint_counts_published_widths():
    fp = S.footprint(S.smoke_config(ARCHS["qwen2-72b"]), 8, 545)
    assert fp["params"] == 4_246_794_240
    # bf16 weights plus the float32 norm scales
    assert 8.49e9 < fp["param_bytes"] < 8.5e9
    # 2 layers x {k, v} x 8 slots x 545 positions x 8 kv heads x 128 x bf16
    assert fp["cache_bytes"] == 2 * 2 * 8 * 545 * 8 * 128 * 2


def test_chip_smoke_refuses_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout and "needs a TPU" in out.stderr
    # alone, without the repository beside it, it cannot even import
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode != 0 and '"ok"' not in out.stdout


_FLEET_SCRIPT = r"""
import json
from repro.configs import ARCHS, reduced
from repro.launch import smoke as S

cfg = S.smoke_config(reduced(ARCHS["qwen2-72b"]))
res = S.fleet_compare(cfg, 4, S.CompileClock(), **TRAFFIC)
print("RESULT:" + json.dumps(dict(
    fields=res["fields"],
    one=[[c.rid, c.tokens.tolist()] for c in res["one"]["done"]],
    many=[[c.rid, c.tokens.tolist()] for c in res["many"]["done"]])))
"""


@pytest.mark.multidevice
def test_smoke_fleet_matches_one_device_4dev():
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FLEET_SCRIPT.replace("TRAFFIC", repr(TRAFFIC))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    res = next(json.loads(line[len("RESULT:"):])
               for line in out.stdout.splitlines()
               if line.startswith("RESULT:"))
    assert res["fields"] >= 8
    assert len(res["many"]) == TRAFFIC["requests"]
    assert dict(res["many"]) == dict(res["one"])
