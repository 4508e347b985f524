"""The harness finds configurations, models, mixes and metrics by name."""
import json
import shutil
import time

import pytest

from harness import cell, spec, traffic
from repro.configs import reduced

ROOT = spec.ROOT


def test_every_cell_resolves():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        sp = spec.resolve(w["name"])
        assert sp["config"]["name"] == w["config"]
        traffic.check(sp["traffic"])
        names = {m["name"] for m in sp["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert sp["per_layer"], w["name"]
        for entry, reader in sp["per_layer"]:
            assert entry["moves"] in names, (w["name"], entry["name"])


def _files(root):
    return {p.relative_to(root): p.read_bytes()
            for p in sorted((root / "bench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_only(tmp_path):
    """A model (a reference module), a configuration naming it, a mix and a
    metric added as new files, with entries in BENCHMARK.json, load and run
    without editing any file already there."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    model = (ROOT / "bench/references/dense_gqa.py").read_text()
    assert 'NAME = "dense_gqa"' in model
    (tmp_path / "bench/references/gqa-copy.py").write_text(
        model.replace('NAME = "dense_gqa"', 'NAME = "gqa-copy"'))
    cfg = json.loads((ROOT / "bench/configs/qwen2-72b-2l-noswap.json").read_text())
    cfg["name"] = "qwen2-72b-1l"
    cfg["num_hidden_layers"] = 1
    cfg["reference"] = "gqa-copy"
    (tmp_path / "bench/configs/qwen2-72b-1l.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/batch.json").read_text())
    mix.update(name="chat-burst", arrivals={"kind": "gamma", "cv": 2.0,
                                            "rate_rps": 3.0})
    (tmp_path / "bench/traffic/chat-burst.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/slots_busy.serve.py").write_text(
        'NAME = "slots_busy.serve"\nUNIT = "%"\nBETTER = "higher"\n'
        'SOURCE = "program_span"\nLAYER = "scheduler (fleet/scheduler token '
        'loop)"\nMOVES = "itl_p95_ms"'
        '\nREADS = "share of slots decoding per step"\n\n\ndef read(ctx):\n'
        '    return None\n')
    bench["configs"].append(dict(bench["configs"][0], name="qwen2-72b-1l",
                                 file="bench/configs/qwen2-72b-1l.json"))
    bench["workloads"].append(dict(name="qwen2-72b-1l.chat-burst",
                                   config="qwen2-72b-1l", traffic="chat-burst",
                                   chips=1, why="test"))
    bench["workloads"].append(dict(name="qwen2-72b-1l.batch",
                                   config="qwen2-72b-1l", traffic="batch",
                                   chips=1, why="test"))
    next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")[
        "workloads"].append("qwen2-72b-1l.batch")
    itl = [m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms"]
    if not itl:
        itl = [dict(name="itl_p95_ms", unit="ms", better="lower", bound=0.1,
                    source="host_clock", workloads=[])]
        bench["end_to_end"].append(itl[0])
    itl[0]["workloads"].append("qwen2-72b-1l.chat-burst")
    assert "workloads" not in next(m for m in bench["end_to_end"]
                                   if m["name"] == "setup_s")
    bench["per_layer"].append(dict(
        name="slots_busy.serve", unit="%", better="higher",
        source="program_span", layer="scheduler (fleet/scheduler token loop)",
        moves="itl_p95_ms", workloads=["qwen2-72b-1l.chat-burst"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    sp = spec.resolve("qwen2-72b-1l.chat-burst", tmp_path)
    assert sp["config"]["num_hidden_layers"] == 1
    assert sp["traffic"]["arrivals"]["kind"] == "gamma"
    assert [e["name"] for e, _ in sp["per_layer"]] == ["slots_busy.serve"]
    assert {m["name"] for m in sp["end_to_end"]} == {"itl_p95_ms", "setup_s"}
    plan = traffic.Plan(sp["traffic"], 3, 1000)
    assert plan.take().due > 0
    assert sp["reference"].NAME == "gqa-copy"
    assert sp["reference"].__file__ == str(
        tmp_path / "bench/references/gqa-copy.py")
    out = cell.run("qwen2-72b-1l.batch", 9, 2.0, False, time.perf_counter(),
                   shrink=reduced, allow_cpu=True, root=tmp_path)
    assert out["checks"]["served_tokens_checked"]["value"] > 0
    assert {"tokens_per_s", "setup_s"} <= set(out["metrics"])
    before = _files(ROOT)
    after = _files(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before


def test_reader_must_agree(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    bench["per_layer"][0]["moves"] = "setup_s"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    name = bench["per_layer"][0]["workloads"][0]
    with pytest.raises(spec.SpecError):
        spec.resolve(name, tmp_path)


def test_config_must_name_its_reference(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    path = tmp_path / bench["configs"][0]["file"]
    cfg = json.loads(path.read_text())
    del cfg["reference"]
    path.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError, match="reference"):
        spec.config(bench, cfg["name"], tmp_path)


@pytest.mark.parametrize("lack", spec.REFERENCE_FIELDS
                         + ("Reference.hidden", "Reference.head"))
def test_reference_must_declare(tmp_path, lack):
    """A reference module that lacks any declaration is refused."""
    (tmp_path / "bench/references").mkdir(parents=True)
    model = (ROOT / "bench/references/dense_gqa.py").read_text()
    (tmp_path / "bench/references/lacking.py").write_text(
        model.replace('NAME = "dense_gqa"', 'NAME = "lacking"')
        + f"\n\ndel {lack}\n")
    with pytest.raises(spec.SpecError, match=lack):
        spec.load_reference("lacking", tmp_path)


def test_reference_must_name_itself(tmp_path):
    (tmp_path / "bench/references").mkdir(parents=True)
    shutil.copy(ROOT / "bench/references/dense_gqa.py",
                tmp_path / "bench/references/other.py")
    with pytest.raises(spec.SpecError, match="NAME"):
        spec.load_reference("other", tmp_path)
    assert spec.load_reference("dense_gqa").NAME == "dense_gqa"
