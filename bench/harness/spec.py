"""Find everything a cell needs by name: ``BENCHMARK.json`` at the root,
one configuration file per entry of ``configs``, the plain reference each
configuration names under ``bench/references/``, one traffic file per mix
under ``bench/traffic/``, one reader per per-layer metric under
``bench/metrics/``.  A new configuration, model, mix or metric is a new
file (plus an entry in ``BENCHMARK.json``); nothing here names one."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

# what a metric reader module must declare, beside ``read(ctx)``.  The
# cells that report a metric are listed in BENCHMARK.json alone, so a
# later cell joins a metric by an entry there, without editing its reader.
METRIC_FIELDS = ("NAME", "UNIT", "BETTER", "SOURCE", "LAYER", "MOVES",
                 "READS")

# what a reference module must declare: its name, how the configuration
# file's keys map to the program's ModelConfig (``PROGRAM_KEYS``) and what
# the program must hold for what the block leaves out (``PROGRAM_FIXED``),
# the replay's row block, the model, and its work count
REFERENCE_FIELDS = ("NAME", "PROGRAM_KEYS", "PROGRAM_FIXED", "ROWS",
                    "Reference", "params", "per_token", "prefill")


class SpecError(ValueError):
    """BENCHMARK.json and the files it names disagree."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                    f"({', '.join(w['name'] for w in bench['workloads'])})")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            data = json.loads((Path(root) / c["file"]).read_text())
            if data.get("name") != name:
                raise SpecError(f"{c['file']} names {data.get('name')!r}, "
                                f"BENCHMARK.json {name!r}")
            if "reference" not in data:
                raise SpecError(f"{c['file']} names no reference module")
            return data
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    data = json.loads(path.read_text())
    if data.get("name") != name:
        raise SpecError(f"{path} names {data.get('name')!r}, not {name!r}")
    return data


def end_to_end(bench: dict, cell_name: str) -> list:
    """The end-to-end metrics this cell reports (all, or those whose
    ``workloads`` list it)."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def _load(kind: str, name: str, root: Path):
    """Import ``bench/<kind>/<name>.py`` by path (a name may hold dots)."""
    path = Path(root) / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return path, mod


def load_reference(name: str, root: Path = ROOT):
    """Import ``bench/references/<name>.py`` and check that it declares
    what a reference must."""
    path, mod = _load("references", name, root)
    missing = [f for f in REFERENCE_FIELDS if not hasattr(mod, f)]
    missing += [f"Reference.{a}" for a in ("hidden", "head")
                if hasattr(mod, "Reference")
                and not hasattr(mod.Reference, a)]
    if missing:
        raise SpecError(f"{path} lacks {missing}")
    if mod.NAME != name:
        raise SpecError(f"{path} declares NAME {mod.NAME!r}")
    return mod


def load_metric(name: str, root: Path = ROOT):
    """Import ``bench/metrics/<name>.py`` and check that it declares what a
    reader must."""
    path, mod = _load("metrics", name, root)
    missing = [f for f in METRIC_FIELDS if not hasattr(mod, f)]
    if missing or not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} lacks {missing or ['read']}")
    if mod.NAME != name:
        raise SpecError(f"{path} declares NAME {mod.NAME!r}")
    return mod


def per_layer(bench: dict, cell_name: str, root: Path = ROOT) -> list:
    """``(entry, reader module)`` for each per-layer metric this cell
    reports; the reader's declarations must agree with the entry."""
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        mod = load_metric(m["name"], root)
        for key, field in (("unit", "UNIT"), ("better", "BETTER"),
                           ("source", "SOURCE"), ("layer", "LAYER"),
                           ("moves", "MOVES")):
            if m[key] != getattr(mod, field):
                raise SpecError(f"metric {m['name']}: BENCHMARK.json {key}="
                                f"{m[key]!r}, reader {field}="
                                f"{getattr(mod, field)!r}")
        out.append((m, mod))
    return out


def resolve(cell_name: str, root: Path = ROOT) -> dict:
    """Everything one run of ``cell_name`` reads: the cell, its
    configuration, the configuration's reference module and traffic, and
    its metric lists."""
    bench = load_benchmark(root)
    c = cell(bench, cell_name)
    conf = config(bench, c["config"], root)
    return dict(bench=bench, cell=c, config=conf,
                reference=load_reference(conf["reference"], root),
                traffic=traffic(c["traffic"], root),
                end_to_end=end_to_end(bench, cell_name),
                per_layer=per_layer(bench, cell_name, root))
