"""``BENCHMARK.json`` and the name-to-file rule.

Every configuration, traffic mix and per-layer metric is a file of its
own, found from its name alone, so a later cell, mix or metric adds files
and entries and edits none:

- configuration ``<c>``: the ``file`` of its ``configs`` entry, and its
  plain reference ``bench/references/<mangle(c)>.py``;
- the configuration's ``model``: ``bench/models/<model>.py``;
- traffic ``<t>``: ``bench/traffic/<t>.json``;
- per-layer metric ``<m>``: ``bench/metrics/<mangle(m)>.py``;

where ``mangle`` writes every character that cannot stand in a Python
module name (``.`` and ``-``) as ``_``.
"""
from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]        # the checkout
BENCH = ROOT / "bench"


def mangle(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_]", "_", name)


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(known: {[e['name'] for e in entries]})")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def config_path(bench: dict, name: str, root: Path = ROOT) -> Path:
    return root / find(bench["configs"], name, "configuration")["file"]


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{mangle(name)}.py"


def reference_path(config_name: str) -> Path:
    return BENCH / "references" / f"{mangle(config_name)}.py"


def model_path(model: str) -> Path:
    return BENCH / "models" / f"{model}.py"


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    return _json(config_path(bench, name, root))


def load_traffic(name: str) -> dict:
    return _json(traffic_path(name))


def _module(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    rel = path.relative_to(ROOT).with_suffix("")
    return importlib.import_module(".".join(rel.parts))


def metric_module(name: str):
    return _module(metric_path(name))


def reference_module(config_name: str):
    return _module(reference_path(config_name))


def model_module(cfg: dict):
    return _module(model_path(cfg["model"]))
