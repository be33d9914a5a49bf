"""BENCHMARK.json and the name-to-file rule of the benchmark (bench/)."""
import json
import re

import pytest

from bench import harness, spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("name,mangled", [
    ("mfu.decode", "mfu_decode"),
    ("decode_attention_roofline", "decode_attention_roofline"),
    ("starcoder2-3b", "starcoder2_3b"),
    ("batch_rows.oneshot", "batch_rows_oneshot"),
])
def test_mangle_writes_dots_and_dashes_as_underscores(name, mangled):
    assert spec.mangle(name) == mangled


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    w = spec.find(BENCH["workloads"], cell, "workload")
    cfg = spec.load_config(BENCH, w["config"])
    assert cfg["name"] == w["config"]
    assert spec.model_path(cfg["model"]).is_file()
    assert spec.reference_path(w["config"]).is_file()
    traffic = spec.load_traffic(w["traffic"])
    assert traffic["kind"] == spec.model_module(cfg).KIND
    for m in harness.cell_metrics(BENCH, cell)["per_layer"]:
        assert callable(spec.metric_module(m["name"]).read), m["name"]
    spec.reference_module(w["config"])


def test_every_metric_file_is_named_by_a_metric():
    """No reader without a metric, no metric without a reader."""
    files = {p.stem for p in (spec.BENCH / "metrics").glob("*.py")
             if p.stem != "__init__"}
    assert files == {spec.mangle(m["name"]) for m in BENCH["per_layer"]}


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in cfg["source_values"], key
            assert cfg[key] != cfg["source_values"][key], key
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["source"] in ("host_clock",
                                                         "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        for cell in m["workloads"]:
            assert m["moves"] in {e["name"] for e in harness.cell_metrics(
                BENCH, cell)["end_to_end"]}
