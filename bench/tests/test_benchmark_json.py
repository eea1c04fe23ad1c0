"""BENCHMARK.json keeps the benchmark's static rules, and every name in
it resolves to a file of its own: a configuration, a traffic mix, a
per-layer metric reader."""
import json
import re
from pathlib import Path

from harness import cell

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "cell": {"name", "config", "traffic", "chips", "why"},
    "e2e": {"name", "unit", "better", "bound", "source"},
    "layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _line(text):
    assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape_and_names():
    b = _bench()
    assert set(b) == KEYS["top"]
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert set(c) == KEYS["config"] and NAME.match(c["name"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).exists()
        assert len(c["reduced"]) <= 16
        _line(c["source"])
        _line(c["why"])
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    for w in b["workloads"]:
        assert set(w) == KEYS["cell"] and NAME.match(w["name"])
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        _line(w["why"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == KEYS["e2e"]
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == KEYS["layer"]
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        _line(m["layer"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_cell_reports_enough():
    b = _bench()
    for w in b["workloads"]:
        e2e = {m["name"] for m in cell.e2e_metrics(b, w)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = cell.layer_metrics(b, w)
        assert layers
        for m in b["per_layer"]:
            if w["name"] in m.get("workloads", []):
                assert m["moves"] in e2e
        for m in layers:
            assert callable(cell.reader(m["name"]))


def test_modes_and_kinds_are_files_found_by_name():
    from harness import find, modes

    for w in _bench()["workloads"]:
        spec = find.cell_spec(w["name"])
        assert callable(modes.load(spec["traffic"]["mode"]))
        kind = find.module("references", spec["config"]["sketch"]["kind"])
        assert callable(kind.replay) and callable(kind.deployment)
