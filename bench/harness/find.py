"""Files of the benchmark found by name.

``BENCHMARK.json`` and the files it names give names, and each name is a
file of its own under ``bench/``: a traffic file's ``mode`` is
``bench/modes/<mode>.py``, a configuration's sketch ``kind`` is
``bench/references/<kind>.py``, a per-layer metric is
``bench/metrics/<name>.py``.  A new mode, kind or metric is a new file;
no file of the harness names them.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path
from typing import Dict

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def module(folder: str, name: str):
    """The module ``bench/<folder>/<name>.py``, loaded once."""
    path = BENCH_DIR / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{folder} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", f"{folder}_{name}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str) -> Dict:
    """The cell's entry, its configuration and its traffic file."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {"bench": bench, "cell": cell,
            "config": load_json(ROOT / cfg_entry["file"]),
            "traffic": load_json(BENCH_DIR / "traffic"
                                 / f"{cell['traffic']}.json")}
