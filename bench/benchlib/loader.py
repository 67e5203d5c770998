"""Find everything by name: BENCHMARK.json -> files under bench/.

A cell names a configuration and a traffic mix; a configuration names
its data builder; a traffic mix names its driver and its queries; a
per-layer metric names its reader. Each of those is one file, loaded by
path, so a later PR adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def read_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module (kind: drivers, data, queries,
    references, readers)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def open_session(cell_: dict, seed: int, rehearse: bool, trace: bool, tag: str = ""):
    """The cell's driver session over an emptied work directory inside the
    checkout, with the program importable and one compile cache at a fixed
    place inside the checkout (for this process and the worker it starts)
    unless the machine has placed it."""
    import shutil
    import sys

    workdir = os.path.join(ROOT, ".bench_work", tag + cell_["name"])
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    return module("drivers", cell_["traffic"]["driver"]).Session(cell_, seed, rehearse, trace, workdir)


def cell(name: str) -> dict:
    """The cell with its configuration, its traffic mix and the metrics
    that it has to report, all resolved."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic = read_json("traffic", f"{w['traffic']}.json")

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bm["end_to_end"] if reported(m)],
        "per_layer": [m for m in bm["per_layer"] if reported(m)],
    }
