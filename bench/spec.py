"""Finds everything by name: cells, configurations, mixes, metrics, limits.

``root`` is a checkout's root: ``BENCHMARK.json`` beside ``bench/``.  A cell
is an entry of ``workloads``; its configuration is
``bench/configs/<config>.json``, its mix ``bench/traffic/<traffic>.json``,
its correctness limits ``bench/limits/<cell>.json``, each per-layer
metric ``bench/metrics/<metric>.py``, and the architecture module that a
configuration may name, ``"architecture": "bench/archs/<name>.py"``: its
reference, weight rules, work counts and program checks
(bench/archs/__init__.py; without the key, bench/archs/dense.py).  Adding
any of them is adding a file.

A configuration may name a device mesh, ``engine.mesh``: axis name -> size
in the program's axis names, such as ``{"data": 1, "model": 4}``.  Its size
is the cell's ``chips``; a cell whose configuration names none runs on one
device.
"""
from __future__ import annotations

import importlib.util
import json
import math
from pathlib import Path

__all__ = ["ROOT", "load", "cell", "chips", "metric_reader", "architecture"]

ROOT = Path(__file__).resolve().parents[1]


def _json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load(root: Path = ROOT) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """One cell, resolved: its entry, configuration, architecture module,
    mix, limits and the metrics it reports at each trace setting."""
    root = Path(root)
    bench = load(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r}; known: {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _json(root / cfg_entry["file"])
    if "mesh" in config["engine"] and chips(config) != w["chips"]:
        raise ValueError(f"{name}: the mesh {config['engine']['mesh']} of "
                         f"{cfg_entry['file']} spans {chips(config)} devices, the "
                         f"cell asks for {w['chips']} chips")

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if applies(m) and m["moves"] in e2e_names]
    return {
        "name": name,
        "entry": w,
        "config": config,
        "arch": architecture(config, root),
        "traffic": _json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        "limits": _json(root / "bench" / "limits" / f"{name}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
        "root": root,
    }


def chips(config: dict) -> int:
    """The devices a configuration's program runs on: its mesh's size, or 1."""
    return math.prod(config["engine"].get("mesh", {}).values())


def _module(name: str, path: Path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _module(f"bench_metric_{name}",
                   Path(root) / "bench" / "metrics" / f"{name}.py").read


def architecture(config: dict, root: Path = ROOT):
    """The architecture module a configuration names (a path under
    ``root``), or the dense default."""
    rel = config.get("architecture")
    if rel is None:
        from bench.archs import dense

        return dense
    if Path(rel).is_absolute() or ".." in Path(rel).parts:
        raise ValueError(f"{config['name']}: architecture {rel!r} is not a path "
                         f"under the root")
    return _module(f"bench_arch_{Path(rel).stem}", Path(root) / rel)
