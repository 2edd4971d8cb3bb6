"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell (``workloads``),
its configuration (``configs[].file``) and its traffic mix; the mix is
``traffic/<name>.json`` beside this file, the mix's ``kind`` names the
driver that runs it, ``<kind>.py`` beside this file, and each per-layer
metric is read by ``layers/<metric>.py``. Adding a cell, a mix, a kind of
traffic or a metric is adding files: nothing here lists them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join("benchmarks", "cells")  # this directory, from the root
# traffic kinds whose driver file has another name; any other kind ``k`` is
# run by ``<k>.py``
DRIVER_ALIASES = {"open_loop": "serve"}
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@dataclasses.dataclass
class Cell:
    """One workload with everything the harness needs to run it."""

    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list  # the BENCHMARK.json entries this cell reports
    per_layer: list


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name``: its configuration, mix and metrics."""
    bench = benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if m["moves"] in e2e_names and _reports(m, name)]
    return Cell(name=name, config=load_json(os.path.join(root, conf["file"])),
                traffic=traffic(w["traffic"], root), chips=int(w["chips"]),
                end_to_end=e2e, per_layer=layers)


def traffic(name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, CELLS, "traffic", f"{name}.json"))


def _load(prefix: str, name: str, path: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str, root: str = ROOT):
    """The ``read(run)`` function of ``layers/<metric>.py``."""
    return _load("layer_", metric,
                 os.path.join(root, CELLS, "layers", f"{metric}.py")).read


def driver(kind: str, root: str = ROOT):
    """The module that runs a mix of traffic kind ``kind``: ``<kind>.py``
    beside this file (``serve.py`` for ``open_loop``). It has
    ``run(cell, seed, seconds, ctx)``."""
    name = DRIVER_ALIASES.get(kind, kind)
    path = os.path.join(root, CELLS, f"{name}.py")
    if not NAME.fullmatch(kind) or not os.path.isfile(path):
        raise KeyError(f"no driver for traffic kind {kind!r}: {path} "
                       "does not exist")
    return _load("driver_", name, path)
