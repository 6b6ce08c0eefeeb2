"""Find a cell and everything that belongs to it by the names in
``BENCHMARK.json``: its configuration file, its traffic file, its loop,
its metrics and their readers.  Nothing here lists cells, mixes or
metrics; a new one is a new file and a new entry.

    bench/configs/<config>.json   sizes, source, cuts, assumptions
    bench/traffic/<traffic>.json  the mix: which loop, and its parameters
    bench/loops/<loop>.py         the loop over the program's entry points
    bench/metrics/<metric>.py     a reader: read(ctx) -> number or None
    bench/layers/*.json           device operations that make up a layer
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # the configuration file
    traffic: dict       # the traffic file
    end_to_end: list    # BENCHMARK.json entries this cell reports
    per_layer: list


def _load_json(path: str):
    with open(path) as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    w = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], w["config"], "config")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
