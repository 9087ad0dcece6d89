"""Finds what a cell is made of, by name, in files of their own. A later PR
adds a configuration, a traffic mix or a per-layer metric as new files plus
entries in `BENCHMARK.json`; nothing here, and no file that is there, needs an
edit. No jax: the parent process imports this.

    BENCHMARK.json workloads[i]  -> config name, traffic name, chips
    perfbench/configs/<config>.json        its `builder`   -> perfbench/builders/<file>
    perfbench/traffic/<traffic>.json       its `generator` -> perfbench/generators/<file>
    perfbench/layer_metrics/<metric>.json  its `reader`    -> perfbench/readers/<file>
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def module(kind: str, file_name: str):
    """`perfbench/<kind>/<file_name>` as a module."""
    return importlib.import_module(
        f"perfbench.{kind}." + file_name.removesuffix(".py"))


def benchmark(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                     f"(have {[w['name'] for w in bench['workloads']]})")


def config_of(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return read_json(os.path.join(root, c["file"]))
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str, here: str = HERE) -> dict:
    return read_json(os.path.join(here, "traffic", name + ".json"))


def metrics_of(bench: dict, group: str, workload: str) -> list:
    """The cell's metrics of `end_to_end` or `per_layer`: those with no
    `workloads` key, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def layer_metric(name: str, here: str = HERE) -> dict:
    return read_json(os.path.join(here, "layer_metrics", name + ".json"))


def peaks(device_kind: str, here: str = HERE) -> dict:
    table = read_json(os.path.join(here, "peaks.json"))["chips"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device_kind {device_kind!r} "
                         f"in perfbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def per_layer_entries(bench: dict, here: str = HERE) -> list:
    """The `per_layer` list of BENCHMARK.json as the metric files give it
    (`python perfbench/run.py --print-per-layer`), in file-name order. A
    metric's file may name cells that are not in the benchmark yet (the mixes
    `PERF.md` keeps for later): those are left out of its list, and a metric
    none of whose cells is in the benchmark is left out altogether."""
    cells = {w["name"] for w in bench["workloads"]}
    out = []
    for fname in sorted(os.listdir(os.path.join(here, "layer_metrics"))):
        m = read_json(os.path.join(here, "layer_metrics", fname))
        entry = {k: m[k] for k in ("name", "unit", "better", "source", "layer",
                                   "moves")}
        if "workloads" in m:
            entry["workloads"] = [w for w in m["workloads"] if w in cells]
            if not entry["workloads"]:
                continue
        out.append(entry)
    return out
