"""Everything the benchmark finds by name, and the decision of ``correct``
from the numbers a driver compares.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness reads

    perfbench/configs/<config>.json      sizes, source, reduced, assumed
    perfbench/traffic/<traffic>.json     lengths, batch, token law
    perfbench/workloads/<cell>.json      mesh, program levers, optimizer,
                                         the limits of the comparison
    perfbench/references/<reference>.py  the plain reference the config names
    perfbench/drivers/<kind>.py          the driver of the traffic's kind:
                                         its window, its end-to-end values,
                                         the numbers it compares
    perfbench/metrics/<metric>.py        one per-layer metric each
    perfbench/peaks.json                 peaks by device_kind

so a later PR adds a cell, a configuration or a metric as new files and a
new entry, and edits none of these.

A reference module provides, for the configurations that name it:

    dims(cfg)                     the sizes readers need (heads ``H``, head
                                  width ``hd``, layers ``L``, ...)
    init_params(cfg, key)         the weights of a run, made from the seed
    train_readings(cfg, opt, ...) the numbers its driver compares
    flops_per_step(cfg, traffic)  the model operations a step requires,
                                  which ``mfu`` reads

so a new configuration brings its own counts in its own file.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    entry: dict
    config: dict
    traffic: dict
    workload: dict

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _pb(root: str, *parts: str) -> str:
    return os.path.join(root, "perfbench", *parts)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def load_cell(root: str, name: str) -> Cell:
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    return Cell(
        name=name, root=root, entry=entry,
        config=_json(_pb(root, "configs", entry["config"] + ".json")),
        traffic=_json(_pb(root, "traffic", entry["traffic"] + ".json")),
        workload=_json(_pb(root, "workloads", name + ".json")))


def load_module(root: str, kind: str, name: str):
    """Import ``perfbench/<kind>/<name>.py`` (names may hold dots)."""
    path = _pb(root, kind, name + ".py")
    key = f"perfbench_{kind}:{os.path.abspath(path)}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def reference_module(cell: Cell):
    return load_module(cell.root, "references", cell.config["reference"])


def driver_module(cell: Cell):
    return load_module(cell.root, "drivers", cell.traffic["kind"])


def peak(root: str, device_kind: str) -> dict:
    table = _json(_pb(root, "peaks.json"))
    if device_kind not in table:
        raise RuntimeError(
            f"no published peak for device_kind {device_kind!r} in "
            f"perfbench/peaks.json; add it with its source")
    return table[device_kind]


def metrics_for(bench: dict, group: str, cell: str) -> list[dict]:
    """The metrics of ``group`` ("end_to_end" or "per_layer") this cell
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------ correctness


def decide(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit."""
    checks = {k: {"value": numbers[k], "limit": limits[k]}
              for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
