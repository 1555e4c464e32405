"""The benchmark's data files, found by name.

``BENCHMARK.json`` at the checkout root lists the cells and metrics;
each cell's parameters live in ``bench/workloads/<cell>.json``, each
traffic mix in ``bench/traffic/<traffic>.json`` (the run mode and what
it asks of the solver), each configuration in
``bench/configs/<config>.json``, each run mode in
``bench/modes/<mode>.py``, each plain reference in
``bench/references/<reference>.py`` and each per-layer metric's reader
in ``bench/metrics/<metric>.py``. Adding any of them is adding files.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(CHECKOUT, "BENCHMARK.json")


def config(name: str) -> dict:
    return _json(HERE, "configs", f"{name}.json")


def workload(name: str) -> dict:
    """The cell's parameters, its traffic mix's merged in."""
    work = _json(HERE, "workloads", f"{name}.json")
    traffic = _json(HERE, "traffic", f"{work['traffic']}.json")
    clash = set(traffic) & set(work)
    if clash:
        raise ValueError(f"{name}: {sorted(clash)} set by both the "
                         f"workload and traffic {work['traffic']!r}")
    return {**traffic, **work}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = _json(HERE, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json knows {sorted(table)}")
    return table[device_kind]


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mode(name: str):
    return _module("modes", name)


def metric_reader(name: str):
    return _module("metrics", name)


def reference(name: str):
    return _module("references", name)


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that the cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]
