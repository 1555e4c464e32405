"""Configurations of the benchmark cut to a size a CPU test can hold."""
from __future__ import annotations

import copy
import json
import os

from bench import initial

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def scaled(name: str, ds: float) -> dict:
    """The configuration ``name`` at spacing ``ds``, its sizes re-derived
    from the program's case of the same name."""
    from repro.core import cases

    cfg = copy.deepcopy(load(name))
    over = dict(cfg["case_overrides"], ds=ds)
    case = cases.build_case(cfg["case"], **over)
    dom = case.domain()
    cfg["case_overrides"] = over
    cfg["ds"] = ds
    cfg["dt"] = case.dt
    cfg["physics"]["h"] = case.h
    cfg["box"]["lo"], cfg["box"]["hi"] = list(dom.lo), list(dom.hi)
    cfg["lattice"]["lo"], cfg["lattice"]["hi"] = list(dom.lo), list(dom.hi)
    cfg["n_particles"] = sum(initial.counts(cfg))
    return cfg


def strip(name: str, width: float) -> dict:
    """The configuration ``name`` at its own spacing, its periodic x axis
    cut to ``width``: the same particles across the channel, fewer along
    it."""
    cfg = copy.deepcopy(load(name))
    for block in (cfg["box"], cfg["lattice"]):
        block["hi"][0] = block["lo"][0] + width
    cfg["n_particles"] = sum(initial.counts(cfg))
    return cfg
