"""The force pass's work, counted from sizes, against a hand count."""
from __future__ import annotations

import copy

import pytest

from bench import roofline, spec


def test_lattice_neighbours_by_hand():
    # within 2h = 2.4 ds of a node: 4 at ds, 4 at √2 ds, 4 at 2 ds and
    # 8 at √5 ds; √8 ds and 3 ds lie outside
    assert roofline.lattice_neighbours(1.2, 2) == 20
    # within 2 ds: only distances 1 and √2 (2 itself is not inside)
    assert roofline.lattice_neighbours(1.0, 2) == 8


def test_dam_break_count_by_hand_at_ten_particles():
    cfg = spec.config("dam_break")
    got = roofline.force_pass(cfg, n_particles=10)
    # record read: rel 2×2 B, v 2×2 B, m 2 B, ρ 4 B; written: dρ 4 B and
    # acceleration 2×4 B
    assert got["bytes"] == 10 * (4 + 4 + 2 + 4 + 4 + 8)
    assert got["pairs"] == 10 * 20
    # geometry 13, continuity 8, pressure 7, artificial viscosity 8,
    # delta-SPH 9
    assert got["flops"] == 200 * (13 + 8 + 7 + 8 + 9)


def test_poiseuille_count_by_hand_at_ten_particles():
    cfg = spec.config("poiseuille")
    got = roofline.force_pass(cfg, n_particles=10)
    assert got["bytes"] == 10 * 26
    # geometry 13, continuity 8, pressure 7, Morris viscosity 9
    assert got["flops"] == 200 * (13 + 8 + 7 + 9)


@pytest.mark.parametrize("name", ["dam_break", "poiseuille"])
def test_count_does_not_depend_on_the_force_path(name):
    cfg = spec.config(name)
    counts = []
    for backend in ("xla", "pallas"):
        c = copy.deepcopy(cfg)
        c["backend"] = backend
        counts.append(roofline.force_pass(c))
    assert counts[0] == counts[1]


def test_roofline_share_names_its_bound():
    peak = spec.peaks("TPU v5 lite")
    counts = {"flops": 1.97e11, "bytes": 8.19e8}  # 1 ms each at peak
    pct, bound = roofline.roofline_pct(counts, peak, 0.002)
    assert pct == pytest.approx(50.0)
    counts["bytes"] *= 2
    pct, bound = roofline.roofline_pct(counts, peak, 0.004)
    assert (pct, bound) == (pytest.approx(50.0), "hbm")
    counts["flops"] *= 4
    assert roofline.roofline_pct(counts, peak, 0.004)[1] == "flops"
