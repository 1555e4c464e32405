"""Work of one force pass, counted from a configuration's sizes.

The count belongs to the physics, not to whatever computes it, so the
same configuration gives the same count whether the program runs the
Pallas kernel or the XLA path:

* bytes: every particle's record read once (relative coordinate,
  velocity and mass at their storage widths, plus the fp32 density)
  and its fp32 density rate and acceleration written once;
* FLOPs: in-support pairs times the FLOPs of the pair equations. The
  pairs are those of the initial lattice: each particle sees every
  lattice node within 2h (h = ``physics.h``, spacing ``ds``).

Each +, −, ×, ÷ and square root counts one FLOP; a select counts none.
"""
from __future__ import annotations

import itertools
import math

BYTES = {"fp32": 4, "fp16": 2, "bf16": 2, "fp8": 1}


def lattice_neighbours(h_over_ds: float, dim: int) -> int:
    """Lattice nodes within 2h of a node, itself left out."""
    reach = int(math.ceil(2.0 * h_over_ds))
    count = 0
    for off in itertools.product(range(-reach, reach + 1), repeat=dim):
        r = math.sqrt(sum(o * o for o in off))
        if 0.0 < r < 2.0 * h_over_ds:
            count += 1
    return count


def flops_per_pair(physics: dict, dim: int) -> int:
    """FLOPs of one ordered pair's terms in the continuity and momentum
    sums, by term (see the module docstring for what counts)."""
    d = dim
    # x_ij (d), r² (2d − 1), r (1), q = r/h (1), the spline's
    # derivative (4), times its normalization and over r (2)
    n = d + (2 * d - 1) + 1 + 1 + 4 + 2
    # continuity: v_ij (d), v_ij·x_ij (2d − 1), × m_j × F (2), sum (1)
    n += d + (2 * d - 1) + 2 + 1
    # pressure: p_i/ρ_i² + p_j/ρ_j² (1), × m_j (1), × F (1),
    # × x_ij per axis (d), sum (d)
    n += 3 + 2 * d
    if physics["alpha"]:
        # r² + 0.01h² (1), μ_ij (1), ρ̄ (2), Π (2), × m_j and add (2)
        n += 8
    if physics["delta"]:
        # ρ_j − ρ_i (1), × 2 (1), x·∇W (1), ÷ (r² + 0.01h²) (2),
        # × m_j/ρ_j (2), × δ h c0 (1), sum (1)
        n += 9
    if physics["viscosity"] == "morris" and physics["mu"]:
        # x·∇W (1), 2μ m_j (1), ÷ ρ_i ρ_j (r² + 0.01h²) (3),
        # × v_ij per axis (d), sum (d)
        n += 5 + 2 * d
    return n


def force_pass(cfg: dict, n_particles: int | None = None) -> dict:
    """{"flops", "bytes", "pairs"} of one force pass of ``cfg``."""
    n = cfg["n_particles"] if n_particles is None else n_particles
    d = cfg["dim"]
    prec = cfg["precision"]
    read = (d * BYTES[prec["coords"]] + d * BYTES[prec["records"]]
            + BYTES[prec["records"]] + 4)
    written = 4 + 4 * d
    pairs = n * lattice_neighbours(cfg["physics"]["h"] / cfg["ds"], d)
    return {
        "pairs": pairs,
        "flops": pairs * flops_per_pair(cfg["physics"], d),
        "bytes": n * (read + written),
    }


def roofline_pct(counts: dict, peak: dict, seconds: float) -> tuple:
    """(share of the roofline in %, the bound that applies)."""
    t_flops = counts["flops"] / peak["flops_per_s"]
    t_bytes = counts["bytes"] / peak["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
