"""`Objective`: first-class, registrable cost functions over candidate grids.

An objective maps ``(workload, Candidates, controller) -> float64 cost
array`` — one cost per candidate, computed with array code so an exact search
is a single masked argmin. Register custom objectives with
``@register_objective("name")`` and they drive ``plan()`` (via a
``dse.register_strategy`` preset) and ``dse.sweep(objective=...)`` without
touching any `repro.plan` internals.

Built-ins:

  interconnect_words  the paper's BW (eqs 2+3 for convs, the blocked-GEMM
                      A/B/C word traffic for matmuls) — the default, and the
                      objective every built-in search Strategy minimizes
  sram_accesses       accesses at the accumulator-owning memory (controller
                      SRAM / VMEM), mirroring `plan.traffic`'s meter model
  energy_bytes        energy-weighted bytes: interconnect transfers cost
                      ~8x an SRAM access per byte (Horowitz-style ratio), so
                      this trades bus words against local accesses
  roofline_latency    max(compute, memory) time on the `repro.roofline`
                      machine model — latency, not traffic, as the target

All objectives use ceil iteration counts (``exact_iters=True``, the
executable semantics) — identical to what the seed exact searches minimized.
"""

from __future__ import annotations

from typing import Callable, Union

import numpy as np

from repro.plan import traffic
from repro.plan.schedule import Controller
from repro.plan.space import Candidates
from repro.plan.workload import Workload
from repro.roofline.constants import (ENERGY_PJ_INTERCONNECT_BYTE,
                                      ENERGY_PJ_SRAM_BYTE, HBM_BW,
                                      PEAK_FLOPS_BF16)

ObjectiveFn = Callable[[Workload, Candidates, Controller], np.ndarray]
Objective = Union[str, ObjectiveFn]

# The per-byte energy weights live in the one shared table
# (``repro.roofline.constants``), consumed by this module and by the
# cycle-approximate simulator (`repro.sim.energy`); the two paths are pinned
# to identical base energies by ``tests/test_sim.py``. The names are
# re-exported here for backwards compatibility.

OBJECTIVES: dict[str, ObjectiveFn] = {}


def register_objective(name: str) -> Callable[[ObjectiveFn], ObjectiveFn]:
    """Register a vectorized cost function under ``name``."""
    def deco(fn: ObjectiveFn) -> ObjectiveFn:
        if name in OBJECTIVES:
            raise ValueError(f"objective {name!r} already registered")
        OBJECTIVES[name] = fn
        return fn
    return deco


def get_objective(objective: Objective) -> ObjectiveFn:
    if callable(objective):
        return objective
    if isinstance(objective, str) and objective.startswith("sim_") \
            and objective not in OBJECTIVES:
        import repro.sim  # noqa: F401  (registers sim_latency / sim_energy)
    try:
        return OBJECTIVES[objective]
    except KeyError:
        raise KeyError(f"unknown objective {objective!r}; "
                       f"registered: {sorted(OBJECTIVES)}") from None


def _terms(wl: Workload, cands: Candidates, controller: Controller):
    return traffic.terms(wl, cands.bm, cands.bn, cands.bk, controller)


# --------------------------------------------------------------- interconnect
@register_objective("interconnect_words")
def interconnect_words(wl: Workload, cands: Candidates,
                       controller: Controller) -> np.ndarray:
    """Words crossing the interconnect/HBM — the paper's BW objective."""
    return _terms(wl, cands, controller).total


# --------------------------------------------------------------- SRAM traffic
@register_objective("sram_accesses")
def sram_accesses(wl: Workload, cands: Candidates,
                  controller: Controller) -> np.ndarray:
    """Total accumulator-memory accesses (reads + writes). Identical for both
    controllers: the active controller moves work off the bus, it does not
    remove it."""
    t = _terms(wl, cands, controller)
    return t.sram_reads + t.sram_writes


# ------------------------------------------------------------ weighted energy
@register_objective("energy_bytes")
def energy_bytes(wl: Workload, cands: Candidates,
                 controller: Controller) -> np.ndarray:
    """Energy-weighted bytes (pJ): interconnect bytes at ~8x the cost of SRAM
    bytes. Unlike pure word counts this penalizes the passive controller's
    read-back twice (once on the bus, once in SRAM)."""
    t = _terms(wl, cands, controller)
    return (t.bytes * ENERGY_PJ_INTERCONNECT_BYTE
            + t.sram_bytes * ENERGY_PJ_SRAM_BYTE)


# ---------------------------------------------------------- roofline latency
@register_objective("roofline_latency")
def roofline_latency(wl: Workload, cands: Candidates,
                     controller: Controller) -> np.ndarray:
    """max(compute, memory) seconds on the `repro.roofline` machine model.
    Compute time is schedule-invariant, so this objective is flat wherever
    the workload is compute-bound and reduces to byte-minimization where it
    is bandwidth-bound — exactly the regime the paper targets."""
    nbytes = _terms(wl, cands, controller).bytes
    return np.maximum(float(wl.flops) / PEAK_FLOPS_BF16, nbytes / HBM_BW)
