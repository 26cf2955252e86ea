"""Objective-driven, vectorized design-space exploration.

The paper's core contribution — pick the (m, n) partition minimizing
bandwidth under a MAC budget (eq 1) — is a constrained design-space search.
This module makes the three ingredients first-class and composable:

  `SearchSpace`  candidate grids            (``repro.plan.space``)
  `Constraint`   feasibility masks          (MAC budget, VMEM bytes,
                                             alignment, group divisibility)
  `Objective`    vectorized cost functions  (``repro.plan.objectives``)

``search()`` evaluates a whole candidate grid as arrays and takes one masked
argmin; every built-in `Strategy` is a thin preset of (space, constraints,
objective) — ``register_strategy`` adds new presets (e.g. around a custom
objective) that drive ``plan()`` and ``sweep()`` without touching call sites.

On top:

  sweep(networks x budgets x strategies x controllers) -> tidy rows
  pareto(rows)                                         -> frontier subset

Parity: the exact-search presets reproduce the seed scalar loops bit-for-bit
(same candidate order, strict-< first-minimum tie-break via argmin);
``tests/test_plan_parity.py`` is the contract.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Protocol, runtime_checkable

import numpy as np

from repro.obs.trace import Stopwatch
from repro.plan import conv_model, gemm_model
from repro.plan.objectives import Objective, get_objective, register_objective
from repro.plan.schedule import Controller, Schedule, Strategy
from repro.plan.space import (AlignedBlockSpace, Candidates, ClosedFormSpace,
                              ConvExactSpace, ConvGridSpace, SearchSpace)
from repro.plan.workload import ConvWorkload, MatmulWorkload, Workload

__all__ = [
    "Constraint", "MacBudget", "VmemBudget", "LaneAligned", "GroupDivisible",
    "StrategySpec", "SearchResult", "search", "plan_with_strategy",
    "strategy_spec", "register_strategy", "unregister_strategy",
    "sweep", "pareto", "certify_space", "register_objective", "get_objective",
    "SearchSpace", "Candidates", "ConvExactSpace", "ConvGridSpace",
    "AlignedBlockSpace", "ClosedFormSpace", "Objective",
]


# ------------------------------------------------------------------ constraints
@runtime_checkable
class Constraint(Protocol):
    """A feasibility mask over a candidate grid."""

    def __call__(self, workload: Workload, cands: Candidates,
                 budget: int) -> np.ndarray: ...


@dataclasses.dataclass(frozen=True)
class MacBudget:
    """eq (1): K^2 * m * n <= P (conv). Matmul grids are unconstrained by
    MACs (their budget is VMEM bytes) and pass."""

    def __call__(self, wl: Workload, cands: Candidates,
                 budget: int) -> np.ndarray:
        if not isinstance(wl, ConvWorkload):
            return np.ones(len(cands), dtype=bool)
        return wl.k * wl.k * cands.bm * cands.bn <= budget


@dataclasses.dataclass(frozen=True)
class VmemBudget:
    """The launch's VMEM footprint (`gemm_model.MatmulBlocks.vmem_bytes`:
    double-buffered inputs and partial-sum tiles) fits the VMEM byte budget;
    element widths come from the workload's dtypes."""

    double_buffer: bool = True

    def __call__(self, wl: MatmulWorkload, cands: Candidates,
                 budget: int) -> np.ndarray:
        nbytes = gemm_model.vmem_bytes_grid(
            cands.bm, cands.bn, cands.bk, in_bytes=wl.in_bytes,
            acc_bytes=wl.acc_bytes, double_buffer=self.double_buffer)
        return nbytes <= budget


@dataclasses.dataclass(frozen=True)
class LaneAligned:
    """TPU tiling: bm a sublane-tile multiple, bn/bk lane multiples."""

    lane: int = gemm_model.LANE
    sublane_tile: int = gemm_model.SUBLANE * 16

    def __call__(self, wl: Workload, cands: Candidates,
                 budget: int) -> np.ndarray:
        return ((cands.bm % self.sublane_tile == 0)
                & (cands.bn % self.lane == 0)
                & (cands.bk % self.lane == 0))


@dataclasses.dataclass(frozen=True)
class GroupDivisible:
    """Grouped convs: a partition never spans groups (m <= M/g, n <= N/g)."""

    def __call__(self, wl: ConvWorkload, cands: Candidates,
                 budget: int) -> np.ndarray:
        g = wl.groups
        return (cands.bm <= wl.cin // g) & (cands.bn <= wl.cout // g)


# ----------------------------------------------------------------- the search
@dataclasses.dataclass(frozen=True)
class StrategySpec:
    """A strategy as data: where to look, what must hold, what to minimize."""

    space: SearchSpace
    constraints: tuple = ()
    objective: Objective = "interconnect_words"


@dataclasses.dataclass(frozen=True)
class SearchResult:
    schedule: Schedule
    cost: float
    n_candidates: int
    n_feasible: int


def search(workload: Workload, budget: int | None = None, *,
           space: SearchSpace, constraints: tuple = (),
           objective: Objective = "interconnect_words",
           controller: "Controller | str" = Controller.PASSIVE) -> SearchResult:
    """One masked argmin over the space's candidate grid.

    Ties resolve to the earliest candidate in the space's enumeration order
    (``np.argmin`` keeps the first minimum), which is exactly what the seed
    scalar loops' strict ``<`` updates did.
    """
    controller = Controller.coerce(controller)
    if budget is None:
        from repro.plan.api import default_budget
        budget = default_budget(workload)
    budget = int(budget)
    cands = space(workload, budget)
    obj_fn = get_objective(objective)
    mask = np.ones(len(cands), dtype=bool)
    for c in constraints:
        mask &= c(workload, cands, budget)
    n_feasible = int(mask.sum())
    if n_feasible == 0:
        fallback = getattr(space, "fallback", None)
        if fallback is None:
            raise ValueError(
                f"no feasible candidate for {workload!r} at budget {budget}")
        cands = fallback(workload, budget)
        cost = obj_fn(workload, cands, controller)
        return SearchResult(schedule=cands.schedule_at(0, controller),
                            cost=float(cost[0]),
                            n_candidates=len(cands), n_feasible=0)
    cost = np.asarray(obj_fn(workload, cands, controller), dtype=np.float64)
    best = int(np.argmin(np.where(mask, cost, np.inf)))
    return SearchResult(schedule=cands.schedule_at(best, controller),
                        cost=float(cost[best]),
                        n_candidates=len(cands), n_feasible=n_feasible)


# ------------------------------------------------------------ strategy presets
_CONV_ALIASES = {"first_order": "paper_opt", "exhaustive_vmem": "exact_opt"}
_CONV_CLOSED = ("max_input", "max_output", "equal", "paper_opt")
_GEMM_CLOSED = ("first_order", "paper_opt", "equal")
_GEMM_EXACT = ("exhaustive_vmem", "exact_opt")

# Custom presets registered via register_strategy, keyed by (kind, name).
_CUSTOM_SPECS: dict[tuple[str, str], StrategySpec] = {}


def _conv_closed_rule(name: str):
    strategy = Strategy(name)

    def rule(wl: ConvWorkload, budget: int):
        m, n = conv_model.closed_form_mn(wl, budget, strategy)
        return m, n, 0
    return rule


def _gemm_first_order_rule(max_block: int):
    def rule(wl: MatmulWorkload, budget: int):
        b = gemm_model.first_order_block(wl.m, wl.n, wl.k,
                                         in_bytes=wl.in_bytes,
                                         acc_bytes=wl.acc_bytes,
                                         vmem_budget=budget,
                                         max_block=max_block)
        return b.bm, b.bn, b.bk
    return rule


def strategy_spec(strategy: "Strategy | str", kind: str,
                  max_block: int = 4096) -> StrategySpec:
    """The (space, constraints, objective) preset behind a strategy name for
    one workload kind. Custom `register_strategy` presets take precedence;
    unknown combinations raise the planner's 'not applicable' error.

    Builtin presets are memoized: specs and their spaces are stateless, so
    every planner call for the same (strategy, kind, max_block) shares one
    `StrategySpec` — which is what lets `PlanContext` share candidate grids
    across a whole fleet batch without rebuilding the space each time."""
    name = strategy.value if isinstance(strategy, Strategy) else str(strategy)
    if name.startswith("sim_") and (kind, name) not in _CUSTOM_SPECS:
        import repro.sim  # noqa: F401  (registers the sim_* presets)
    if (kind, name) in _CUSTOM_SPECS:
        return _CUSTOM_SPECS[(kind, name)]
    return _builtin_spec(name, kind, max_block)


@functools.lru_cache(maxsize=None)
def _builtin_spec(name: str, kind: str, max_block: int) -> StrategySpec:
    strategy = name
    if kind == "conv":
        # GEMM-flavoured names degrade to their conv equivalents: the closed
        # form *is* the first-order model, the exact search is exhaustive.
        name = _CONV_ALIASES.get(name, name)
        if name in _CONV_CLOSED:
            return StrategySpec(
                space=ClosedFormSpace(kind="conv", rule=_conv_closed_rule(name)))
        if name == "exact_opt":
            return StrategySpec(space=ConvExactSpace(),
                                constraints=(MacBudget(), GroupDivisible()))
        raise ValueError(f"strategy {strategy} is not applicable to convs")
    if kind == "matmul":
        if name in _GEMM_EXACT:
            return StrategySpec(space=AlignedBlockSpace(max_block),
                                constraints=(VmemBudget(),))
        if name in _GEMM_CLOSED:
            return StrategySpec(space=ClosedFormSpace(
                kind="matmul", rule=_gemm_first_order_rule(max_block)))
        raise ValueError(f"strategy {strategy} is not applicable to matmuls")
    raise ValueError(f"unknown workload kind {kind!r}")


def _workload_kind(workload: Workload) -> str:
    if isinstance(workload, ConvWorkload):
        return "conv"
    if isinstance(workload, MatmulWorkload):
        return "matmul"
    raise TypeError(f"unknown workload type {type(workload).__name__}")


def plan_with_strategy(workload: Workload, budget: int,
                       strategy: "Strategy | str",
                       controller: "Controller | str",
                       max_block: int = 4096, *,
                       objective: "Objective | None" = None) -> Schedule:
    """Resolve a strategy to its preset and run the search — the single
    implementation every planner in ``repro.plan.planners`` delegates to.

    ``objective`` overrides the preset's scoring function while keeping its
    candidate space and feasibility constraints (how ``plan_graph`` re-scores
    a word-count strategy's space under a simulated-cost objective).
    """
    spec = strategy_spec(strategy, _workload_kind(workload), max_block)
    return search(workload, budget, space=spec.space,
                  constraints=spec.constraints,
                  objective=spec.objective if objective is None else objective,
                  controller=controller).schedule


def register_strategy(name: str, *, conv: StrategySpec | None = None,
                      matmul: StrategySpec | None = None) -> None:
    """Register a custom strategy preset (and its planner) under ``name``,
    making it a first-class ``strategy=`` argument to ``plan()``/``sweep()``.
    Provide a spec per workload kind the strategy supports."""
    if conv is None and matmul is None:
        raise ValueError("register_strategy needs a conv and/or matmul spec")
    from repro.plan import api, planners

    # Register the planner FIRST: a duplicate name raises here, before any
    # spec is stored, so a failed registration cannot shadow a builtin.
    @planners.register_planner(name)
    def _planner(workload, budget, controller):
        return plan_with_strategy(workload, budget, name, controller)

    if conv is not None:
        _CUSTOM_SPECS[("conv", name)] = conv
    if matmul is not None:
        _CUSTOM_SPECS[("matmul", name)] = matmul
    # Plans are LRU-cached on the strategy *name*; drop anything cached under
    # a previous registration of this name — per-layer and graph-level alike.
    api.clear_plan_cache()
    from repro.plan import netplan
    netplan.clear_plan_graph_cache()


def unregister_strategy(name: str) -> None:
    """Remove a custom strategy preset and its planner (test hygiene).
    Built-in strategies cannot be unregistered."""
    from repro.plan import api, planners
    if name in {s.value for s in Strategy}:
        raise ValueError(f"cannot unregister built-in strategy {name!r}")
    _CUSTOM_SPECS.pop(("conv", name), None)
    _CUSTOM_SPECS.pop(("matmul", name), None)
    planners.PLANNERS.pop(name, None)
    api.clear_plan_cache()
    from repro.plan import netplan
    netplan.clear_plan_graph_cache()


# ---------------------------------------------------------------------- sweep
def _as_networks(networks) -> list[tuple[str, tuple]]:
    """Normalize the ``networks`` argument: a CNN-zoo name, an iterable of
    names, an iterable of workloads, or a {name: workloads} mapping."""
    from repro.plan.workload import conv_workloads
    if isinstance(networks, str):
        return [(networks, conv_workloads(networks))]
    if isinstance(networks, dict):
        return [(name, tuple(wls)) for name, wls in networks.items()]
    items = list(networks)
    if not items:
        return []
    if all(isinstance(it, str) for it in items):
        return [(name, conv_workloads(name)) for name in items]
    return [("custom", tuple(items))]


def sweep(networks, budgets, strategies=("paper_opt",),
          controllers=("passive",), objective: Objective = "interconnect_words",
          exact_iters: bool | None = None, paper_convention: bool = False,
          per_layer: bool = False) -> list[dict]:
    """Evaluate networks x budgets x strategies x controllers into tidy rows.

    Each cell plans its whole network in one shot (``plan_many`` batches the
    exact conv search across layers) and yields one row — or one row per
    layer with ``per_layer=True`` (layer rows carry the ``workload`` and
    ``schedule`` objects for downstream consumers such as
    ``amc.validate_sweep``).

    The ``cost`` column re-scores the *chosen* schedules under ``objective``
    (ceil-iteration semantics); selection is governed by each strategy's own
    preset objective. ``interconnect_words`` and friends follow the sweep's
    ``exact_iters``/``paper_convention`` conventions, matching
    ``network_traffic`` bit-for-bit for the paper tables.
    """
    import dataclasses as _dc

    from repro.plan import api
    obj_fn = get_objective(objective)
    obj_name = objective if isinstance(objective, str) else getattr(
        objective, "__name__", "custom")
    if isinstance(budgets, (int, np.integer)):
        budgets = (int(budgets),)
    rows: list[dict] = []
    for net_name, workloads in _as_networks(networks):
        for budget in budgets:
            for strategy in strategies:
                strat = api.coerce_strategy(strategy)
                strat_name = strat.value if isinstance(strat, Strategy) else strat
                exact = (strat is Strategy.EXACT_OPT if exact_iters is None
                         else exact_iters)
                for controller in controllers:
                    ctrl = Controller.coerce(controller)
                    wls = tuple(
                        _dc.replace(w, groups=1)
                        if paper_convention and isinstance(w, ConvWorkload)
                        and w.groups > 1 else w
                        for w in workloads)
                    # us_per_call times the planning itself (comparable to
                    # the pre-DSE _timed() benchmark rows); the objective
                    # re-scoring below is reporting, not planning.
                    with Stopwatch() as sw:
                        plans = api.plan_many(wls, budget, strat, ctrl,
                                              exact_iters=exact)
                    us = sw.us
                    costs = [
                        float(obj_fn(p.workload,
                                     Candidates.single(p.schedule.kind,
                                                       p.schedule.bm,
                                                       p.schedule.bn,
                                                       p.schedule.bk),
                                     ctrl)[0])
                        for p in plans]
                    base = {"network": net_name, "budget": int(budget),
                            "strategy": strat_name, "controller": ctrl.value,
                            "objective": obj_name, "us_per_call": us}
                    if per_layer:
                        for p, c in zip(plans, costs):
                            rows.append({
                                **base, "layer": p.workload.name,
                                "m": p.schedule.bm, "n": p.schedule.bn,
                                "bk": p.schedule.bk, "cost": c,
                                **p.traffic.as_dict(),
                                "workload": p.workload,
                                "schedule": p.schedule})
                    else:
                        totals: dict[str, float] = {}
                        for p in plans:
                            for key, val in p.traffic.as_dict().items():
                                totals[key] = totals.get(key, 0.0) + val
                        rows.append({**base, "cost": float(sum(costs)),
                                     "n_layers": len(plans), **totals})
    return rows


def certify_space(workload: Workload, budget: int | None = None, *,
                  controller="passive", space: "SearchSpace | None" = None):
    """Statically certify every candidate this module would search over:
    delegates to `repro.check.dataflow`, which traces the matching Pallas
    kernel once per grid-degeneracy class and proves the vectorized word
    counts equal the analytical model for the whole space. Returns a
    `repro.check.dataflow.SpaceCertificate` (``.ok``, per-candidate
    equal/bounded HBM tallies, diagnostics)."""
    from repro.check.dataflow import certify_conv_space, certify_matmul_space
    if isinstance(workload, ConvWorkload):
        return certify_conv_space(workload, budget, controller, space)
    return certify_matmul_space(workload, budget, controller, space)


def pareto(rows, x: str = "budget", y: str = "cost") -> list[dict]:
    """The non-dominated subset of ``rows``, minimizing both ``x`` and ``y``
    (e.g. the MAC-budget-vs-traffic frontier of the paper's central
    trade-off). Rows missing either key are ignored; output is sorted by
    ``x`` ascending."""
    pts = [r for r in rows if r.get(x) is not None and r.get(y) is not None]
    pts.sort(key=lambda r: (r[x], r[y]))
    frontier: list[dict] = []
    best_y = float("inf")
    for r in pts:
        if r[y] < best_y:
            frontier.append(r)
            best_y = r[y]
    return frontier
