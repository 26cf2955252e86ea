"""Tests for the `repro.plan.dse` design-space exploration API.

Covers: property tests pinning the vectorized evaluators and searches to
the frozen seed model (``_seed_model``) bit-for-bit (randomized workloads,
groups, controllers), the batched network search vs per-layer plans, custom
Objective/Strategy registration driving ``plan()``/``sweep()`` end-to-end,
sweep/pareto semantics, the AMC cross-validation of sweep rows, and the
dtype-threaded VMEM footprints.
"""

import dataclasses

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:   # optional dep: fall back to the vendored stub
    from _hypothesis_stub import given, settings, st

import _seed_model as seed
from repro import plan
from repro.core import amc
from repro.core.cnn_zoo import get_cnn
from repro.plan import conv_model, dse, gemm_model, objectives
from repro.plan.schedule import Controller, Schedule, Strategy
from repro.plan.space import Candidates


def _wl(mg=64, ng=128, g=1, k=3, wi=28, wo=28):
    return plan.ConvWorkload(name="t", cin=g * mg, cout=g * ng, k=k,
                             wi=wi, hi=wi, wo=wo, ho=wo, groups=g)


conv_wl_st = st.builds(
    _wl,
    mg=st.integers(1, 96), ng=st.integers(1, 96),
    g=st.sampled_from([1, 2, 4]),
    k=st.sampled_from([1, 3, 5, 7]),
    wi=st.integers(4, 64), wo=st.integers(4, 64))

P_ST = st.sampled_from([64, 512, 2048, 16384])
CTRL_ST = st.sampled_from(list(Controller))


# ------------------------------------------------- grid == scalar, bit-for-bit
@settings(max_examples=100, deadline=None)
@given(wl=conv_wl_st, p=P_ST, ctrl=CTRL_ST, exact=st.booleans())
def test_property_conv_grid_matches_scalar(wl, p, ctrl, exact):
    """`conv_terms` over the exact space == the seed's scalar eqs (2)/(3) on
    every candidate, and == its own one-schedule evaluation, exact float
    equality (both controllers, both iteration conventions, grouped convs
    included)."""
    m, n = conv_model.conv_exact_candidates(wl, p)
    t = conv_model.conv_terms(wl, m, n, ctrl, exact_iters=exact)
    for i in range(len(m)):
        si, so = seed.layer_bandwidth(wl, seed.SeedPartition(int(m[i]),
                                                             int(n[i])),
                                      ctrl.value, exact_iters=exact)
        one = conv_model.conv_terms(wl, int(m[i]), int(n[i]), ctrl,
                                    exact_iters=exact)
        assert t.b_i[i] == si == one.b_i and t.b_o[i] == so == one.b_o, (
            wl, int(m[i]), int(n[i]))


@settings(max_examples=100, deadline=None)
@given(wl=conv_wl_st, p=P_ST, ctrl=CTRL_ST)
def test_property_vectorized_exact_matches_scalar_loop(wl, p, ctrl):
    """The masked-argmin exact search picks the same (m, n) as the frozen
    per-candidate scalar loop — including its first-minimum tie-break."""
    sched = plan.plan(wl, p, "exact_opt", ctrl).schedule
    want = seed.partition_layer(wl, p, "exact_opt", ctrl.value)
    assert (sched.m, sched.n) == (want.m, want.n)


@settings(max_examples=50, deadline=None)
@given(m=st.integers(1, 6000), n=st.integers(1, 6000), k=st.integers(1, 6000),
       ctrl=CTRL_ST)
def test_property_gemm_vectorized_matches_scalar_loop(m, n, k, ctrl):
    """Vectorized aligned-block search == frozen triple loop, and the traffic
    grid matches the seed's scalar model on every candidate."""
    got = gemm_model.plan_matmul_blocks(m, n, k, controller=ctrl)
    want = seed.plan_matmul_blocks(m, n, k, controller=ctrl.value)
    assert (got.bm, got.bn, got.bk) == (want.bm, want.bn, want.bk)
    bm, bn, bk = gemm_model.aligned_block_candidates(m, n, k)
    wl = plan.MatmulWorkload(m=m, n=n, k=k)
    total = gemm_model.matmul_terms(wl, bm, bn, bk, ctrl).total
    for i in range(0, len(bm), max(1, len(bm) // 7)):   # spot-check the grid
        blocks = seed.SeedBlocks(int(bm[i]), int(bn[i]), int(bk[i]))
        assert total[i] == seed.matmul_traffic(m, n, k, blocks, ctrl.value)


@settings(max_examples=20, deadline=None)
@given(p=P_ST, ctrl=CTRL_ST)
def test_property_batch_matches_per_layer(p, ctrl):
    """One segmented argmin over a whole network == per-layer searches."""
    wls = plan.conv_workloads("squeezenet")
    batch = conv_model.conv_exact_search_batch(wls, p, ctrl)
    for wl, mn in zip(wls, batch):
        want = seed.partition_layer(wl, p, "exact_opt", ctrl.value)
        assert mn == (want.m, want.n)


def test_plan_many_batches_exact_conv():
    """plan_many's batched exact path returns the same plans as plan()."""
    plans = plan.plan_many("resnet18", 2048, "exact_opt", "active")
    for p in plans:
        single = plan.plan(p.workload, 2048, "exact_opt", "active")
        assert p.schedule == single.schedule
        assert p.traffic == single.traffic


# ------------------------------------------------------- spaces & constraints
def test_conv_grid_space_with_mac_budget_matches_exact():
    """The full (m, n) rectangle + MacBudget finds a schedule at least as
    good as the greedy-n exact space (the greedy n is optimal, so equal)."""
    wl = plan.ConvWorkload.from_layer(get_cnn("resnet18")[1])
    exact = dse.search(wl, 2048, space=dse.ConvExactSpace(),
                       constraints=(dse.MacBudget(),))
    grid = dse.search(wl, 2048, space=dse.ConvGridSpace(),
                      constraints=(dse.MacBudget(), dse.GroupDivisible()))
    assert grid.cost <= exact.cost
    assert grid.n_feasible < grid.n_candidates  # budget actually masks
    sched = grid.schedule
    assert wl.k ** 2 * sched.m * sched.n <= 2048


def test_vmem_budget_constraint_uses_workload_dtypes():
    wl8 = plan.MatmulWorkload(m=4096, n=4096, k=4096, in_bytes=1, acc_bytes=4)
    wl32 = plan.MatmulWorkload(m=4096, n=4096, k=4096, in_bytes=4, acc_bytes=4)
    budget = 2 << 20
    space = dse.AlignedBlockSpace()
    cands = space(wl8, budget)
    feas8 = dse.VmemBudget()(wl8, cands, budget).sum()
    feas32 = dse.VmemBudget()(wl32, cands, budget).sum()
    assert feas8 > feas32  # narrower dtypes fit more candidates


def test_lane_aligned_constraint():
    cands = Candidates(kind="matmul",
                       bm=np.array([128, 130]), bn=np.array([128, 128]),
                       bk=np.array([128, 128]))
    mask = dse.LaneAligned()(plan.MatmulWorkload(m=256, n=256, k=256),
                             cands, 0)
    assert mask.tolist() == [True, False]


# --------------------------------------- custom objectives drive plan()/sweep
def test_custom_objective_drives_plan_and_sweep_end_to_end():
    """A user-registered Objective + Strategy preset flows through plan(),
    the plan cache, and dse.sweep() without touching repro.plan internals."""
    obj_name = "_test_input_words_only"
    strat_name = "_test_min_input_words"

    @plan.register_objective(obj_name)
    def input_only(wl, cands, controller):
        return conv_model.conv_terms(wl, cands.bm, cands.bn, controller).b_i

    try:
        dse.register_strategy(strat_name, conv=dse.StrategySpec(
            space=dse.ConvExactSpace(),
            constraints=(dse.MacBudget(),),
            objective=obj_name))
        wl = plan.ConvWorkload.from_layer(get_cnn("alexnet")[1])
        p = plan.plan(wl, 2048, strat_name, "passive")
        # minimizing B_i alone maximizes n: no exact-space candidate has
        # strictly lower input traffic than the chosen schedule
        m, n = conv_model.conv_exact_candidates(wl, 2048)
        b_i = conv_model.conv_terms(wl, m, n, Controller.PASSIVE).b_i
        chosen_b_i = conv_model.conv_terms(
            wl, p.schedule.m, p.schedule.n, Controller.PASSIVE).b_i
        assert chosen_b_i == b_i.min()
        # plan() accepts and caches the custom strategy name
        assert plan.plan(wl, 2048, strat_name, "passive") is p
        # and sweep() both selects and scores with it
        rows = dse.sweep([wl], (2048,), (strat_name,), ("passive",),
                         objective=obj_name)
        assert rows[0]["strategy"] == strat_name
        assert rows[0]["cost"] == b_i.min()
    finally:
        dse.unregister_strategy(strat_name)
        plan.OBJECTIVES.pop(obj_name, None)
    with pytest.raises(ValueError, match="unknown strategy"):
        plan.plan(wl, 2048, strat_name, "passive")


def test_builtin_objectives_registered_and_finite():
    wl = plan.ConvWorkload.from_layer(get_cnn("resnet18")[1])
    gemm = plan.MatmulWorkload(m=1024, n=1024, k=1024)
    cands_c = dse.ConvExactSpace()(wl, 2048)
    cands_m = dse.AlignedBlockSpace()(gemm, plan.DEFAULT_VMEM_BUDGET)
    for name in ("interconnect_words", "sram_accesses", "energy_bytes",
                 "roofline_latency"):
        fn = plan.get_objective(name)
        for w, c in ((wl, cands_c), (gemm, cands_m)):
            cost = fn(w, c, Controller.PASSIVE)
            assert cost.shape == (len(c),)
            assert np.all(np.isfinite(cost)) and np.all(cost > 0)


def test_objective_consistency_with_traffic_report():
    """The interconnect/SRAM objectives agree with TrafficReport on the
    chosen schedule (same formulas, vectorized)."""
    wl = plan.ConvWorkload.from_layer(get_cnn("resnet18")[6])
    for ctrl in Controller:
        p = plan.plan(wl, 2048, "exact_opt", ctrl)
        c = Candidates.single("conv", p.schedule.m, p.schedule.n)
        r = p.traffic
        assert plan.get_objective("interconnect_words")(wl, c, ctrl)[0] \
            == r.interconnect_words
        assert plan.get_objective("sram_accesses")(wl, c, ctrl)[0] \
            == r.sram_reads + r.sram_writes


# ------------------------------------------------------------- sweep & pareto
def test_sweep_matches_network_traffic():
    rows = dse.sweep(["alexnet"], (512, 2048), ("paper_opt",),
                     ("passive", "active"), paper_convention=True)
    assert len(rows) == 4
    for r in rows:
        want = plan.network_traffic("alexnet", r["budget"], "paper_opt",
                                    r["controller"], paper_convention=True)
        assert r["interconnect_words"] == want


def test_sweep_per_layer_rows_and_amc_validation():
    wls = [w for w in plan.conv_workloads("resnet18") if w.groups == 1][:3]
    rows = dse.sweep(wls, (512,), ("exact_opt",), ("passive", "active"),
                     per_layer=True)
    assert len(rows) == 2 * len(wls)
    for r in rows:
        assert r["schedule"].m == r["m"] and r["schedule"].n == r["n"]
    # the instrumented AMC meter agrees with every swept schedule exactly
    assert amc.validate_sweep(rows) == len(rows)


def test_pareto_frontier_budget_vs_traffic():
    rows = dse.sweep(["alexnet"], (256, 512, 1024, 2048, 4096), ("exact_opt",),
                     ("active",))
    frontier = dse.pareto(rows, x="budget", y="interconnect_words")
    assert frontier  # non-empty, sorted by budget, strictly improving traffic
    budgets = [r["budget"] for r in frontier]
    traffics = [r["interconnect_words"] for r in frontier]
    assert budgets == sorted(budgets)
    assert all(a > b for a, b in zip(traffics, traffics[1:]))
    # every dropped row is dominated by some frontier row
    for r in rows:
        if r not in frontier:
            assert any(f["budget"] <= r["budget"]
                       and f["interconnect_words"] <= r["interconnect_words"]
                       for f in frontier)


# --------------------------------------------------- dtype-threaded VMEM bytes
def test_vmem_bytes_threads_workload_dtypes():
    wl8 = plan.MatmulWorkload(m=4096, n=4096, k=4096, in_bytes=1, out_bytes=1,
                              acc_bytes=4)
    p = plan.plan(wl8, strategy="exhaustive_vmem", controller="active")
    s = p.schedule
    want = s.as_blocks().vmem_bytes(in_bytes=1, acc_bytes=4)
    assert p.vmem_bytes == want
    assert s.vmem_bytes(workload=wl8) == want
    # explicit arguments still win over the workload's dtypes
    assert s.vmem_bytes(2, 4, workload=wl8) == s.as_blocks().vmem_bytes(2, 4)
    # legacy default (bf16 operands) is unchanged and differs for int8
    assert s.vmem_bytes() == s.as_blocks().vmem_bytes(2, 4) != want
    # the planner itself searched under the int8 footprint
    assert want <= plan.DEFAULT_VMEM_BUDGET


def test_plan_vmem_bytes_fp32():
    wl32 = plan.MatmulWorkload(m=2048, n=2048, k=2048, in_bytes=4, acc_bytes=4)
    budget = 4 << 20
    p = plan.plan(wl32, budget, "exhaustive_vmem", "active")
    assert p.vmem_bytes <= budget          # fp32-aware search respects budget
    wl16 = dataclasses.replace(wl32, in_bytes=2)
    p16 = plan.plan(wl16, budget, "exhaustive_vmem", "active")
    assert p16.vmem_bytes <= budget


def test_exact_opt_parity_below_one_mac_column():
    """P < K^2 (eq 1 unsatisfiable): the preset degrades to (1, 1) exactly as
    the seed loop's initial best did — plan(), plan_many() and the frozen
    scalar oracle all agree."""
    wl = _wl(mg=16, ng=16, k=5, wi=8, wo=8)
    assert seed.partition_layer(wl, 16, "exact_opt") == seed.SeedPartition(1, 1)
    p = plan.plan(wl, 16, "exact_opt", "passive")
    assert (p.schedule.m, p.schedule.n) == (1, 1)
    [pm] = plan.plan_many([wl], 16, "exact_opt", "passive")
    assert pm.schedule == p.schedule


def test_register_strategy_duplicate_name_does_not_shadow_builtin():
    wl = plan.ConvWorkload.from_layer(get_cnn("resnet18")[1])
    before = plan.plan(wl, 2048, "exact_opt", "passive").schedule
    with pytest.raises(ValueError, match="already registered"):
        dse.register_strategy("exact_opt", conv=dse.StrategySpec(
            space=dse.ClosedFormSpace("conv", lambda w, b: (1, 1, 0))))
    assert plan.plan(wl, 2048, "exact_opt", "passive").schedule == before


def test_reregistering_strategy_does_not_serve_stale_cached_plans():
    wl = plan.ConvWorkload.from_layer(get_cnn("alexnet")[1])
    name = "_test_reregister"
    try:
        dse.register_strategy(name, conv=dse.StrategySpec(
            space=dse.ClosedFormSpace("conv", lambda w, b: (2, 2, 0))))
        assert plan.plan(wl, 2048, name).schedule.m == 2
        dse.unregister_strategy(name)
        dse.register_strategy(name, conv=dse.StrategySpec(
            space=dse.ClosedFormSpace("conv", lambda w, b: (4, 4, 0))))
        assert plan.plan(wl, 2048, name).schedule.m == 4
    finally:
        dse.unregister_strategy(name)


def test_unregister_strategy_refuses_builtins():
    with pytest.raises(ValueError, match="built-in"):
        dse.unregister_strategy("exact_opt")
    assert "exact_opt" in plan.PLANNERS   # untouched


def test_plan_vmem_bytes_rejects_conv_plans():
    p = plan.plan(plan.ConvWorkload.from_layer(get_cnn("alexnet")[1]), 2048)
    with pytest.raises(TypeError, match="matmul plans only"):
        p.vmem_bytes


# ------------------------------------------------------------- misc invariants
def test_search_result_metadata():
    wl = plan.MatmulWorkload(m=4096, n=4096, k=4096)
    res = dse.search(wl, plan.DEFAULT_VMEM_BUDGET,
                     space=dse.AlignedBlockSpace(),
                     constraints=(dse.VmemBudget(),), controller="active")
    assert 0 < res.n_feasible <= res.n_candidates
    assert res.cost == plan.traffic_report(wl, res.schedule).interconnect_words


def test_search_fallback_when_infeasible():
    wl = plan.MatmulWorkload(m=4096, n=4096, k=4096)
    res = dse.search(wl, 1024, space=dse.AlignedBlockSpace(),   # tiny budget
                     constraints=(dse.VmemBudget(),), controller="active")
    assert res.n_feasible == 0
    assert (res.schedule.bm, res.schedule.bn, res.schedule.bk) == (128, 128, 128)


def test_strategy_specs_cover_all_builtins():
    for s in Strategy:
        spec = dse.strategy_spec(s, "conv")
        assert isinstance(spec, dse.StrategySpec)
    for s in (Strategy.EXACT_OPT, Strategy.EXHAUSTIVE_VMEM,
              Strategy.FIRST_ORDER, Strategy.PAPER_OPT, Strategy.EQUAL):
        assert isinstance(dse.strategy_spec(s, "matmul"), dse.StrategySpec)
    with pytest.raises(ValueError, match="not applicable"):
        dse.strategy_spec(Strategy.MAX_INPUT, "matmul")
