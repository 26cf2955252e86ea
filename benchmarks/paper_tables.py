"""Benchmarks reproducing every table/figure of the paper, driven by the
``repro.plan.dse`` sweep API (one tidy-row sweep per table instead of a
hand-rolled enumeration per section).

Each function returns rows and prints ``name,us_per_call,derived`` CSV lines
(us_per_call = wall time of computing the table entry; derived = the value).
"""

from __future__ import annotations

import time

from repro import plan
from repro.core.cnn_zoo import PAPER_CNNS, PAPER_TABLE3
from repro.plan import dse
from repro.plan.schedule import Controller

P_TABLE1 = (512, 2048, 16384)
P_TABLE2 = (512, 1024, 2048, 4096, 8192, 16384)
STRATEGIES = ("max_input", "max_output", "equal", "paper_opt")

# Published values for validation deltas (Table I, paper_opt column).
PAPER_T1_OPT = {
    "alexnet": {512: 25.1, 2048: 12.6, 16384: 4.3},
    "vgg16": {512: 442.5, 2048: 237.2, 16384: 83.5},
    "squeezenet": {512: 52.0, 2048: 26.2, 16384: 11.1},
    "googlenet": {512: 93.5, 2048: 47.7, 16384: 17.5},
    "resnet18": {512: 88.9, 2048: 46.8, 16384: 16.0},
    "resnet50": {512: 952.6, 2048: 479.5, 16384: 168.5},
    "mobilenet": {512: 68.3, 2048: 35.0, 16384: 16.1},
    "mnasnet": {512: 373.4, 2048: 183.0, 16384: 66.0},
}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def table1() -> list[str]:
    """Table I: BW (M activations) per partition strategy x P x CNN."""
    sweep = dse.sweep(PAPER_CNNS, P_TABLE1, STRATEGIES, ("passive",),
                      paper_convention=True)
    return [f"table1/{r['network']}/P{r['budget']}/{r['strategy']}"
            f",{r['us_per_call']:.0f},{r['interconnect_words'] / 1e6:.2f}"
            for r in sweep]


def table2() -> list[str]:
    """Table II: passive vs active controller x P x CNN (paper_opt part.)."""
    sweep = dse.sweep(PAPER_CNNS, P_TABLE2, ("paper_opt",),
                      ("passive", "active"), paper_convention=True)
    return [f"table2/{r['network']}/P{r['budget']}/{r['controller']}"
            f",{r['us_per_call']:.0f},{r['interconnect_words'] / 1e6:.2f}"
            for r in sweep]


def table3() -> list[str]:
    """Table III: minimum BW (unlimited MACs), with deviation vs paper."""
    rows = []
    for net in PAPER_CNNS:
        val, us = _timed(lambda: plan.min_network_traffic(net) / 1e6)
        dev = 100 * (val - PAPER_TABLE3[net]) / PAPER_TABLE3[net]
        rows.append(f"table3/{net},{us:.0f},{val:.3f}")
        rows.append(f"table3_dev_pct/{net},0,{dev:.1f}")
    return rows


def fig2() -> list[str]:
    """Fig. 2: % bandwidth saving of the active controller."""
    sweep = dse.sweep(PAPER_CNNS, P_TABLE2, ("paper_opt",),
                      ("passive", "active"), paper_convention=True)
    by_cell = {(r["network"], r["budget"], r["controller"]): r for r in sweep}
    rows = []
    for net in PAPER_CNNS:
        for p in P_TABLE2:
            pas = by_cell[(net, p, "passive")]
            act = by_cell[(net, p, "active")]
            saving = 100.0 * (1 - act["interconnect_words"]
                              / pas["interconnect_words"])
            us = pas["us_per_call"] + act["us_per_call"]
            rows.append(f"fig2/{net}/P{p},{us:.0f},{saving:.1f}")
    return rows


def beyond_exact_search() -> list[str]:
    """Beyond-paper: integer-exact partition search + groups-aware model +
    active-aware re-optimization (factor 2 in eq 7 drops when reads are
    free)."""
    paper = dse.sweep(PAPER_CNNS, P_TABLE1, ("paper_opt",), ("passive",),
                      exact_iters=True)
    exact = dse.sweep(PAPER_CNNS, P_TABLE1, ("exact_opt",), ("passive",))
    rows = []
    for rp, re_ in zip(paper, exact):
        gain = 100 * (1 - re_["interconnect_words"] / rp["interconnect_words"])
        us = rp["us_per_call"] + re_["us_per_call"]
        rows.append(f"beyond/exact_vs_eq7/{rp['network']}/P{rp['budget']}"
                    f",{us:.0f},{gain:.2f}")
    return rows


def netplan_savings(smoke: bool = False) -> list[str]:
    """Network-graph planning: independent-layer (``no_fusion``) totals vs
    the fused-residency graph planner, per zoo CNN — the inter-layer savings
    the per-layer model cannot see. derived = M words for the total rows,
    percent for ``saving_pct``, a count for ``resident_edges``. The rows are
    committed as ``BENCH_netplan.json`` (``run.py netplan --json``)."""
    from repro.plan import netplan

    nets = ("alexnet", "squeezenet", "resnet18") if smoke else PAPER_CNNS
    rows = []
    for net in nets:
        (p, us) = _timed(lambda: netplan.plan_graph(
            net, 2048, "exact_opt", "passive",
            residency_bytes=netplan.DEFAULT_RESIDENCY_BYTES))
        rows.append(f"netplan/{net}/no_fusion,{us:.0f}"
                    f",{p.baseline_words / 1e6:.2f}")
        rows.append(f"netplan/{net}/fused,{us:.0f}"
                    f",{p.total_words / 1e6:.2f}")
        rows.append(f"netplan/{net}/saving_pct,0,{p.saving_pct:.1f}")
        rows.append(f"netplan/{net}/resident_edges,0"
                    f",{sum(1 for e in p.edges if e.resident)}")
    return rows


def sim_bandwidth(smoke: bool = False) -> list[str]:
    """Cycle-approximate simulation (`repro.sim`): latency, average/peak
    interconnect bandwidth, and energy per zoo CNN under both controllers
    (exact_opt partitions at P = 2048), plus the active-controller saving
    and the paper's headline comparison — optimal partitioning + active
    controller vs. the equal-partition passive baseline (up to ~40%+).
    derived = ms / GB/s / M words / uJ / percent per the row name. The rows
    are committed as ``BENCH_sim.json`` (``run.py sim --json``)."""
    from repro.plan import netplan

    nets = ("alexnet", "squeezenet", "resnet18") if smoke else PAPER_CNNS
    rows = []
    for net in nets:
        reps = {}
        for ctrl in ("passive", "active"):
            (rep, us) = _timed(lambda: netplan.plan_graph(
                net, 2048, "exact_opt", ctrl, residency_bytes=0).simulate())
            reps[ctrl] = rep
            rows.append(f"sim/{net}/{ctrl}/latency_ms,{us:.0f}"
                        f",{rep.latency_s * 1e3:.3f}")
            rows.append(f"sim/{net}/{ctrl}/avg_bw_gbs,0"
                        f",{rep.avg_bw_bytes_s / 1e9:.2f}")
            rows.append(f"sim/{net}/{ctrl}/peak_bw_gbs,0"
                        f",{rep.peak_bw_bytes_s / 1e9:.2f}")
            rows.append(f"sim/{net}/{ctrl}/bus_mwords,0"
                        f",{rep.interconnect_words / 1e6:.2f}")
            rows.append(f"sim/{net}/{ctrl}/energy_uj,0"
                        f",{rep.energy_pj / 1e6:.2f}")
        pas, act = reps["passive"], reps["active"]
        rows.append(f"sim/{net}/active_words_saving_pct,0,"
                    f"{100 * (1 - act.interconnect_words / pas.interconnect_words):.1f}")
        rows.append(f"sim/{net}/active_latency_saving_pct,0,"
                    f"{100 * (1 - act.latency_s / pas.latency_s):.1f}")
        # The paper's headline: optimal partitioning AND the active
        # controller vs. an unoptimized (equal-partition) passive design.
        (base, us) = _timed(lambda: netplan.plan_graph(
            net, 2048, "equal", "passive", residency_bytes=0).simulate())
        rows.append(f"sim/{net}/combined_words_saving_pct,{us:.0f},"
                    f"{100 * (1 - act.interconnect_words / base.interconnect_words):.1f}")
        rows.append(f"sim/{net}/combined_latency_saving_pct,0,"
                    f"{100 * (1 - act.latency_s / base.latency_s):.1f}")
    rows.extend(sim_speedup())
    return rows


def sim_speedup(repeats: int = 3) -> list[str]:
    """Grid-rate sim-objective speedup: the frozen per-candidate
    ``simulate()`` loop (``sim.scalar_sim_objective``) vs the batched
    evaluator (``sim.sim_latency`` over ``simulate_batch``), evaluating
    ``sim_latency`` over the full `ConvExactSpace` of every ResNet-18 layer
    at P = 2048. The batched costs are asserted exactly equal to the scalar
    loop's before timing is reported. derived = candidate count for the
    scalar/batch rows, speedup factor for the ``sim_speedup`` row (committed
    as the ``dse/sim_speedup/...`` rows of ``BENCH_sim.json``)."""
    import numpy as np

    from repro import sim

    wls = plan.conv_workloads("resnet18")
    grids = [(w, dse.ConvExactSpace()(w, 2048)) for w in wls]
    scalar = sim.scalar_sim_objective("latency_s")
    ctrl = Controller.ACTIVE

    def run_scalar():
        return [scalar(w, g, ctrl) for w, g in grids]

    def run_batch():
        return [np.asarray(sim.sim_latency(w, g, ctrl)) for w, g in grids]

    for (w, _), a, b in zip(grids, run_scalar(), run_batch()):
        assert np.array_equal(a, b), \
            f"batched sim objective diverged from scalar on {w.name}"
    t_scalar = min(_timed(run_scalar)[1] for _ in range(repeats))
    t_batch = min(_timed(run_batch)[1] for _ in range(repeats))
    n_cand = sum(len(g) for _, g in grids)
    return [
        f"dse/sim_scalar/resnet18/P2048,{t_scalar:.0f},{n_cand}",
        f"dse/sim_batch/resnet18/P2048,{t_batch:.0f},{n_cand}",
        f"dse/sim_speedup/resnet18/P2048,{t_batch:.0f},"
        f"{t_scalar / t_batch:.1f}",
    ]


def simplan_latency(smoke: bool = False) -> list[str]:
    """Sim-objective network planning: ``plan_graph(..., objective=
    "sim_latency")`` on every zoo CNN (all 8 in smoke mode too — the beam
    scores with grid-rate batched evaluations, so the full set stays cheap).
    ``no_fusion_ms`` simulates the per-layer sim-optimal baseline plans;
    ``fused_ms`` the jointly planned fused-residency schedule. derived = ms /
    percent / a count per the row name; committed as ``BENCH_simplan.json``
    (``run.py simplan --json``)."""
    del smoke  # the full zoo is the smoke set: planning is grid-rate
    from repro import sim
    from repro.plan import netplan

    rows = []
    for net in PAPER_CNNS:
        (p, us) = _timed(lambda: netplan.plan_graph(
            net, 2048, "exact_opt", "active", objective="sim_latency"))
        fused = p.simulate()
        base = sum(sim.simulate(pl.workload, pl.schedule).latency_s
                   for pl in p.baseline)
        rows.append(f"simplan/{net}/no_fusion_ms,0,{base * 1e3:.3f}")
        rows.append(f"simplan/{net}/fused_ms,{us:.0f}"
                    f",{fused.latency_s * 1e3:.3f}")
        rows.append(f"simplan/{net}/latency_saving_pct,0"
                    f",{100 * (1 - fused.latency_s / base):.1f}")
        rows.append(f"simplan/{net}/resident_edges,0"
                    f",{sum(1 for e in p.edges if e.resident)}")
    return rows


def planserve_rows(smoke: bool = False) -> list[str]:
    """Planner-as-a-service load report (`repro.launch.planserve`): plans/sec
    and p50/p99 latency for a seeded Poisson stream over the zoo x strategies
    x controllers catalog, plus the headline batched-vs-sequential speedup —
    a repeated zoo request stream served by batched ``plan_graphs`` micro-
    batches (persistent context + graph-level plan LRU) vs a loop of the
    frozen pre-fleet ``plan_graph_loop`` planner, which rebuilds every graph,
    grid, and baseline per call. derived = plans/s, ms, a ratio, M words, or
    a must-be-zero count per the row name; committed as
    ``BENCH_planserve.json`` (``run.py planserve --json``). The wall-clock
    rows are guarded by a floor (throughput/speedup) or ceiling (latency);
    ``fleet_mwords`` and the mismatch/diagnostic counts are exact."""
    import repro.check as rc
    from repro.launch import planserve
    from repro.plan import clear_plan_graph_cache, plan_graphs

    scope = "zoo2" if smoke else "zoo"
    load, _ = _timed(lambda: planserve.run_load(smoke=smoke))
    sp, us = _timed(lambda: planserve.run_speedup(smoke=smoke))
    rows = [
        f"planserve/{scope}/plans_per_s,0,{load['plans_per_s']:.0f}",
        f"planserve/{scope}/p50_ms,0,{load['p50_ms']:.2f}",
        f"planserve/{scope}/p99_ms,0,{load['p99_ms']:.2f}",
        f"planserve/{scope}/speedup_batched_vs_sequential,{us:.0f}"
        f",{sp['batched_vs_sequential']:.1f}",
        f"planserve/{scope}/word_mismatches,0,{sp['word_mismatches']}",
        f"planserve/{scope}/fleet_mwords,0,{sp['fleet_total_mwords']:.2f}",
    ]
    # Acceptance: fleet outputs verify clean through `repro.check`.
    nets = list(PAPER_CNNS)[:2] if smoke else PAPER_CNNS
    clear_plan_graph_cache()
    (plans, us) = _timed(lambda: plan_graphs(nets, 2048, "exact_opt",
                                             "passive"))
    diags = rc.check(list(plans))
    rows.append(f"planserve/{scope}/fleet_check_diags,{us:.0f},{len(diags)}")
    return rows


def obs_rows(smoke: bool = False) -> list[str]:
    """Observability (`repro.obs`) cost + exactness rows.

    * ``disabled_overhead`` — the tracer-off ceiling on the planserve smoke
      stream: 1 + (per-``span()`` disabled dispatch cost x spans the stream
      would record) / stream busy seconds. Computed from a microbenchmark of
      the no-op path (noise-immune, ~1.0000x) and guarded by the hard <= 1.05
      ``overhead`` class in ``run.py check`` — the acceptance bound that
      leaving spans in hot paths costs <= 5%.
    * ``enabled_overhead`` — measured busy-time ratio of the same stream with
      a recording tracer vs without (wall-clock: ceiling-guarded only).
    * ``export_wall_ms`` — resnet18/active virtual-time trace export+verify
      wall time (ceiling-guarded).
    * ``trace_events`` — virtual-time export event count (exact; the
      *span* count of the wall-clock stream is batching- and hence
      machine-dependent, so it informs the overhead model but is not a row).
    * ``word_pin_mismatches`` — zoo x controller traces whose per-track
      cycles or counter words fail the word-for-word pin (must be 0).
    * ``metric_families`` — distinct metric names in the registry after the
      stream (exact).

    Committed as ``BENCH_obs.json`` (``run.py obs --json``)."""
    from repro import obs
    from repro.launch import planserve
    from repro.plan import clear_plan_graph_cache
    from repro.plan.netplan import plan_graph

    scope = "zoo2" if smoke else "zoo"
    # Warm every cache once so the tracer-off / tracer-on streams compare
    # identical planning work.
    planserve.run_load(smoke=True)

    rep_off, _ = _timed(lambda: planserve.run_load(smoke=True))
    busy_off = rep_off["requests"] / rep_off["busy_plans_per_s"]
    with obs.tracing() as tr:
        rep_on, _ = _timed(lambda: planserve.run_load(smoke=True))
    busy_on = rep_on["requests"] / rep_on["busy_plans_per_s"]
    n_spans = len(tr)

    # The disabled fast path, microbenchmarked: one module-global read plus
    # the shared no-op context manager.
    n_calls = 200_000
    with obs.Stopwatch() as sw:
        for _ in range(n_calls):
            with obs.span("bench"):
                pass
    span_cost_s = sw.s / n_calls
    disabled_overhead = 1.0 + span_cost_s * n_spans / busy_off
    enabled_overhead = busy_on / busy_off

    # Virtual-time export: wall time + the word-for-word pins over the zoo.
    nets = list(PAPER_CNNS)[:2] if smoke else list(PAPER_CNNS)
    clear_plan_graph_cache()
    report = plan_graph("resnet18", controller="active").simulate()
    (events, export_us) = _timed(
        lambda: obs.simreport_to_trace(report))
    obs.verify_sim_trace(report, events)

    mismatches = 0
    for net in nets:
        for ctrl in ("passive", "active"):
            r = plan_graph(net, controller=ctrl).simulate()
            try:
                obs.verify_sim_trace(r, obs.simreport_to_trace(r))
            except ValueError:
                mismatches += 1

    return [
        f"obs/{scope}/disabled_overhead,{span_cost_s * 1e6:.4f}"
        f",{disabled_overhead:.4f}",
        f"obs/{scope}/enabled_overhead,0,{enabled_overhead:.3f}",
        f"obs/{scope}/export_wall_ms,{export_us:.0f},{export_us / 1e3:.2f}",
        f"obs/{scope}/trace_events,0,{len(events)}",
        f"obs/{scope}/word_pin_mismatches,0,{mismatches}",
        f"obs/{scope}/metric_families,0,{len(obs.REGISTRY.families())}",
    ]


def faults_rows(smoke: bool = False) -> list[str]:
    """Fault-injection / graceful-degradation rows (`repro.faults.chaos`).

    One 50-schedule seeded chaos run over the zoo + hardened planner
    service. Every row is deterministic (seeded draws + the virtual
    service-time model), so all counts are ``exact``-guarded except the
    ``availability_*`` rows, which use the floor-ratchet ``availability``
    class in ``run.py check`` (fresh must be >= committed — the service may
    only get more available). The invariant rows (violations, word drift,
    replan mismatches, check diags) must be exactly 0.

    Committed as ``BENCH_faults.json`` (``run.py faults --json``)."""
    from repro.faults import run_chaos

    scope = "zoo2" if smoke else "zoo"
    (rep, us) = _timed(lambda: run_chaos(50, smoke=smoke))
    shed_rate = 100.0 * rep.sheds / rep.requests if rep.requests else 0.0
    return [
        f"faults/{scope}/schedules,{us:.0f},{rep.schedules}",
        f"faults/{scope}/fault_events,0,{rep.fault_events}",
        f"faults/{scope}/invariant_violations,0,{len(rep.violations)}",
        f"faults/{scope}/word_drift,0,{rep.word_drift}",
        f"faults/{scope}/replan_mismatches,0,{rep.replan_mismatches}",
        f"faults/{scope}/check_diags,0,{rep.check_diagnostics}",
        f"faults/{scope}/availability_floor_pct,0"
        f",{rep.availability_min_pct:.2f}",
        f"faults/{scope}/availability_mean_pct,0"
        f",{rep.availability_mean_pct:.2f}",
        f"faults/{scope}/degraded_p99_virtual_ms,0"
        f",{rep.degraded_p99_max_ms:.3f}",
        f"faults/{scope}/shed_rate_pct,0,{shed_rate:.3f}",
        f"faults/{scope}/retries,0,{rep.retries}",
        f"faults/{scope}/breaker_opens,0,{rep.breaker_opens}",
    ]


def dse_pareto() -> list[str]:
    """Budget-vs-traffic Pareto frontier (exact search, active controller):
    the MAC budgets that actually buy bandwidth, per CNN."""
    budgets = (256, 512, 1024, 2048, 4096, 8192, 16384)
    rows = []
    for net in PAPER_CNNS:
        sweep = dse.sweep([net], budgets, ("exact_opt",), ("active",))
        frontier = dse.pareto(sweep, x="budget", y="interconnect_words")
        for r in frontier:
            rows.append(f"pareto/{r['network']}/P{r['budget']}"
                        f",{r['us_per_call']:.0f}"
                        f",{r['interconnect_words'] / 1e6:.2f}")
    return rows


def check_plans_rows(smoke: bool = False) -> list[str]:
    """Static verification status of every zoo NetPlan (`repro.check`):
    derived = diagnostic count, which must be exactly 0 — a non-zero value is
    a planner or checker regression, caught by ``run.py check`` since the
    rows are committed as ``BENCH_check.json``. us_per_call = plan+verify
    wall-clock (not compared). The codebase lint rides along as one row."""
    import repro.check as rc

    nets = ("alexnet", "squeezenet", "resnet18") if smoke else PAPER_CNNS
    rows = []
    for net in nets:
        for ctrl in ("passive", "active"):
            diags, timings = rc.check_plans((net,), (ctrl,))
            us = timings[f"{net}/{ctrl}"] * 1e6
            rows.append(f"check/{net}/{ctrl},{us:.0f},{len(diags)}")
    (lint, us) = _timed(rc.check_codebase)
    rows.append(f"check/codebase,{us:.0f},{len(lint)}")
    return rows


def check_dataflow_rows(smoke: bool = False) -> list[str]:
    """Kernel-body dataflow certification (`repro.check.dataflow`): derived =
    certified candidate count per net (every admitted candidate of every
    launchable conv layer's exact space, both controllers) — a deterministic
    function of the zoo and the kernels, committed in ``BENCH_check.json``
    and guarded exactly by ``run.py check``. The closing row counts
    diagnostics across the whole sweep, which must be exactly 0."""
    import repro.check as rc

    nets = ("alexnet", "squeezenet", "resnet18") if smoke else PAPER_CNNS
    rows = []
    n_diags = 0
    for net in nets:
        (out, us) = _timed(lambda n=net: rc.check_dataflow((n,)))
        diags, timings = out
        n_diags += len(diags)
        rows.append(f"dataflow/{net},{us:.0f},{timings.get('_certified', 0)}")
    rows.append(f"dataflow/diagnostics,0,{n_diags}")
    return rows
