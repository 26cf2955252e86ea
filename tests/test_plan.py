"""Tests for the unified ``repro.plan`` API: typed enums, the unified
Schedule, the plan cache, planner registry, the active-controller optimum
shift (eq 7 refinement), AMC cross-validation, and kernel consumption of
Schedule objects."""

import dataclasses

import numpy as np
import pytest

from repro import plan
from repro.core import amc
from repro.core.cnn_zoo import ConvLayer, get_cnn
from repro.plan.schedule import Controller, Partition, Schedule, Strategy


# ------------------------------------------------------------- enums/schedule
def test_strategy_roundtrip():
    for s in Strategy:
        assert Strategy.coerce(s.value) is s
        assert Strategy.coerce(s) is s
    with pytest.raises(ValueError, match="unknown strategy"):
        Strategy.coerce("nope")


def test_controller_roundtrip():
    for c in Controller:
        assert Controller.coerce(c.value) is c
        assert Controller.coerce(c) is c
    with pytest.raises(ValueError, match="unknown controller"):
        Controller.coerce("semi_active")


def test_schedule_partition_roundtrip():
    part = Partition(m=8, n=28)
    sched = Schedule.from_partition(part, "active")
    assert sched.kind == "conv"
    assert (sched.m, sched.n) == (8, 28)
    assert sched.controller is Controller.ACTIVE
    assert sched.as_partition() == part
    assert sched.macs(3) == 9 * 8 * 28


def test_schedule_blocks_roundtrip():
    blocks = plan.MatmulBlocks(bm=256, bn=512, bk=128)
    sched = Schedule.from_blocks(blocks, "passive")
    assert sched.kind == "matmul"
    assert sched.as_blocks() == blocks
    assert sched.vmem_bytes() == blocks.vmem_bytes()
    with pytest.raises(ValueError):
        sched.as_partition()          # wrong-kind access is an error


def test_schedule_validation():
    with pytest.raises(ValueError):
        Schedule(kind="gemm", bm=1, bn=1)
    with pytest.raises(ValueError):
        Schedule(kind="conv", bm=0, bn=1)


# -------------------------------------------------------------------- caching
def test_plan_cache_hits():
    plan.clear_plan_cache()
    wl = plan.ConvWorkload.from_layer(get_cnn("alexnet")[1])
    p1 = plan.plan(wl, 2048, "paper_opt", "passive")
    misses = plan.plan_cache_info().misses
    p2 = plan.plan(wl, 2048, "paper_opt", "passive")
    info = plan.plan_cache_info()
    assert p2 is p1                       # cached object returned
    assert info.hits >= 1
    assert info.misses == misses          # no new miss
    # a different budget is a different key
    plan.plan(wl, 4096, "paper_opt", "passive")
    assert plan.plan_cache_info().misses == misses + 1


def test_plan_cache_distinguishes_controller():
    plan.clear_plan_cache()
    wl = plan.MatmulWorkload(m=512, n=512, k=512)
    pa = plan.plan(wl, strategy="exhaustive_vmem", controller="active")
    pp = plan.plan(wl, strategy="exhaustive_vmem", controller="passive")
    assert pa is not pp
    assert pa.schedule.controller is Controller.ACTIVE
    assert pp.schedule.controller is Controller.PASSIVE


# ------------------------------------------------------------------- registry
def test_planner_registry_contents():
    for name in ("paper_opt", "exact_opt", "first_order", "exhaustive_vmem"):
        assert name in plan.PLANNERS
        assert plan.get_planner(name) is plan.PLANNERS[name]
    with pytest.raises(KeyError, match="unknown planner"):
        plan.get_planner("simulated_annealing")


def test_register_custom_planner():
    name = "_test_fixed"
    try:
        @plan.register_planner(name)
        def fixed(workload, budget, controller):
            return Schedule(kind="conv", bm=1, bn=1, controller=controller)

        sched = plan.get_planner(name)(
            plan.ConvWorkload.from_layer(get_cnn("alexnet")[0]), 2048,
            Controller.PASSIVE)
        assert (sched.m, sched.n) == (1, 1)
        with pytest.raises(ValueError, match="already registered"):
            plan.register_planner(name)(fixed)
    finally:
        plan.PLANNERS.pop(name, None)


def test_strategy_kind_mismatch_raises():
    conv = plan.ConvWorkload.from_layer(get_cnn("alexnet")[0])
    gemm = plan.MatmulWorkload(m=256, n=256, k=256)
    with pytest.raises(ValueError, match="not applicable"):
        plan.plan(gemm, strategy="max_input")
    # conv accepts the GEMM-flavoured names via aliasing
    assert plan.plan(conv, 2048, "first_order").schedule.kind == "conv"
    assert plan.plan(conv, 2048, "exhaustive_vmem").schedule.kind == "conv"


# ------------------------------------------- eq (7) active-controller refinement
def test_exact_opt_optimum_shifts_with_controller():
    """Beyond-paper eq (7) refinement: with free read-back the factor 2 drops,
    so the active-optimal partition uses smaller m (input maps) and the
    passive-optimal schedule is strictly worse when re-evaluated active."""
    wl = plan.ConvWorkload.from_layer(get_cnn("resnet18")[1])
    strict_wins = 0
    for p_macs in (512, 2048, 8192):
        sp = plan.plan(wl, p_macs, "exact_opt", "passive").schedule
        sa = plan.plan(wl, p_macs, "exact_opt", "active").schedule
        assert sa.m < sp.m, (p_macs, sa, sp)
        # the active-aware schedule never loses under the active controller
        # (and wins strictly for at least one budget) ...
        passive_sched_active_ctrl = dataclasses.replace(
            sp, controller=Controller.ACTIVE)
        t_aware = plan.traffic_report(wl, sa).interconnect_words
        t_naive = plan.traffic_report(wl, passive_sched_active_ctrl).interconnect_words
        assert t_aware <= t_naive, p_macs
        strict_wins += t_aware < t_naive
        # ... and the continuous optima order the same way (factor sqrt(2))
        m_p = plan.optimal_m_realvalued(wl, p_macs, Controller.PASSIVE)
        m_a = plan.optimal_m_realvalued(wl, p_macs, Controller.ACTIVE)
        assert m_a == pytest.approx(m_p / np.sqrt(2.0))
    assert strict_wins >= 1


# ------------------------------------------------- AMC vs TrafficReport parity
@pytest.mark.parametrize("idx", [1, 6])          # two dense ResNet-18 layers
@pytest.mark.parametrize("controller", ["passive", "active"])
def test_amc_validates_resnet18_schedules(idx, controller):
    """The instrumented AMC simulation must meter exactly what the
    TrafficReport predicts, on real ResNet-18 layers, for planned schedules."""
    layer = get_cnn("resnet18")[idx]
    assert layer.groups == 1
    # shrink spatial dims to keep the numpy sim fast; channels stay real
    small = dataclasses.replace(layer, wi=8, hi=8, wo=8, ho=8, stride=1)
    sched = plan.plan(plan.ConvWorkload.from_layer(small), 2048,
                      "paper_opt", controller).schedule
    meter, report = amc.validate_schedule(small, sched)
    assert meter.interconnect_words == report.interconnect_words
    assert meter.sram_reads == report.sram_reads
    assert meter.sram_writes == report.sram_writes
    # the report embeds the analytical eqs (2)/(3)
    assert report.interconnect_words == report.input_words + report.output_words


def test_amc_accepts_legacy_partition_with_explicit_active():
    layer = ConvLayer(name="t", cin=8, cout=16, k=3, wi=12, hi=12, wo=12, ho=12)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 12, 12)).astype(np.float32)
    w = rng.standard_normal((16, 8, 3, 3)).astype(np.float32)
    _, meter = amc.run_partitioned_conv(layer, Partition(2, 4), x, w, active=True)
    assert meter.interconnect_words == amc.analytical_interconnect_words(
        layer, Partition(2, 4), True)
    with pytest.raises(TypeError, match="active="):
        amc.run_partitioned_conv(layer, Partition(2, 4), x, w)


# --------------------------------------------------------- workload adapters
def test_conv_workload_layer_roundtrip():
    layer = get_cnn("mobilenet")[3]
    wl = plan.ConvWorkload.from_layer(layer)
    assert wl.to_layer() == layer
    assert wl.in_acts == layer.in_acts
    assert wl.macs == layer.macs


def test_transformer_matmul_adapter():
    from repro.configs.registry import get_config
    cfg = get_config("gemma-2b")
    loads = plan.transformer_matmuls(cfg, seq_len=1024, batch=2)
    names = [w.name.split("/")[-1] for w in loads]
    assert names[:2] == ["qkv", "attn_out"]
    assert "ffn_up" in names and "lm_head" in names
    for wl in loads:
        assert wl.m == 2048 and wl.n > 0 and wl.k > 0
        p = plan.plan(wl, strategy="exhaustive_vmem", controller="active")
        assert p.schedule.vmem_bytes() <= plan.DEFAULT_VMEM_BUDGET
        assert p.traffic.interconnect_words >= wl.m * wl.k  # touch A once


def test_transformer_matmul_adapter_moe():
    from repro.configs.registry import get_config
    cfg = get_config("qwen2-moe-a2.7b")
    names = [w.name.split("/")[-1]
             for w in plan.transformer_matmuls(cfg, seq_len=512)]
    assert "expert_up" in names and "expert_down" in names


# --------------------------------------------------------------- plan_many
def test_plan_many_accepts_cnn_name():
    plans = plan.plan_many("alexnet", 2048, "paper_opt", "active")
    assert len(plans) == len(get_cnn("alexnet"))
    total = sum(p.traffic.interconnect_words for p in plans)
    assert total > 0
    for p in plans:
        assert p.schedule.controller is Controller.ACTIVE
        assert p.schedule.macs(p.workload.k) <= 2048


# ------------------------------------------------------ kernels eat Schedules
def test_psum_matmul_consumes_schedule():
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.psum_matmul import psum_matmul
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((96, 80)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((80, 72)), jnp.float32)
    for ctrl in (Controller.ACTIVE, Controller.PASSIVE):
        sched = Schedule(kind="matmul", bm=32, bn=64, bk=32, controller=ctrl)
        got = psum_matmul(x, w, schedule=sched)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref.matmul_ref(x, w)),
                                   rtol=1e-3, atol=1e-3)
    with pytest.raises(ValueError, match="matmul schedule"):
        psum_matmul(x, w, schedule=Schedule(kind="conv", bm=4, bn=4))


def test_conv2d_psum_consumes_schedule():
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.conv2d_psum import conv2d_psum
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((8, 14, 14)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8, 3, 3)), jnp.float32)
    sched = plan.plan(
        plan.ConvWorkload(name="t", cin=8, cout=16, k=3, wi=12, hi=12,
                          wo=12, ho=12), 512, "paper_opt", "active").schedule
    got = conv2d_psum(x, w, schedule=sched)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.conv2d_ref(x, w)),
                               rtol=1e-3, atol=1e-3)
    with pytest.raises(ValueError, match="conv schedule"):
        conv2d_psum(x, w, schedule=Schedule(kind="matmul", bm=8, bn=8, bk=8))


# ---------------------------------------------------------- traffic report
def test_traffic_report_breakdown_consistency():
    wl = plan.MatmulWorkload(m=1024, n=1024, k=1024)
    p = plan.plan(wl, strategy="exhaustive_vmem", controller="active")
    r = p.traffic
    assert r.interconnect_words == r.input_words + r.output_words
    assert r.total_words == r.interconnect_words
    assert r.bytes >= r.interconnect_words * min(wl.in_bytes, wl.out_bytes)
    assert set(r.as_dict()) == {"interconnect_words", "input_words",
                                "output_words", "sram_reads", "sram_writes",
                                "bytes"}


_READER_WORKLOADS = [
    plan.ConvWorkload(name="dense", cin=64, cout=128, k=3, wi=28, hi=28,
                      wo=28, ho=28),
    plan.ConvWorkload(name="depthwise", cin=96, cout=96, k=3, wi=28, hi=28,
                      wo=14, ho=14, stride=2, groups=96),
    plan.MatmulWorkload(m=2048, n=8960, k=1536, name="dense"),
    plan.MatmulWorkload(m=12288, n=1408, k=2048, name="grouped", groups=64),
]


def _one_node_graph(wl):
    """``wl`` alone, fed by one input tensor holding its whole input."""
    if isinstance(wl, plan.ConvWorkload):
        return plan.NetworkGraph.from_layers([wl])
    tensors = {"x": plan.Tensor("x", channels=wl.k, h=1, w=wl.m,
                                word_bytes=wl.in_bytes),
               "y": plan.Tensor("y", channels=wl.n, h=1, w=wl.m,
                                word_bytes=wl.out_bytes)}
    return plan.NetworkGraph(name="one", nodes=(
        plan.Node(name="input", op="input", ins=(), out="x"),
        plan.Node(name=wl.name, op="matmul", ins=("x",), out="y",
                  workload=wl)), tensors=tensors)


@pytest.mark.parametrize("controller", ["passive", "active"])
@pytest.mark.parametrize("wl", _READER_WORKLOADS,
                         ids=["conv", "conv-depthwise", "gemm",
                              "gemm-groups64"])
def test_readers_agree_with_the_evaluator(wl, controller):
    """Every reader of the traffic model reports the one evaluator's numbers
    at a planned schedule: the `TrafficReport`, the word and SRAM
    objectives, the verifier, and the network report with nothing resident
    (a grouped GEMM's partial row tiles are outside the residency model)."""
    import repro.check as rc
    from repro.plan.space import Candidates
    from repro.plan.traffic import terms
    conv = isinstance(wl, plan.ConvWorkload)
    p = plan.plan(wl, strategy="exact_opt" if conv else "exhaustive_vmem",
                  controller=controller)
    s = p.schedule
    t = terms(wl, s.bm, s.bn, s.bk, s.controller)
    r = plan.traffic_report(wl, s)
    assert r == p.traffic
    assert r.as_dict() == {
        "interconnect_words": t.total, "input_words": t.input_words,
        "output_words": t.output_words, "sram_reads": t.sram_reads,
        "sram_writes": t.sram_writes, "bytes": t.bytes}
    assert all(type(v) is float for v in r.as_dict().values())
    cands = Candidates.single(s.kind, s.bm, s.bn, s.bk)
    ctrl = s.controller
    assert plan.get_objective("interconnect_words")(wl, cands, ctrl)[0] \
        == r.interconnect_words
    assert plan.get_objective("sram_accesses")(wl, cands, ctrl)[0] \
        == r.sram_reads + r.sram_writes
    assert rc.check_traffic(wl, s, r) == []
    if conv or wl.groups == 1:
        g = _one_node_graph(wl)
        net = plan.network_report(g, {g.workload_nodes[0].name: s})
        words = [f for f in r.as_dict() if f != "bytes"]
        assert {f: getattr(net, f) for f in words} == \
            {f: getattr(r, f) for f in words}


def test_traffic_report_kind_mismatch():
    wl = plan.MatmulWorkload(m=256, n=256, k=256)
    with pytest.raises(ValueError, match="matmul workload"):
        plan.traffic_report(wl, Schedule(kind="conv", bm=4, bn=4))
