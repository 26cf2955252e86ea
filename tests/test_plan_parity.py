"""Parity: the redesigned ``repro.plan`` pipeline reproduces the seed
``bwmodel``/``partitioner`` numbers bit-for-bit.

The reference implementations are frozen verbatim copies of the seed code
(pre-``repro.plan``, in ``_seed_model``); the tests sweep the paper's Table
I/II grid (all eight CNNs x MAC budgets x strategies x controllers) and a
GEMM set, and require exact float equality against the API.
"""

import pytest

import _seed_model as seed
from repro import plan
from repro.core.cnn_zoo import PAPER_CNNS, get_cnn

# --------------------------------------------------------------------------
# Parity sweeps
# --------------------------------------------------------------------------
TABLE1_P = (512, 2048, 16384)
TABLE2_P = (512, 1024, 2048, 4096, 8192, 16384)
TABLE1_STRATEGIES = ("max_input", "max_output", "equal", "paper_opt")


@pytest.mark.parametrize("net", PAPER_CNNS)
def test_table1_bit_for_bit(net):
    """Table I totals: new pipeline == frozen seed code, exactly."""
    layers = get_cnn(net)
    for p in TABLE1_P:
        for strat in TABLE1_STRATEGIES:
            want = seed.network_bandwidth(layers, p, strat,
                                          paper_convention=True)
            new = plan.network_traffic(net, p, strat, paper_convention=True)
            assert new == want, (net, p, strat)


@pytest.mark.parametrize("net", PAPER_CNNS)
def test_table2_bit_for_bit(net):
    """Table II totals (passive vs active controller): exact parity."""
    layers = get_cnn(net)
    for p in TABLE2_P:
        for ctrl in ("passive", "active"):
            want = seed.network_bandwidth(layers, p, "paper_opt", ctrl,
                                          paper_convention=True)
            new = plan.network_traffic(net, p, "paper_opt", ctrl,
                                       paper_convention=True)
            assert new == want, (net, p, ctrl)


@pytest.mark.parametrize("net", ("resnet18", "mobilenet", "mnasnet"))
@pytest.mark.parametrize("p", TABLE1_P)
def test_exact_opt_and_groups_aware_parity(net, p):
    """The beyond-paper paths (exact search, groups-aware model) also agree."""
    layers = get_cnn(net)
    for strat in ("exact_opt", "paper_opt"):
        for ctrl in ("passive", "active"):
            want = seed.network_bandwidth(layers, p, strat, ctrl)
            new = plan.network_traffic(net, p, strat, ctrl)
            assert new == want, (net, p, strat, ctrl)


@pytest.mark.parametrize("net", PAPER_CNNS)
@pytest.mark.parametrize("p", TABLE1_P)
def test_per_layer_schedule_parity(net, p):
    """Chosen (m, n) matches the seed partitioner layer-by-layer."""
    for layer in get_cnn(net):
        want = seed.partition_layer(layer, p, "paper_opt")
        sched = plan.plan(plan.ConvWorkload.from_layer(layer), p,
                          "paper_opt", "passive").schedule
        assert (sched.m, sched.n) == (want.m, want.n), (net, layer.name, p)


GEMMS = [(4096, 4096, 4096), (8192, 28672, 8192), (512, 512, 512),
         (1048576, 2048, 1536), (128, 128, 128)]


@pytest.mark.parametrize("m,n,k", GEMMS)
@pytest.mark.parametrize("ctrl", ("active", "passive"))
def test_gemm_blocks_bit_for_bit(m, n, k, ctrl):
    """VMEM block planning: new pipeline == frozen seed search, exactly."""
    want = seed.plan_matmul_blocks(m, n, k, controller=ctrl)
    p = plan.plan(plan.MatmulWorkload(m=m, n=n, k=k),
                  strategy="exhaustive_vmem", controller=ctrl)
    s = p.schedule
    assert (s.bm, s.bn, s.bk) == (want.bm, want.bn, want.bk)
    assert p.traffic.interconnect_words == seed.matmul_traffic(m, n, k, want,
                                                               ctrl)
