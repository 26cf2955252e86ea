"""Parity: the redesigned ``repro.plan`` pipeline reproduces the seed
``bwmodel``/``partitioner`` numbers bit-for-bit.

The reference implementations below are frozen verbatim copies of the seed
code (pre-``repro.plan``); the tests sweep the paper's Table I/II grid (all
eight CNNs x MAC budgets x strategies x controllers) and a GEMM set, and
require exact float equality against both the new API and the legacy shims.
"""

import dataclasses
import math

import pytest

from repro import plan
from repro.core import bwmodel
from repro.core.cnn_zoo import PAPER_CNNS, get_cnn

# --------------------------------------------------------------------------
# Frozen seed reference: conv model (verbatim from the seed bwmodel.py)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _SeedPartition:
    m: int
    n: int


def _factors(x):
    fs = [d for d in range(1, int(math.isqrt(x)) + 1) if x % d == 0]
    return sorted(set(fs + [x // d for d in fs]))


def _snap_to_factor(value, total, cap):
    cands = [f for f in _factors(total) if f <= cap]
    return min(cands, key=lambda f: (abs(f - value), f)) if cands else 1


def _seed_layer_bandwidth(layer, part, controller="passive", exact_iters=False):
    g = layer.groups
    mg, ng = layer.cin // g, layer.cout // g
    m = min(part.m, mg)
    n = min(part.n, ng)
    out_iters = math.ceil(ng / n) if exact_iters else ng / n
    in_iters = math.ceil(mg / m) if exact_iters else mg / m
    b_i = layer.wi * layer.hi * layer.cin * out_iters
    writes = layer.wo * layer.ho * layer.cout * in_iters
    if controller == "active":
        b_o = writes
    else:
        b_o = 2 * writes - layer.wo * layer.ho * layer.cout
    return float(b_i), float(b_o)


def _seed_partition_layer(layer, p_macs, strategy="paper_opt", controller="passive"):
    g = layer.groups
    mg, ng = layer.cin // g, layer.cout // g
    budget = max(1, p_macs // (layer.k * layer.k))
    if strategy == "max_input":
        m = min(mg, budget)
        n = min(ng, max(1, budget // m))
    elif strategy == "max_output":
        n = min(ng, budget)
        m = min(mg, max(1, budget // n))
    elif strategy == "equal":
        side = max(1, int(math.isqrt(budget)))
        m = min(mg, side)
        n = min(ng, max(1, budget // m))
    elif strategy == "paper_opt":
        m_star = math.sqrt(2.0 * layer.wo * layer.ho * p_macs
                           / (layer.wi * layer.hi * layer.k * layer.k))
        m = _snap_to_factor(m_star, mg, cap=min(mg, budget))
        n = min(ng, max(1, budget // m))
    elif strategy == "exact_opt":
        best, best_b = _SeedPartition(1, 1), float("inf")
        for m in range(1, min(mg, budget) + 1):
            n = min(ng, max(1, budget // m))
            b = sum(_seed_layer_bandwidth(layer, _SeedPartition(m, n), controller,
                                          exact_iters=True))
            if b < best_b:
                best, best_b = _SeedPartition(m, n), b
        return best
    else:
        raise ValueError(strategy)
    return _SeedPartition(m, n)


def _seed_network_bandwidth(layers, p_macs, strategy="paper_opt",
                            controller="passive", exact_iters=None,
                            paper_convention=False):
    total = 0.0
    exact = strategy == "exact_opt" if exact_iters is None else exact_iters
    for layer in layers:
        if paper_convention and layer.groups > 1:
            layer = dataclasses.replace(layer, groups=1)
        part = _seed_partition_layer(layer, p_macs, strategy, controller)
        total += sum(_seed_layer_bandwidth(layer, part, controller,
                                           exact_iters=exact))
    return total


# --------------------------------------------------------------------------
# Frozen seed reference: GEMM block planner (verbatim from seed partitioner.py)
# --------------------------------------------------------------------------
_LANE, _SUBLANE = 128, 8
_DEFAULT_VMEM_BUDGET = 96 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class _SeedBlocks:
    bm: int
    bn: int
    bk: int

    def vmem_bytes(self, in_bytes=2, acc_bytes=4, double_buffer=True):
        # The seed counted one accumulator tile; the launches allocate every
        # blocked operand twice and the passive one streams fp32 partial
        # sums in and out, so the footprint the planner must respect is:
        mult = 2 if double_buffer else 1
        return mult * ((self.bm * self.bk + self.bk * self.bn) * in_bytes
                       + 2 * self.bm * self.bn * acc_bytes)


def _seed_matmul_traffic(m, n, k, blocks, controller="active"):
    gi = math.ceil(m / blocks.bm)
    gj = math.ceil(n / blocks.bn)
    gk = math.ceil(k / blocks.bk)
    a_reads = gj * m * k
    b_reads = gi * k * n
    c_traffic = m * n if controller == "active" else (2 * gk - 1) * m * n
    return float(a_reads + b_reads + c_traffic)


def _seed_aligned_candidates(dim, align, cap):
    top = min(((dim + align - 1) // align) * align, cap)
    cands = []
    c = align
    while c <= top:
        cands.append(c)
        c *= 2
    if top not in cands:
        cands.append(top)
    return sorted(set(cands))


def _seed_plan_matmul_blocks(m, n, k, in_bytes=2, acc_bytes=4,
                             vmem_budget=_DEFAULT_VMEM_BUDGET,
                             controller="active", max_block=4096):
    best, best_t = None, float("inf")
    for bm in _seed_aligned_candidates(m, _SUBLANE * 16, max_block):
        for bn in _seed_aligned_candidates(n, _LANE, max_block):
            for bk in _seed_aligned_candidates(k, _LANE, max_block):
                b = _SeedBlocks(bm, bn, bk)
                if b.vmem_bytes(in_bytes, acc_bytes) > vmem_budget:
                    continue
                t = _seed_matmul_traffic(m, n, k, b, controller)
                if t < best_t:
                    best, best_t = b, t
    return best if best is not None else _SeedBlocks(_SUBLANE * 16, _LANE, _LANE)


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
            seed = _seed_network_bandwidth(layers, p, strat,
                                           paper_convention=True)
            new = plan.network_traffic(net, p, strat, paper_convention=True)
            shim = bwmodel.network_table(net, p, strat, paper_convention=True)
            assert new == seed, (net, p, strat)
            assert shim == seed, (net, p, strat)


@pytest.mark.parametrize("net", PAPER_CNNS)
def test_table2_bit_for_bit(net):
    """Table II totals (passive vs active controller): exact parity."""
    layers = get_cnn(net)
    for p in TABLE2_P:
        for ctrl in ("passive", "active"):
            seed = _seed_network_bandwidth(layers, p, "paper_opt", ctrl,
                                           paper_convention=True)
            new = plan.network_traffic(net, p, "paper_opt", ctrl,
                                       paper_convention=True)
            assert new == seed, (net, p, ctrl)


@pytest.mark.parametrize("net", ("resnet18", "mobilenet", "mnasnet"))
@pytest.mark.parametrize("p", TABLE1_P)
def test_exact_opt_and_groups_aware_parity(net, p):
    """The beyond-paper paths (exact search, groups-aware model) also agree."""
    layers = get_cnn(net)
    for strat in ("exact_opt", "paper_opt"):
        for ctrl in ("passive", "active"):
            seed = _seed_network_bandwidth(layers, p, strat, ctrl)
            new = plan.network_traffic(net, p, strat, ctrl)
            assert new == seed, (net, p, strat, ctrl)


@pytest.mark.parametrize("net", PAPER_CNNS)
@pytest.mark.parametrize("p", TABLE1_P)
def test_per_layer_schedule_parity(net, p):
    """Chosen (m, n) matches the seed partitioner layer-by-layer."""
    for layer in get_cnn(net):
        seed = _seed_partition_layer(layer, p, "paper_opt")
        sched = plan.plan(plan.ConvWorkload.from_layer(layer), p,
                          "paper_opt", "passive").schedule
        assert (sched.m, sched.n) == (seed.m, seed.n), (net, layer.name, p)


GEMMS = [(4096, 4096, 4096), (8192, 28672, 8192), (512, 512, 512),
         (1048576, 2048, 1536), (128, 128, 128)]


@pytest.mark.parametrize("m,n,k", GEMMS)
@pytest.mark.parametrize("ctrl", ("active", "passive"))
def test_gemm_blocks_bit_for_bit(m, n, k, ctrl):
    """VMEM block planning: new pipeline == frozen seed search, exactly."""
    seed = _seed_plan_matmul_blocks(m, n, k, controller=ctrl)
    p = plan.plan(plan.MatmulWorkload(m=m, n=n, k=k),
                  strategy="exhaustive_vmem", controller=ctrl)
    s = p.schedule
    assert (s.bm, s.bn, s.bk) == (seed.bm, seed.bn, seed.bk)
    assert p.traffic.interconnect_words == _seed_matmul_traffic(m, n, k, seed, ctrl)
