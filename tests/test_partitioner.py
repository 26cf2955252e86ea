"""Tests for the TPU-side generalization: VMEM-budget matmul block planning
(`repro.plan.gemm_model`)."""

import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:   # optional dep: fall back to the vendored stub
    from _hypothesis_stub import given, settings, st

from repro.plan.gemm_model import (DEFAULT_VMEM_BUDGET, MatmulBlocks,
                                   first_order_block, matmul_terms,
                                   plan_matmul_blocks)
from repro.plan.workload import MatmulWorkload

GEMMS = [
    (4096, 4096, 4096),
    (8192, 28672, 8192),    # llama-90b FFN up
    (1048576, 2048, 1536),  # token-major qwen2 qkv
    (512, 512, 512),
    (128, 128, 128),
]


def matmul_traffic(m, n, k, b, controller):
    return matmul_terms(MatmulWorkload(m=m, n=n, k=k), b.bm, b.bn, b.bk,
                        controller)


@pytest.mark.parametrize("m,n,k", GEMMS)
def test_planned_blocks_fit_budget_and_align(m, n, k):
    b = plan_matmul_blocks(m, n, k)
    assert b.vmem_bytes() <= DEFAULT_VMEM_BUDGET
    assert b.bm % 128 == 0 and b.bn % 128 == 0 and b.bk % 128 == 0


@pytest.mark.parametrize("m,n,k", GEMMS)
def test_active_beats_passive_traffic(m, n, k):
    b = plan_matmul_blocks(m, n, k)
    ta = matmul_traffic(m, n, k, b, "active").total
    tp = matmul_traffic(m, n, k, b, "passive").total
    assert ta <= tp
    if k > b.bk:  # more than one reduction step -> strict saving
        assert ta < tp


@pytest.mark.parametrize("m,n,k", GEMMS)
def test_exact_search_beats_first_order(m, n, k):
    exact = plan_matmul_blocks(m, n, k)
    fo = first_order_block(m, n, k)
    te = matmul_traffic(m, n, k, exact, "active").total
    tf = matmul_traffic(m, n, k, fo, "active").total
    assert te <= tf * 1.0001


def test_traffic_floor_is_touch_each_operand_once():
    m, n, k = 1024, 1024, 1024
    b = plan_matmul_blocks(m, n, k)
    t = matmul_traffic(m, n, k, b, "active")
    assert t.total >= m * k + k * n + m * n


@settings(max_examples=100, deadline=None)
@given(m=st.integers(128, 16384), n=st.integers(128, 16384),
       k=st.integers(128, 16384))
def test_property_budget_respected(m, n, k):
    b = plan_matmul_blocks(m, n, k)
    assert b.vmem_bytes() <= DEFAULT_VMEM_BUDGET
    t = matmul_traffic(m, n, k, b, "active")
    assert t.total >= m * k + k * n + m * n - 1


@settings(max_examples=50, deadline=None)
@given(m=st.integers(256, 8192), n=st.integers(256, 8192),
       k=st.integers(256, 8192),
       budget=st.sampled_from([1 << 20, 4 << 20, 16 << 20, 64 << 20]))
def test_property_more_vmem_never_more_traffic(m, n, k, budget):
    """Monotonicity: growing the budget (paper: adding MACs) can only help."""
    small = plan_matmul_blocks(m, n, k, vmem_budget=budget)
    large = plan_matmul_blocks(m, n, k, vmem_budget=budget * 2)
    ts = matmul_traffic(m, n, k, small, "active").total
    tl = matmul_traffic(m, n, k, large, "active").total
    assert tl <= ts * 1.0001


def test_bytes_model_passive_spills_are_fp32():
    m = n = k = 2048
    b = MatmulBlocks(256, 256, 256)
    active_bytes = matmul_traffic(m, n, k, b, "active").bytes
    passive_bytes = matmul_traffic(m, n, k, b, "passive").bytes
    gk = k // b.bk
    io = (gk and (n // b.bn) * m * k + (m // b.bm) * k * n) * 2
    assert active_bytes == io + m * n * 2
    assert passive_bytes == io + ((gk - 1) * 2 + 1) * m * n * 4
