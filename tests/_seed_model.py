"""Frozen seed reference: the conv model and the GEMM block planner as the
seed wrote them, before ``repro.plan`` (verbatim but for public names).

The parity oracles of the tests: `repro.plan`'s vectorized evaluators and
searches must reproduce these numbers and choices bit-for-bit. Do not
optimise. Controllers are the seed's strings (``"passive"`` / ``"active"``);
layers are anything with the paper's fields (``core.cnn_zoo.ConvLayer`` or
``plan.ConvWorkload``).
"""

import dataclasses
import math


# --------------------------------------------------------------------------
# Conv model (seed bwmodel.py)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SeedPartition:
    m: int
    n: int


def _factors(x):
    fs = [d for d in range(1, int(math.isqrt(x)) + 1) if x % d == 0]
    return sorted(set(fs + [x // d for d in fs]))


def _snap_to_factor(value, total, cap):
    cands = [f for f in _factors(total) if f <= cap]
    return min(cands, key=lambda f: (abs(f - value), f)) if cands else 1


def layer_bandwidth(layer, part, controller="passive", exact_iters=False):
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


def partition_layer(layer, p_macs, strategy="paper_opt", controller="passive"):
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
        best, best_b = SeedPartition(1, 1), float("inf")
        for m in range(1, min(mg, budget) + 1):
            n = min(ng, max(1, budget // m))
            b = sum(layer_bandwidth(layer, SeedPartition(m, n), controller,
                                    exact_iters=True))
            if b < best_b:
                best, best_b = SeedPartition(m, n), b
        return best
    else:
        raise ValueError(strategy)
    return SeedPartition(m, n)


def network_bandwidth(layers, p_macs, strategy="paper_opt",
                      controller="passive", exact_iters=None,
                      paper_convention=False):
    total = 0.0
    exact = strategy == "exact_opt" if exact_iters is None else exact_iters
    for layer in layers:
        if paper_convention and layer.groups > 1:
            layer = dataclasses.replace(layer, groups=1)
        part = partition_layer(layer, p_macs, strategy, controller)
        total += sum(layer_bandwidth(layer, part, controller,
                                     exact_iters=exact))
    return total


# --------------------------------------------------------------------------
# GEMM block planner (seed partitioner.py)
# --------------------------------------------------------------------------
_LANE, _SUBLANE = 128, 8
_DEFAULT_VMEM_BUDGET = 96 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class SeedBlocks:
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


def matmul_traffic(m, n, k, blocks, controller="active"):
    gi = math.ceil(m / blocks.bm)
    gj = math.ceil(n / blocks.bn)
    gk = math.ceil(k / blocks.bk)
    a_reads = gj * m * k
    b_reads = gi * k * n
    c_traffic = m * n if controller == "active" else (2 * gk - 1) * m * n
    return float(a_reads + b_reads + c_traffic)


def _aligned_candidates(dim, align, cap):
    top = min(((dim + align - 1) // align) * align, cap)
    cands = []
    c = align
    while c <= top:
        cands.append(c)
        c *= 2
    if top not in cands:
        cands.append(top)
    return sorted(set(cands))


def plan_matmul_blocks(m, n, k, in_bytes=2, acc_bytes=4,
                       vmem_budget=_DEFAULT_VMEM_BUDGET,
                       controller="active", max_block=4096):
    best, best_t = None, float("inf")
    for bm in _aligned_candidates(m, _SUBLANE * 16, max_block):
        for bn in _aligned_candidates(n, _LANE, max_block):
            for bk in _aligned_candidates(k, _LANE, max_block):
                b = SeedBlocks(bm, bn, bk)
                if b.vmem_bytes(in_bytes, acc_bytes) > vmem_budget:
                    continue
                t = matmul_traffic(m, n, k, b, controller)
                if t < best_t:
                    best, best_t = b, t
    return best if best is not None else SeedBlocks(_SUBLANE * 16, _LANE, _LANE)
