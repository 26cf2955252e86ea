"""The paper's traffic model generalized to VMEM-budget GEMM blocking.

Single implementation of the block-shape search; ``core.partitioner`` is a
thin shim over this module. The objective is the paper's first-order traffic
model with the constraint swapped (eq 1's P MACs -> a VMEM byte budget):

  paper:  K^2 * m * n                                           <= P MACs
  here :  2 * (bytes(bm,bk) + bytes(bk,bn) + 2 * acc_bytes(bm,bn))  <= VMEM budget

(the VMEM a `psum_matmul` launch allocates, double buffers counted — see
`MatmulBlocks.vmem_bytes`; the budget is the limit every launch requests.)

Traffic for C[M,N] = A[M,K] @ B[K,N] with grid (M/bm, N/bn, K/bk):

  A reads:  ceil(N/bn) * M * K          (each A block re-read per N block)
  B reads:  ceil(M/bm) * K * N
  C,active: M * N                        (accumulator VMEM-resident across k)
  C,passive: (2*ceil(K/bk) - 1) * M * N  (spill + read-back per k step)
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.plan.schedule import Controller, Schedule, Strategy
from repro.plan.workload import MatmulWorkload

# TPU v5e: 128 MiB of VMEM per TensorCore. Mosaic grants a kernel all of it
# when asked through ``vmem_limit_bytes`` and only 16 MiB when not asked.
VMEM_BYTES = 128 * 1024 * 1024
# The one VMEM limit: every launch requests it (`repro.kernels.launch.run`),
# the planner budgets against it and the launch preflight checks against it.
# A quarter of VMEM stays free for Mosaic's internal scratch.
VMEM_LIMIT_BYTES = VMEM_BYTES * 3 // 4
DEFAULT_VMEM_BUDGET = VMEM_LIMIT_BYTES
LANE = 128      # last-dim tile (MXU/VPU lane count)
SUBLANE = 8     # second-to-last tile for fp32


@dataclasses.dataclass(frozen=True)
class MatmulBlocks:
    bm: int
    bn: int
    bk: int

    def vmem_bytes(self, in_bytes: int = 2, acc_bytes: int = 4,
                   double_buffer: bool = True) -> int:
        """VMEM a `psum_matmul` launch allocates under either controller.

        Pallas buffers every blocked operand (twice when double-buffered).
        The passive launch streams an fp32 partial-sum tile in and out; that
        bounds the active launch's accumulator plus its output tile."""
        return int(vmem_bytes_grid(self.bm, self.bn, self.bk, in_bytes,
                                   acc_bytes, double_buffer))


def matmul_traffic(m: int, n: int, k: int, blocks, controller="active"
                   ) -> dict[str, float]:
    """HBM traffic in *elements* for the blocked GEMM.

    `blocks` is anything with bm/bn/bk (MatmulBlocks or a matmul Schedule);
    `controller` coerces from the legacy strings.
    """
    controller = Controller.coerce(controller)
    gi = math.ceil(m / blocks.bm)
    gj = math.ceil(n / blocks.bn)
    gk = math.ceil(k / blocks.bk)
    a_reads = gj * m * k
    b_reads = gi * k * n
    if controller is Controller.ACTIVE:
        c_traffic = m * n
    else:
        c_traffic = (2 * gk - 1) * m * n
    return {"a_reads": float(a_reads), "b_reads": float(b_reads),
            "c_traffic": float(c_traffic),
            "total": float(a_reads + b_reads + c_traffic)}


def _aligned_candidates(dim: int, align: int, cap: int) -> list[int]:
    """Hardware-aligned block sizes for a dimension: multiples of `align`,
    capped at min(dim rounded up, cap)."""
    top = min(((dim + align - 1) // align) * align, cap)
    cands = []
    c = align
    while c <= top:
        cands.append(c)
        c *= 2
    if top not in cands:
        cands.append(top)
    return sorted(set(cands))


def aligned_block_candidates(m: int, n: int, k: int, max_block: int = 4096
                             ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The exhaustive search's (bm, bn, bk) grid as flat int64 arrays, in the
    seed triple-loop's iteration order (bm-major, then bn, then bk)."""
    bm, bn, bk = np.meshgrid(
        np.asarray(_aligned_candidates(m, SUBLANE * 16, max_block), np.int64),
        np.asarray(_aligned_candidates(n, LANE, max_block), np.int64),
        np.asarray(_aligned_candidates(k, LANE, max_block), np.int64),
        indexing="ij")
    return bm.ravel(), bn.ravel(), bk.ravel()


def vmem_bytes_grid(bm, bn, bk, in_bytes: int = 2, acc_bytes: int = 4,
                    double_buffer: bool = True) -> np.ndarray:
    """Vectorized ``MatmulBlocks.vmem_bytes`` over candidate arrays."""
    bm = np.asarray(bm, np.int64)
    bn = np.asarray(bn, np.int64)
    bk = np.asarray(bk, np.int64)
    mult = 2 if double_buffer else 1
    return mult * ((bm * bk + bk * bn) * in_bytes + 2 * bm * bn * acc_bytes)


def matmul_traffic_grid(m: int, n: int, k: int, bm, bn, bk,
                        controller="active") -> dict[str, np.ndarray]:
    """Vectorized `matmul_traffic` over candidate block arrays; the ``total``
    entry is bit-identical to the scalar evaluator element-for-element
    (exact int64 arithmetic, one final float conversion)."""
    controller = Controller.coerce(controller)
    bm = np.asarray(bm, np.int64)
    bn = np.asarray(bn, np.int64)
    bk = np.asarray(bk, np.int64)
    gi = -(-m // bm)
    gj = -(-n // bn)
    gk = -(-k // bk)
    a_reads = gj * (m * k)
    b_reads = gi * (k * n)
    if controller is Controller.ACTIVE:
        c_traffic = np.full_like(a_reads, m * n)
    else:
        c_traffic = (2 * gk - 1) * (m * n)
    return {"a_reads": a_reads.astype(np.float64),
            "b_reads": b_reads.astype(np.float64),
            "c_traffic": c_traffic.astype(np.float64),
            "total": (a_reads + b_reads + c_traffic).astype(np.float64)}


def traffic_model_bytes_grid(m: int, n: int, k: int, bm, bn, bk, controller,
                             in_bytes: int = 2, out_bytes: int = 2,
                             acc_bytes: int = 4) -> np.ndarray:
    """Vectorized `traffic_model_bytes` over candidate block arrays — the one
    dtype-weighted byte model the `repro.plan.objectives` cost functions
    share. Passive spills move fp32 accumulators; the active final write is
    the output dtype."""
    controller = Controller.coerce(controller)
    t = matmul_traffic_grid(m, n, k, bm, bn, bk, controller)
    io = (t["a_reads"] + t["b_reads"]) * in_bytes
    if controller is Controller.ACTIVE:
        return io + float(m * n * out_bytes)
    gk = -(-k // np.asarray(bk, np.int64))
    return io + ((gk - 1) * 2 + 1) * (m * n) * acc_bytes


def plan_matmul_blocks_scalar(m: int, n: int, k: int, *, in_bytes: int = 2,
                              acc_bytes: int = 4,
                              vmem_budget: int = DEFAULT_VMEM_BUDGET,
                              controller="active",
                              max_block: int = 4096) -> MatmulBlocks:
    """Frozen pre-vectorization exhaustive search (the seed's triple Python
    loop). Parity oracle for the property tests and the benchmark baseline.
    Do not optimise."""
    controller = Controller.coerce(controller)
    best: MatmulBlocks | None = None
    best_t = float("inf")
    for bm in _aligned_candidates(m, SUBLANE * 16, max_block):      # mult of 128
        for bn in _aligned_candidates(n, LANE, max_block):
            for bk in _aligned_candidates(k, LANE, max_block):
                b = MatmulBlocks(bm, bn, bk)
                if b.vmem_bytes(in_bytes, acc_bytes) > vmem_budget:
                    continue
                t = matmul_traffic(m, n, k, b, controller)["total"]
                if t < best_t:
                    best, best_t = b, t
    if best is None:  # budget smaller than one minimal tile — take minimum
        best = MatmulBlocks(SUBLANE * 16, LANE, LANE)
    return best


def plan_matmul_blocks(m: int, n: int, k: int, *, in_bytes: int = 2,
                       acc_bytes: int = 4, vmem_budget: int = DEFAULT_VMEM_BUDGET,
                       controller="active", max_block: int = 4096) -> MatmulBlocks:
    """Exact search over hardware-aligned block shapes minimizing HBM traffic
    under the VMEM budget — the integer-exact analogue of the paper's eq (7),
    as one masked argmin over the aligned candidate grid (`repro.plan.dse`).

    First-order intuition (matches eq 7 when the C term dominates): traffic
    ~ M*N*K*(1/bm + 1/bn) + C-term, so square (bm = bn = sqrt(budget)) output
    blocks with the largest feasible bk.
    """
    from repro.plan import dse, space
    wl = MatmulWorkload(m=m, n=n, k=k, in_bytes=in_bytes, acc_bytes=acc_bytes)
    res = dse.search(wl, vmem_budget, space=space.AlignedBlockSpace(max_block),
                     constraints=(dse.VmemBudget(),),
                     objective="interconnect_words",
                     controller=Controller.coerce(controller))
    return res.schedule.as_blocks()


def first_order_block(m: int, n: int, k: int, *, in_bytes: int = 2,
                      acc_bytes: int = 4,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET,
                      max_block: int = 4096) -> MatmulBlocks:
    """Closed-form analogue of the paper's eq (7) for GEMM: with the input
    terms dominating, minimize 1/bm + 1/bn -> bm = bn (the 'square block'
    rule). The partial-sum tiles take at most half of the VMEM model
    (`MatmulBlocks.vmem_bytes`), and bk is as large as the leftover allows."""
    side = min(int(math.sqrt(vmem_budget / (8 * acc_bytes))), max_block)
    bm = max(LANE, (min(side, m) // LANE) * LANE)
    bn = max(LANE, (min(side, n) // LANE) * LANE)
    leftover = vmem_budget // 2 - 2 * bm * bn * acc_bytes
    bk_budget = leftover // (in_bytes * (bm + bn))
    bk = max(LANE, (min(bk_budget, k) // LANE) * LANE)
    return MatmulBlocks(bm, bn, bk)


def conv_blocks_from_partition(m_part: int, n_part: int) -> tuple[int, int]:
    """Map the paper's (m input maps, n output maps) partition onto channel
    block sizes for the Pallas conv kernel (snap to lane multiples)."""
    bm = max(SUBLANE, min(512, 1 << (m_part - 1).bit_length()))
    bn = max(LANE, min(512, 1 << (n_part - 1).bit_length()))
    return bm, bn


def traffic_model_bytes(m: int, n: int, k: int, blocks, controller,
                        in_bytes: int = 2, out_bytes: int = 2,
                        acc_bytes: int = 4) -> float:
    """Traffic in bytes, distinguishing in/out/accumulator element widths.
    Passive spills move fp32 accumulators; the active final write is the
    output dtype — an additional saving the paper's word-count model hides."""
    controller = Controller.coerce(controller)
    t = matmul_traffic(m, n, k, blocks, controller)
    io = (t["a_reads"] + t["b_reads"]) * in_bytes
    if controller is Controller.ACTIVE:
        c = m * n * out_bytes
    else:
        gk = math.ceil(k / blocks.bk)
        c = ((gk - 1) * 2 + 1) * m * n * acc_bytes  # spills are fp32
    return io + c


def plan_gemm(wl: MatmulWorkload, vmem_budget: int, strategy: Strategy,
              controller: Controller, max_block: int = 4096) -> Schedule:
    """Strategy dispatch for GEMM workloads.

    EXHAUSTIVE_VMEM / EXACT_OPT -> the exact aligned search;
    FIRST_ORDER / PAPER_OPT / EQUAL -> the closed-form square-block rule
    (eq 7's analogue; 'equal' because bm = bn). The conv-only max_input /
    max_output strategies have no GEMM meaning and raise.

    Like `plan_conv`, every strategy is a `repro.plan.dse` preset of
    (space, constraints, objective); this is the GEMM-flavoured entry point.
    """
    from repro.plan import dse
    return dse.plan_with_strategy(wl, vmem_budget, strategy, controller,
                                  max_block=max_block)
