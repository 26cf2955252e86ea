"""The paper's traffic model generalized to VMEM-budget GEMM blocking.

Single implementation of the GEMM traffic model (`matmul_terms`) and the
block-shape search. The objective is the paper's first-order traffic model
with the constraint swapped (eq 1's P MACs -> a VMEM byte budget):

  paper:  K^2 * m * n                                           <= P MACs
  here :  2 * (bytes(bm,bk) + bytes(bk,bn) + 2 * acc_bytes(bm,bn))  <= VMEM budget

(the VMEM a `psum_matmul` launch allocates, double buffers counted — see
`MatmulBlocks.vmem_bytes`; the budget is the limit every launch requests.)

Traffic for C[M,N] = A[M,K] @ B[K,N] with grid (M/bm, N/bn, K/bk):

  A reads:  ceil(N/bn) * M * K          (each A block re-read per N block)
  B reads:  ceil(M/bm) * K * N
  C,active: M * N                        (accumulator VMEM-resident across k)
  C,passive: (2*ceil(K/bk) - 1) * M * N  (spill + read-back per k step)

A grouped GEMM (``groups`` = G > 1: M rows sorted by group, one K x N weight
per group) runs at most ceil(M/bm) + G - 1 row tiles: each boundary between
two groups inside a row tile costs one more, partial, tile. Each partial
tile re-reads up to bm rows of A; C is written once (a shared row block
stays resident between its groups). The launch walks a group's row tiles
one after another, within one N block. With more than one K block
(bk < K), the weight block changes on every k-step, so each row tile reads
its group's weights again. With one K block (bk = K), the group's
consecutive tiles ask for the same weight block, which the pipeline does
not fetch again: each group's weights are read once per N block. All of
these are the worst case over group sizes:

  A reads:  ceil(N/bn) * (M + (G - 1) * bm) * K
  B reads:  (ceil(M/bm) + G - 1) * K * N    (bk < K)
            G * K * N                       (bk = K)

The accumulator memory (VMEM) is written once per k-step and read on every
later one, under either controller:

  SRAM writes:  ceil(K/bk) * M * N
  SRAM reads:   (ceil(K/bk) - 1) * M * N

Bytes weight A and B reads by the operand width, the active controller's one
C write by the output width, and the passive controller's spills and
read-backs by the fp32 accumulator width.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

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


class MatmulTerms(NamedTuple):
    """`matmul_terms`' result: the int64 count of N blocks and float64
    words and bytes, 0-d for one schedule, one entry per candidate for
    arrays."""

    gj: np.ndarray           # N blocks: reads of each A word
    a_reads: np.ndarray
    b_reads: np.ndarray
    c_traffic: np.ndarray
    input_words: np.ndarray  # A + B reads
    total: np.ndarray
    sram_reads: np.ndarray
    sram_writes: np.ndarray
    bytes: np.ndarray
    sram_bytes: np.ndarray   # SRAM accesses at the accumulator width

    @property
    def output_words(self) -> np.ndarray:
        return self.c_traffic

    @property
    def read_iters(self) -> np.ndarray:
        return self.gj

    @property
    def weight_reads(self) -> np.ndarray:
        return self.b_reads


def matmul_terms(wl: MatmulWorkload, bm, bn, bk, controller) -> MatmulTerms:
    """The GEMM traffic model (module docstring) for ``wl`` under blocks
    ``bm``/``bn``/``bk``: scalars for one schedule or candidate arrays.
    Every count is exact int64 arithmetic with one final float64
    conversion."""
    controller = Controller.coerce(controller)
    bm = np.asarray(bm, np.int64)
    bn = np.asarray(bn, np.int64)
    bk = np.asarray(bk, np.int64)
    m, n, k, groups = wl.m, wl.n, wl.k, wl.groups
    gi = -(-m // bm) + (groups - 1)
    gj = -(-n // bn)
    gk = -(-k // bk)
    a_reads = gj * (m * k) + gj * ((groups - 1) * k) * bm
    b_reads = (np.where(gk == 1, groups, gi) if groups > 1 else gi) * (k * n)
    acc = m * n
    if controller is Controller.ACTIVE:
        c_traffic = np.full_like(a_reads, acc)
        c_bytes = c_traffic * wl.out_bytes
    else:
        c_traffic = (2 * gk - 1) * acc
        c_bytes = c_traffic * wl.acc_bytes          # spills are fp32
    sram_reads = (gk - 1) * acc
    sram_writes = gk * acc
    f64 = functools.partial(np.asarray, dtype=np.float64)
    return MatmulTerms(
        gj=gj, a_reads=f64(a_reads), b_reads=f64(b_reads),
        c_traffic=f64(c_traffic), input_words=f64(a_reads + b_reads),
        total=f64(a_reads + b_reads + c_traffic),
        sram_reads=f64(sram_reads), sram_writes=f64(sram_writes),
        bytes=f64((a_reads + b_reads) * wl.in_bytes + c_bytes),
        sram_bytes=f64((sram_reads + sram_writes) * wl.acc_bytes))


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
