"""`TrafficReport`: one per-level traffic breakdown for any (workload,
schedule) pair — interconnect words (the paper's "BW"), local-memory
(SRAM/VMEM) accesses, and dtype-weighted bytes.

Every number comes from the workload kind's one evaluator, `terms`:
`conv_model.conv_terms` (eqs (2)/(3), mirrored access-for-access by the
instrumented AMC simulation, ``core.amc``) or `gemm_model.matmul_terms`
(the blocked-GEMM model, proved against the Pallas kernels' traced words by
`repro.check.dataflow`).
"""

from __future__ import annotations

import dataclasses

from repro.plan import conv_model, gemm_model
from repro.plan.schedule import Schedule
from repro.plan.workload import ConvWorkload, MatmulWorkload, Workload


@dataclasses.dataclass(frozen=True)
class TrafficReport:
    """Per-level traffic for one scheduled workload.

    interconnect_words — words crossing the interconnect/HBM (the paper's BW)
    input_words        — operand-read share of the above (B_i / A+B reads)
    output_words       — partial-sum/output share (B_o / C traffic)
    sram_reads/writes  — accesses at the memory owning the accumulator
                         (controller SRAM for the SoC model, VMEM for TPU);
                         identical for both controllers — the active
                         controller moves work off the bus, it does not
                         remove it
    bytes              — dtype-weighted interconnect bytes
    """

    interconnect_words: float
    input_words: float
    output_words: float
    sram_reads: float
    sram_writes: float
    bytes: float

    @property
    def total_words(self) -> float:
        return self.interconnect_words

    def as_dict(self) -> dict[str, float]:
        return dataclasses.asdict(self)


def terms(wl: Workload, bm, bn, bk, controller, exact_iters: bool = True
          ) -> "conv_model.ConvTerms | gemm_model.MatmulTerms":
    """The traffic model of ``wl``'s kind under blocks ``bm``/``bn``/``bk``
    (``bk`` unused for convs; ``exact_iters`` unused for GEMMs): scalars for
    one schedule or candidate arrays."""
    if isinstance(wl, ConvWorkload):
        return conv_model.conv_terms(wl, bm, bn, controller, exact_iters)
    if isinstance(wl, MatmulWorkload):
        return gemm_model.matmul_terms(wl, bm, bn, bk, controller)
    raise TypeError(f"unknown workload type {type(wl).__name__}")


def _report(t) -> TrafficReport:
    return TrafficReport(interconnect_words=float(t.total),
                         input_words=float(t.input_words),
                         output_words=float(t.output_words),
                         sram_reads=float(t.sram_reads),
                         sram_writes=float(t.sram_writes),
                         bytes=float(t.bytes))


def conv_traffic(wl: ConvWorkload, schedule: Schedule,
                 exact_iters: bool = True) -> TrafficReport:
    """Report for a partitioned conv (defaults to ceil iteration counts, the
    executable semantics; pass exact_iters=False for the paper's real-valued
    M/m convention)."""
    return _report(conv_model.conv_terms(wl, schedule.m, schedule.n,
                                         schedule.controller, exact_iters))


def matmul_traffic_report(wl: MatmulWorkload, schedule: Schedule) -> TrafficReport:
    """Report for a blocked GEMM under the schedule's controller."""
    return _report(gemm_model.matmul_terms(wl, schedule.bm, schedule.bn,
                                           schedule.bk, schedule.controller))


def traffic_report(workload: Workload, schedule: Schedule,
                   exact_iters: bool = True) -> TrafficReport:
    """Dispatch on workload kind; validates the schedule kind matches."""
    if isinstance(workload, ConvWorkload):
        if schedule.kind != "conv":
            raise ValueError(f"conv workload needs a conv schedule, got {schedule}")
        return conv_traffic(workload, schedule, exact_iters)
    if isinstance(workload, MatmulWorkload):
        if schedule.kind != "matmul":
            raise ValueError(f"matmul workload needs a matmul schedule, got {schedule}")
        return matmul_traffic_report(workload, schedule)
    raise TypeError(f"unknown workload type {type(workload).__name__}")
