"""`TrafficReport`: one per-level traffic breakdown for any (workload,
schedule) pair — interconnect words (the paper's "BW"), local-memory
(SRAM/VMEM) accesses, and dtype-weighted bytes.

The conv numbers reproduce the analytical model of eqs (2)/(3) and mirror the
instrumented AMC simulation (``core.amc``) access-for-access, which is what
``amc.run_partitioned_conv`` cross-validates against. The matmul numbers are
the blocked-GEMM model of ``plan.gemm_model`` (validated against the Pallas
kernels' ``hbm_traffic_bytes``).
"""

from __future__ import annotations

import dataclasses
import math

from repro.plan import conv_model, gemm_model
from repro.plan.schedule import Controller, Schedule
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


def conv_traffic(wl: ConvWorkload, schedule: Schedule,
                 exact_iters: bool = True) -> TrafficReport:
    """Report for a partitioned conv (defaults to ceil iteration counts, the
    executable semantics; pass exact_iters=False for the paper's real-valued
    M/m convention)."""
    b_i, b_o = conv_model.conv_bandwidth(wl, schedule.m, schedule.n,
                                         schedule.controller, exact_iters)
    g = wl.groups
    mg = wl.cin // g
    in_iters = math.ceil(mg / min(schedule.m, mg))
    # Mirror of the AMC meter: every input word is read from input SRAM once
    # per arrival; the accumulator is written every iteration and read on
    # every non-first iteration (internally when active, over the bus when
    # passive — same count, different interconnect charge).
    sram_reads = b_i + (in_iters - 1) * wl.out_acts
    sram_writes = float(in_iters * wl.out_acts)
    total = b_i + b_o
    return TrafficReport(interconnect_words=total, input_words=b_i,
                         output_words=b_o, sram_reads=sram_reads,
                         sram_writes=sram_writes,
                         bytes=total * wl.word_bytes)


def matmul_traffic_report(wl: MatmulWorkload, schedule: Schedule) -> TrafficReport:
    """Report for a blocked GEMM under the schedule's controller."""
    t = gemm_model.matmul_traffic(wl.m, wl.n, wl.k, schedule,
                                  schedule.controller, wl.groups)
    nbytes = gemm_model.traffic_model_bytes(
        wl.m, wl.n, wl.k, schedule, schedule.controller,
        in_bytes=wl.in_bytes, out_bytes=wl.out_bytes, acc_bytes=wl.acc_bytes,
        groups=wl.groups)
    gk = math.ceil(wl.k / schedule.bk)
    acc = wl.m * wl.n
    return TrafficReport(
        interconnect_words=t["total"],
        input_words=t["a_reads"] + t["b_reads"],
        output_words=t["c_traffic"],
        sram_reads=float((gk - 1) * acc),   # accumulator re-reads per k step
        sram_writes=float(gk * acc),
        bytes=nbytes)


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
