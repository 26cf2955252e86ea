"""Pallas launch pre-flight: prove a kernel's BlockSpec geometry before
anything compiles.

`conv2d_psum` / `psum_matmul` / `flash_attention` pick their grid,
BlockSpecs, and scratch from a `Schedule`; a malformed launch (block not
dividing the padded array, an index map addressing past the array, a VMEM
working set over the launch's limit) surfaces from Mosaic as a deep compile
error — or worse, as silent padding garbage under the interpreter. This
module checks the very `repro.kernels.launch.LaunchPlan` each kernel executes
(built from plain integers by the kernel's ``*_launch_plan`` builder, imported
lazily), so `run_network_kernels` can reject a bad plan with an RPC03x
diagnostic *before* the first `pallas_call`.
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.check.diagnostics import Diagnostic, errors, raise_on_error
from repro.plan.gemm_model import VMEM_LIMIT_BYTES
from repro.plan.graph import NetworkGraph
from repro.plan.schedule import Schedule
from repro.plan.workload import ConvWorkload

# Grids with at most this many points get every point's index map evaluated;
# larger grids are sampled at the corners (sound for the kernels' affine
# projection maps, which are monotone in each grid coordinate).
_EXHAUSTIVE_GRID = 4096


def _grid_points(grid: Sequence[int]):
    total = 1
    for g in grid:
        total *= g
    ranges: List[Sequence[int]]
    if total <= _EXHAUSTIVE_GRID:
        ranges = [range(g) for g in grid]
    else:
        ranges = [sorted({0, g - 1}) for g in grid]
    return itertools.product(*ranges)


def launch_vmem_bytes(plan: Any) -> int:
    """VMEM a launch allocates: Pallas double-buffers every blocked operand;
    scratch buffers are allocated once."""
    blocks = sum(op.block_words * op.elem_bytes for op in plan.operands)
    scratch = sum(s.words * np.dtype(s.dtype or np.float32).itemsize
                  for s in plan.scratch)
    return 2 * blocks + scratch


def check_launch(plan: Any, vmem_budget: Optional[int] = None,
                 subject: Optional[str] = None) -> List[Diagnostic]:
    """RPC030 (divisibility), RPC031 (index map range / rank), RPC032 (VMEM)
    for a `LaunchPlan` (or anything with its name/grid/operands/scratch).

    A launch with scalar-prefetch operands gets RPC030 and RPC032, which do
    not depend on device data, and an RPC034 warning in place of the index
    maps' range and rank, which do."""
    out: List[Diagnostic] = []
    subject = subject or plan.name
    prefetch = getattr(plan, "prefetch", ())
    if prefetch:
        out.append(Diagnostic(
            "RPC034", subject,
            f"index maps read the scalar-prefetch operands "
            f"{[p.name for p in prefetch]}; block indices and their range "
            f"were not checked"))
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else int(vmem_budget)
    if any(g < 1 for g in plan.grid):
        out.append(Diagnostic(
            "RPC031", subject, f"empty grid {plan.grid}"))
        return out
    for op in plan.operands:
        if len(op.block_shape) != len(op.array_shape):
            out.append(Diagnostic(
                "RPC031", subject,
                f"{op.name}: block rank {len(op.block_shape)} != array rank "
                f"{len(op.array_shape)}"))
            continue
        if any(b < 1 for b in op.block_shape):
            out.append(Diagnostic(
                "RPC030", subject,
                f"{op.name}: non-positive block {op.block_shape}"))
            continue
        if any(a % b for a, b in zip(op.array_shape, op.block_shape)):
            out.append(Diagnostic(
                "RPC030", subject,
                f"{op.name}: block {op.block_shape} does not divide the "
                f"padded array {op.array_shape}"))
            continue
        bounds = tuple(a // b for a, b in
                       zip(op.array_shape, op.block_shape))
        for pt in (() if prefetch else _grid_points(plan.grid)):
            idx = tuple(op.index_map(*pt))
            if len(idx) != len(bounds):
                out.append(Diagnostic(
                    "RPC031", subject,
                    f"{op.name}: index map returns rank {len(idx)}, "
                    f"expected {len(bounds)}"))
                break
            if any(i < 0 or i >= hi for i, hi in zip(idx, bounds)):
                out.append(Diagnostic(
                    "RPC031", subject,
                    f"{op.name}: index map sends grid point {pt} to block "
                    f"{idx}, valid range {tuple((0, hi - 1) for hi in bounds)}"
                ))
                break
    vmem = launch_vmem_bytes(plan)
    if vmem > budget:
        out.append(Diagnostic(
            "RPC032", subject,
            f"VMEM footprint {vmem} B (double-buffered blocks + scratch) > "
            f"limit {budget} B"))
    return out


# ------------------------------------------------------------ conv2d_psum
def check_conv_launch(wl: ConvWorkload, schedule: Schedule,
                      subject: Optional[str] = None,
                      vmem_budget: Optional[int] = None) -> List[Diagnostic]:
    """Pre-flight one conv node as `run_network_kernels` would launch it:
    channel-concatenated "same"-padded input, schedule blocks.

    The proof is memoized per distinct launch (workload, schedule, subject,
    budget), as the dataflow proof is per geometry; every call gets a fresh
    list."""
    subject = subject or getattr(wl, "name", "conv2d_psum")
    budget = VMEM_LIMIT_BYTES if vmem_budget is None else int(vmem_budget)
    return list(_conv_launch_cached(wl, schedule, subject, budget))


@functools.lru_cache(maxsize=512)
def _conv_launch_cached(wl: ConvWorkload, schedule: Schedule, subject: str,
                        budget: int) -> Tuple[Diagnostic, ...]:
    if schedule.kind != "conv":
        return (Diagnostic(
            "RPC003", subject,
            f"kernel launch for a conv needs kind='conv', got "
            f"{schedule.kind!r}"),)
    if wl.groups != 1:
        return (Diagnostic(
            "RPC031", subject,
            f"conv2d_psum executes dense convs only (groups={wl.groups})"),)
    pad = wl.k // 2
    if (wl.hi + 2 * pad - wl.k) // wl.stride + 1 != wl.ho or \
            (wl.wi + 2 * pad - wl.k) // wl.stride + 1 != wl.wo:
        return (Diagnostic(
            "RPC031", subject,
            f"not 'same'-padded: ({wl.hi}x{wl.wi}, k={wl.k}, "
            f"stride={wl.stride}) cannot produce ({wl.ho}x{wl.wo}); "
            f"shrink() the graph first"),)
    from repro.kernels.conv2d_psum import conv_launch_plan
    plan = conv_launch_plan(cin=wl.cin, hp=wl.hi + 2 * pad,
                            wp=wl.wi + 2 * pad, cout=wl.cout, kk=wl.k,
                            stride=wl.stride, block_m=schedule.bm,
                            block_n=schedule.bn)
    return tuple(check_launch(plan, budget, subject))


# ------------------------------------------------------------ psum_matmul
def check_matmul_launch(m: int, k: int, n: int, schedule: Schedule,
                        subject: str = "psum_matmul",
                        vmem_budget: Optional[int] = None
                        ) -> List[Diagnostic]:
    """Pre-flight every launch `psum_matmul` issues under ``schedule`` (one,
    or one per k-step for the passive controller)."""
    if schedule.kind != "matmul":
        return [Diagnostic(
            "RPC003", subject,
            f"kernel launch for a GEMM needs kind='matmul', got "
            f"{schedule.kind!r}")]
    from repro.kernels.psum_matmul import (matmul_launch_plan,
                                           reduction_launches)
    ctrl = schedule.controller.value
    out: List[Diagnostic] = []
    for step in range(reduction_launches(k, schedule.bk, ctrl)):
        plan = matmul_launch_plan(m=m, k=k, n=n, bm=schedule.bm,
                                  bn=schedule.bn, bk=schedule.bk,
                                  controller=ctrl, k_step=step)
        out += check_launch(plan, vmem_budget, subject)
    return out


# --------------------------------------------------------- flash_attention
def check_flash_launch(bh: int, sq: int, skv: int, d: int, bq: int = 128,
                       bk: int = 128, causal: bool = True, q_offset: int = 0,
                       subject: str = "flash_attention",
                       vmem_budget: Optional[int] = None) -> List[Diagnostic]:
    """Pre-flight one attention launch: geometry (RPC030-032) plus the one
    semantic hazard BlockSpecs can't express — zero-padded kv keys are only
    maskable inside the kernel when causal; non-causal padded kv would let
    padded keys contribute exp(0) softmax weight (RPC031)."""
    out: List[Diagnostic] = []
    if min(bh, sq, skv, d) < 1:
        out.append(Diagnostic(
            "RPC031", subject,
            f"degenerate attention shape bh={bh} sq={sq} skv={skv} d={d}"))
        return out
    bk_eff = max(1, min(bk, skv))
    if skv % bk_eff and not causal:
        out.append(Diagnostic(
            "RPC031", subject,
            f"skv={skv} is not a multiple of bk={bk_eff} and causal=False: "
            f"the kernel masks padded keys via the causal id lattice only; "
            f"pad kv to a block multiple or use causal masking"))
    if causal and q_offset < 0:
        out.append(Diagnostic(
            "RPC031", subject,
            f"negative q_offset={q_offset} puts query ids before key id 0"))
    from repro.kernels.flash_attention import flash_launch_plan
    plan = flash_launch_plan(bh=bh, sq=sq, skv=skv, d=d, bq=bq, bk=bk,
                             causal=causal, q_offset=q_offset)
    return out + check_launch(plan, vmem_budget, subject)


def preflight_flash_launch(bh: int, sq: int, skv: int, d: int, bq: int = 128,
                           bk: int = 128, causal: bool = True,
                           q_offset: int = 0,
                           vmem_budget: Optional[int] = None) -> None:
    """The gate `flash_attention` calls before building its plan: raises
    `CheckError` on any RPC03x error, compiles nothing."""
    raise_on_error(check_flash_launch(bh, sq, skv, d, bq, bk, causal,
                                      q_offset, vmem_budget=vmem_budget),
                   context="flash_attention pre-flight failed")


# ------------------------------------------------------- whole-network gate
def check_network_kernels(graph: NetworkGraph, schedules: Any,
                          params: Optional[Mapping[str, object]] = None,
                          vmem_budget: Optional[int] = None
                          ) -> List[Diagnostic]:
    """Pre-flight every conv node `run_network_kernels` would launch.

    ``schedules`` is a NetPlan or a {node name: Schedule} mapping, exactly as
    the runner accepts. RPC033 for nodes with no schedule (or, when ``params``
    is given, no weights); RPC031 for weights whose shape disagrees with the
    workload; RPC030/031/032 from the per-node launch geometry.
    """
    if hasattr(schedules, "schedules"):      # a NetPlan
        schedules = schedules.schedules
    out: List[Diagnostic] = []
    for node in graph.workload_nodes:
        wl = node.workload
        if not isinstance(wl, ConvWorkload):
            continue       # the network runner only launches convs
        sched = schedules.get(node.name) if schedules is not None else None
        if sched is None:
            out.append(Diagnostic(
                "RPC033", node.name, "conv node has no schedule"))
            continue
        if params is not None:
            wt = params.get(node.name)
            if wt is None:
                out.append(Diagnostic(
                    "RPC033", node.name, "conv node has no kernel weights"))
                continue
            want = (wl.cout, wl.cin, wl.k, wl.k)
            got = tuple(getattr(wt, "shape", ()))
            if got != want:
                out.append(Diagnostic(
                    "RPC031", node.name,
                    f"weights shaped {got}, workload needs {want}"))
                continue
        out += check_conv_launch(wl, sched, node.name, vmem_budget)
    return out


def preflight_network_kernels(graph: NetworkGraph, schedules: Any,
                              params: Optional[Mapping[str, object]] = None,
                              vmem_budget: Optional[int] = None,
                              dataflow: bool = True) -> None:
    """The gate `run_network_kernels` calls before any pallas_call: raises
    `CheckError` listing every RPC03x/RPC04x error, compiles nothing.

    Each conv's launch-geometry proof is memoized per distinct launch
    (`check_conv_launch`), so a repeated call proves only launches it has not
    seen; the schedule and weight lookups (RPC033, RPC031) run on every call,
    and a failing launch raises on every call. With ``dataflow`` (the
    default) every node's launch is also traced by `repro.check.dataflow` —
    race/coverage/accumulation proofs plus the eq (2)/(3) word-count
    equivalence — cached per launch geometry, so the added cost across a
    whole zoo is a handful of traces.

    The ``kernel.preflight`` span carries ``geometry_cached`` and
    ``geometry_proved``: the conv launches served from the memo and those
    proven in this call.
    """
    from repro.obs.trace import span
    with span("kernel.preflight", cat="kernel", graph=graph.name,
              dataflow=dataflow) as sp:
        before = _conv_launch_cached.cache_info()
        found = check_network_kernels(graph, schedules, params, vmem_budget)
        after = _conv_launch_cached.cache_info()
        sp.set("geometry_cached", after.hits - before.hits)
        sp.set("geometry_proved", after.misses - before.misses)
        if dataflow and not errors(found):
            from repro.check.dataflow import check_network_dataflow
            found += check_network_dataflow(graph, schedules)
        sp.set("diagnostics", len(found))
        raise_on_error(found, context="kernel pre-flight failed")
