"""Kernel-body dataflow analysis: race/coverage proofs and static traffic
equivalence for the Pallas launches in ``repro.kernels``.

Built on `repro.check.footprint`: `trace_launch` abstractly executes a
`LaunchPlan`'s body recording every Ref read/write with its ``pl.when``
guard, and `visit_structure` classifies each operand's BlockSpec index map.
From those two artifacts this module proves, per launch:

  RPC040  no two parallel grid steps can store to the same output block
  RPC041  scratch accumulators are initialized before any read can see them
  RPC042  the written blocks cover the whole output array
  RPC043  the accumulation chain has the shape eqs (3)/(7) assume — init at
          the chain start, one unguarded RMW per step, drain at the end,
          reduction axes a contiguous innermost grid suffix
  RPC044  aliased input/output operands address identical block windows
  RPC045  the word counts *derived from the trace* equal the analytical
          model (`TrafficReport` / `gemm_model`) — the kernels provably move
          the words the paper's eqs (2)/(3) charge
  RPC046  (warning) the body is outside the tracer's fragment; proofs skipped

Counting conventions (the bridge between trace events and the meter):

  * Word totals are **real words** — elements of the logical unpadded
    operand. Channel padding and spatial halo are zero ghost words; because
    every distinct block is transferred the same number of times (projection
    index maps), total real traffic = per-block multiplicity x real words,
    for *any* block size, dividing or not.
  * The accumulator is counted **step-level**, exactly like the AMC meter: a
    chain of length L does L writes and L-1 observing reads (the chain-start
    read sees the zero-init written in the same step; the drain read shares
    the final RMW step). The paper's eq (3) is this count: passive
    B_o = (L + (L-1)) * out_acts, active B_o = L * out_acts.
  * HBM<->VMEM transfers follow Pallas revisit elision: a block is
    (re)copied only when its index changes between consecutive grid steps.
    The first fetch of an output block whose first-run reads are all
    write-dominated is dead and not charged — that elision *is* eq (3)'s
    "-1".

The per-level split this machinery proves (and the one divergence it found):
at the level that owns the accumulator — VMEM<->compute for the TPU kernels,
the interconnect for the paper's SoC — the traced counts equal the model
exactly for **every** candidate. At the HBM<->VMEM level the kernels can do
strictly *better* than eq (2)/(3) whenever a block index is constant across
an inner grid axis (conv with a single cin block, the passive GEMM's A
operand across j): Pallas retains the block and elides the re-fetch the
model charges. `SpaceCertificate` records, per candidate, whether the HBM
count is equal or strictly bounded by the model.

Vectorized certification (`certify_conv_space` / `certify_matmul_space`):
the abstract trace is a function of the kernel *code*, not the grid sizes —
grids only enter through guard constants and axis extents. So one trace per
degeneracy class (which grid axes are 1) validates the structure, and the
trace-derived counting formulas are then evaluated as numpy arrays over the
whole candidate set against `conv_terms` / `matmul_terms`,
certifying every admitted candidate of a search space in one call.

Everything here is pure Python + numpy until a kernel module is imported
lazily for its ``*_launch_plan`` builder; no jax tracing, no compilation.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.check.diagnostics import Diagnostic, errors, raise_on_error
from repro.check.footprint import (Event, KernelTrace, UntraceableKernel,
                                   per_block_fetches, trace_launch,
                                   visit_axes, visit_structure)
from repro.plan.schedule import Controller, Schedule
from repro.plan.workload import ConvWorkload, MatmulWorkload

_ENUM_LIMIT = 1024          # exact position enumeration below this many steps


class _Unsupported(Exception):
    """Event/guard structure outside the counting fragment (degrades to
    RPC046, never to a wrong count)."""


def _prod(xs: Iterable[int]) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# -------------------------------------------------------------- launch view
@dataclasses.dataclass(frozen=True)
class LaunchAnalysis:
    """One traced launch plus its classified index maps."""

    plan: object
    trace: KernelTrace
    deps: Dict[str, tuple]                   # operand name -> per-dim Dep
    vaxes: Dict[str, frozenset]              # operand name -> visit axes
    parallel: Tuple[int, ...]
    arbitrary: Tuple[int, ...]

    @property
    def grid(self) -> Tuple[int, ...]:
        return self.trace.grid

    def events(self, name: str) -> Tuple[Event, ...]:
        return self.trace.ref_events(name)


def _semantics(plan) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    sems = plan.dimension_semantics or ("arbitrary",) * len(plan.grid)
    par = tuple(i for i, s in enumerate(sems) if s == "parallel")
    arb = tuple(i for i in range(len(plan.grid)) if i not in par)
    return par, arb


def _valid_guard(guard, grid) -> bool:
    """A guard with a coordinate outside the grid never fires."""
    return all(0 <= p.value < grid[p.axis] for p in guard)


# ---------------------------------------------------- position-class engine
def _positions(axes: Sequence[int], grid: Sequence[int], pred_values):
    """Yield (coords, weight) covering every assignment of ``axes``. Small
    extents are enumerated exactly; large single-axis chains collapse to
    start/mid/end classes (sound only when every pred on the chain axis is
    at a boundary value, checked here)."""
    axes = sorted(axes)
    total = _prod(grid[a] for a in axes)
    if total <= _ENUM_LIMIT:
        for coords in itertools.product(*[range(grid[a]) for a in axes]):
            yield dict(zip(axes, coords)), 1
        return
    big = [a for a in axes if grid[a] > 1]
    if len(big) != 1:
        raise _Unsupported("multi-axis chain too large to enumerate")
    b = big[0]
    for v in pred_values.get(b, ()):
        if v not in (0, grid[b] - 1):
            raise _Unsupported(f"interior guard coordinate {v} on axis {b}")
    base = {a: 0 for a in axes}
    yield {**base, b: 0}, 1
    if grid[b] > 2:
        yield {**base, b: None}, grid[b] - 2        # interior: no pred fires
    yield {**base, b: grid[b] - 1}, 1


def _fires(guard, coords: Dict[int, Optional[int]], grid) -> bool:
    if not _valid_guard(guard, grid):
        return False
    for p in guard:
        if p.axis not in coords:
            raise _Unsupported(f"guard on axis {p.axis} outside the "
                               f"position axes {sorted(coords)}")
        c = coords[p.axis]
        if c is None or c != p.value:
            return False
    return True


def _pred_values(events: Sequence[Event]) -> Dict[int, set]:
    out: Dict[int, set] = {}
    for e in events:
        for p in e.guard:
            out.setdefault(p.axis, set()).add(p.value)
    return out


def _chain_counts(events: Sequence[Event], axes: Sequence[int], grid
                  ) -> Tuple[int, int]:
    """Step-level (writes, observing reads) per chain over ``axes``: one
    write per step that stores, one read per step whose first firing access
    is a read (a read preceded by a same-step write observes that write,
    not the previous step — the meter's convention)."""
    writes = reads = 0
    for coords, weight in _positions(axes, grid, _pred_values(events)):
        wrote = False
        read_obs = False
        for e in events:
            if not _fires(e.guard, coords, grid):
                continue
            if e.kind == "write":
                wrote = True
            elif e.kind == "read" and not wrote:
                read_obs = True
        writes += weight * (1 if wrote else 0)
        reads += weight * (1 if read_obs else 0)
    return writes, reads


def _out_hbm_counts(events: Sequence[Event], split_axes: Sequence[int],
                    internal_axes: Sequence[int], grid) -> Tuple[int, int]:
    """(writebacks, live fetches) per output block. Each ``split_axes``
    position is one fetch-run of the block (Pallas re-copies it); within a
    run the ``internal_axes`` sweep while the block stays in VMEM. A fetch
    is live iff some read in the run observes pre-run data; a writeback is
    charged for every run that stores."""
    pv = _pred_values(events)
    writebacks = live = 0
    for s_coords, s_w in _positions(split_axes, grid, pv):
        wrote_run = False
        observed = False
        for i_coords, i_w in _positions(internal_axes, grid, pv):
            coords = {**s_coords, **i_coords}
            for e in events:
                if not _fires(e.guard, coords, grid):
                    continue
                if e.kind == "write":
                    wrote_run = True
                elif e.kind == "read" and not wrote_run:
                    observed = True
        writebacks += s_w * (1 if wrote_run else 0)
        live += s_w * (1 if observed else 0)
    return writebacks, live


def _read_multiplicity(events: Sequence[Event], vaxes: frozenset,
                       grid) -> int:
    """Per-sweep read multiplicity of an input operand: how many times each
    real word crosses VMEM->compute, summed over read events."""
    mult = 0
    for e in events:
        if e.kind != "read":
            continue
        if not _valid_guard(e.guard, grid):
            continue
        pinned = {p.axis for p in e.guard}
        if pinned & vaxes:
            raise _Unsupported(f"read of {e.ref} pinned to a visit axis")
        mult += _prod(grid[a] for a in range(len(grid))
                      if a not in vaxes and a not in pinned)
    return mult


def _split_internal(vaxes: frozenset, grid) -> Tuple[list, list]:
    """Non-visit axes of an operand, split into run-splitting (above the
    innermost effective visit axis: each coordinate is a separate fetch of
    the same block) and run-internal (below: the block is retained)."""
    active = [a for a in vaxes if grid[a] > 1]
    amax = max(active) if active else -1
    split = [a for a in range(len(grid)) if a not in vaxes and a <= amax]
    internal = [a for a in range(len(grid)) if a not in vaxes and a > amax]
    return split, internal


# ------------------------------------------------------- structural passes
def analyze_launch(plan, subject: Optional[str] = None
                   ) -> Tuple[List[Diagnostic], Optional[LaunchAnalysis]]:
    """Trace a `LaunchPlan` and run the structural dataflow passes
    (RPC040-044; RPC046 when untraceable). Word-count equivalence (RPC045)
    is per-kernel — see `conv_dataflow` / `matmul_dataflow` /
    `flash_dataflow`."""
    subject = subject or plan.name
    out: List[Diagnostic] = []
    prefetch = getattr(plan, "prefetch", ())
    if prefetch:
        return [Diagnostic(
            "RPC046", subject,
            f"index maps and body read the scalar-prefetch operands "
            f"{[p.name for p in prefetch]}: which block each grid step "
            f"touches depends on device data; dataflow proofs skipped")], None
    try:
        trace = trace_launch(plan)
    except UntraceableKernel as exc:
        return [Diagnostic("RPC046", subject, str(exc))], None
    grid = plan.grid
    par, arb = _semantics(plan)
    deps: Dict[str, tuple] = {}
    vaxes: Dict[str, frozenset] = {}
    for op in plan.operands:
        d = visit_structure(op.index_map, grid)
        deps[op.name] = d
        if any(kind == "other" for kind, _ in d):
            out.append(Diagnostic(
                "RPC046", subject,
                f"{op.name}: index map is not a per-dim projection; "
                f"footprint passes skipped for this operand"))
        vaxes[op.name] = visit_axes(d)
    ana = LaunchAnalysis(plan=plan, trace=trace, deps=deps, vaxes=vaxes,
                         parallel=par, arbitrary=arb)

    # RPC044 — aliased operands must share block windows exactly.
    for i_in, i_out in plan.input_output_aliases:
        a, b = plan.inputs[i_in], plan.outputs[i_out]
        if (a.block_shape != b.block_shape
                or deps[a.name] != deps[b.name]):
            out.append(Diagnostic(
                "RPC044", subject,
                f"alias {a.name}->{b.name}: block windows differ "
                f"({a.block_shape}/{deps[a.name]} vs "
                f"{b.block_shape}/{deps[b.name]})"))

    # RPC043 (guard sanity) — a guard coordinate outside the grid never fires.
    for e in trace.events:
        if not _valid_guard(e.guard, grid):
            out.append(Diagnostic(
                "RPC043", subject,
                f"{e.ref}: a {e.kind} is guarded at grid coordinate "
                f"{[(p.axis, p.value) for p in e.guard]} outside the grid "
                f"{tuple(grid)}; it can never fire"))

    # RPC040 — every output store must pin each parallel axis its index map
    # drops, else two parallel steps write the same block.
    for op in plan.outputs:
        if any(kind == "other" for kind, _ in deps[op.name]):
            continue
        dropped = [a for a in par
                   if grid[a] > 1 and a not in vaxes[op.name]]
        for e in trace.ref_events(op.name):
            if e.kind != "write" or not _valid_guard(e.guard, grid):
                continue
            pinned = {p.axis for p in e.guard}
            missing = [a for a in dropped if a not in pinned]
            if missing:
                out.append(Diagnostic(
                    "RPC040", subject,
                    f"{op.name}: store may fire on every coordinate of "
                    f"parallel grid axis(es) {missing} whose value its "
                    f"index map ignores — write-write race"))
                break

    # RPC041 — at a chain start (arbitrary coords 0) no scratch/output read
    # may precede an unconditional initializing write.
    for name, kind in trace.ref_kinds.items():
        if kind == "in":
            if any(e.kind == "write" for e in trace.ref_events(name)):
                out.append(Diagnostic(
                    "RPC043", subject,
                    f"{name}: store to an input operand"))
            continue
        initialized = False
        for e in trace.events:
            if e.ref != name or not _valid_guard(e.guard, grid):
                continue
            arb_ok = all(p.value == 0 for p in e.guard if p.axis in arb)
            if e.kind == "write":
                must = arb_ok and all(p.axis in arb for p in e.guard)
                if must:
                    initialized = True
            elif e.kind == "read" and arb_ok and not initialized:
                out.append(Diagnostic(
                    "RPC041", subject,
                    f"{name}: may be read at a chain start before any "
                    f"unconditional initializing write"))
                break

    # RPC042 — the union of written blocks must cover the output array.
    for op in plan.outputs:
        d = deps[op.name]
        if any(kind == "other" for kind, _ in d):
            continue
        bounds = tuple(a // b for a, b in
                       zip(op.array_shape, op.block_shape))
        covered_dims = True
        for dim, (kind_, val) in enumerate(d):
            if kind_ == "const" and bounds[dim] > 1:
                out.append(Diagnostic(
                    "RPC042", subject,
                    f"{op.name}: block dim {dim} is pinned to {val} but the "
                    f"array has {bounds[dim]} blocks along it"))
                covered_dims = False
            elif kind_ == "axis" and grid[val] != bounds[dim]:
                out.append(Diagnostic(
                    "RPC042", subject,
                    f"{op.name}: grid axis {val} visits {grid[val]} of the "
                    f"{bounds[dim]} blocks along dim {dim}"))
                covered_dims = False
        if not covered_dims:
            continue
        writes = [e for e in trace.ref_events(op.name) if e.kind == "write"
                  and _valid_guard(e.guard, grid)]
        vax = sorted(vaxes[op.name])
        n_blocks = _prod(grid[a] for a in vax)
        if not writes:
            out.append(Diagnostic(
                "RPC042", subject, f"{op.name}: no store reaches it"))
            continue
        if any(not any(p.axis in vaxes[op.name] for p in e.guard)
               for e in writes):
            continue                      # some store fires for every block
        if n_blocks <= 65536:
            for coords in itertools.product(*[range(grid[a]) for a in vax]):
                cmap = dict(zip(vax, coords))
                if not any(all(p.axis not in cmap or p.value == cmap[p.axis]
                               for p in e.guard) for e in writes):
                    out.append(Diagnostic(
                        "RPC042", subject,
                        f"{op.name}: block at grid coords {cmap} is never "
                        f"written (every store's guard excludes it)"))
                    break
        else:
            out.append(Diagnostic(
                "RPC046", subject,
                f"{op.name}: {n_blocks} blocks with per-block-guarded "
                f"stores; coverage not enumerable"))

    # RPC043 — accumulation-chain shape.
    scratch_names = [s.name for s in plan.scratch]
    rmw_refs = {e.ref for e in trace.events
                if e.kind == "write" and e.ref in e.sources}
    arb_big = [a for a in arb if grid[a] > 1]
    par_big = [a for a in par if grid[a] > 1]
    if scratch_names and arb_big and par_big \
            and max(par_big) > min(arb_big):
        out.append(Diagnostic(
            "RPC043", subject,
            f"arbitrary (reduction) axes {arb_big} are not an innermost "
            f"suffix below the parallel axes {par_big}: the VMEM scratch "
            f"revisit chain is not contiguous"))
    for name in scratch_names + [o.name for o in plan.outputs]:
        evs = [e for e in trace.ref_events(name)
               if _valid_guard(e.guard, grid)]
        if name not in rmw_refs:
            continue
        chain_len = _prod(grid[a] for a in arb_big)
        for e in evs:
            if e.kind != "write":
                continue
            if e.zero:
                pinned0 = {p.axis for p in e.guard
                           if p.axis in arb and p.value == 0}
                if chain_len > 1 and not all(
                        a in pinned0 for a in arb_big):
                    out.append(Diagnostic(
                        "RPC043", subject,
                        f"{name}: zero-fill write may fire mid-chain "
                        f"(guard {[(p.axis, p.value) for p in e.guard]}), "
                        f"resetting partial sums"))
            elif name in e.sources and e.guard:
                out.append(Diagnostic(
                    "RPC043", subject,
                    f"{name}: the read-modify-write accumulation is guarded "
                    f"({[(p.axis, p.value) for p in e.guard]}); skipped "
                    f"steps break the eq (3) revisit count"))
    # Drain writes of scratch-sourced finals must land on the last chain step.
    for op in plan.outputs:
        for e in trace.ref_events(op.name):
            if e.kind != "write" or not _valid_guard(e.guard, grid):
                continue
            if not (e.sources & set(scratch_names)):
                continue
            for p in e.guard:
                if p.axis in arb and grid[p.axis] > 1 \
                        and p.value != grid[p.axis] - 1:
                    out.append(Diagnostic(
                        "RPC043", subject,
                        f"{op.name}: the drain store fires at reduction "
                        f"coordinate {p.value}, not the chain end "
                        f"{grid[p.axis] - 1}; partial sums would be final"))
    return out, ana


# ------------------------------------------------------- per-launch words
@dataclasses.dataclass(frozen=True)
class RefWords:
    """Real-word traffic of one ref at the two levels the proof separates."""

    name: str
    compute_reads: int          # VMEM->compute (load footprint x sweeps)
    compute_writes: int
    hbm_reads: int              # HBM->VMEM under revisit elision
    hbm_writes: int
    hbm_model: int              # what the first-order model charges
    hbm_equal: bool             # elision-free (== model) vs bounded (<)


def _in_words(ana: LaunchAnalysis, name: str, real: int) -> RefWords:
    grid = ana.grid
    vax = ana.vaxes[name]
    mult = _read_multiplicity(ana.events(name), vax, grid)
    f = per_block_fetches(vax, grid)
    model_f = _prod(grid[a] for a in range(len(grid)) if a not in vax)
    return RefWords(name=name, compute_reads=mult * real, compute_writes=0,
                    hbm_reads=f * real, hbm_writes=0,
                    hbm_model=model_f * real, hbm_equal=f == model_f)


def _out_words(ana: LaunchAnalysis, name: str, real: int) -> RefWords:
    grid = ana.grid
    vax = ana.vaxes[name]
    split, internal = _split_internal(vax, grid)
    wb, live = _out_hbm_counts(ana.events(name), split, internal, grid)
    f = _prod(grid[a] for a in split)
    # Compute-level: step-level RMW count over the revisit (non-visit) axes.
    w, r = _chain_counts(ana.events(name), split + internal, grid)
    return RefWords(name=name, compute_reads=r * real, compute_writes=w * real,
                    hbm_reads=live * real, hbm_writes=wb * real,
                    hbm_model=(2 * f - 1) * real if f > 1 else real,
                    hbm_equal=True)


def _scratch_chain(ana: LaunchAnalysis, name: str, real: int
                   ) -> Tuple[int, int]:
    """(writes, observing reads) in real words over all chains of a scratch
    accumulator; ``real`` is the real-word footprint of one full sweep of
    chains (e.g. the real output activations)."""
    arb_axes = [a for a in ana.arbitrary]
    w, r = _chain_counts(ana.events(name), arb_axes, ana.grid)
    return w * real, r * real


# ------------------------------------------------------------ conv kernel
def _mismatch(subject: str, what: str, derived, model) -> Diagnostic:
    return Diagnostic(
        "RPC045", subject,
        f"{what}: trace-derived {derived} != model {model}")


@dataclasses.dataclass(frozen=True)
class DataflowReport:
    """Scalar certificate for one launch: diagnostics + per-level words."""

    subject: str
    diagnostics: Tuple[Diagnostic, ...]
    words: Dict[str, RefWords]
    sram_reads: int = 0
    sram_writes: int = 0

    @property
    def ok(self) -> bool:
        return not errors(self.diagnostics)


def conv_dataflow(wl: ConvWorkload, schedule: Schedule,
                  subject: Optional[str] = None) -> DataflowReport:
    """Prove `conv2d_psum` under ``schedule`` moves exactly the words
    eqs (2)/(3) charge for ``wl`` — at the accumulator level for any
    (m, n), at the HBM level when retention-free."""
    from repro.check.kernels import check_conv_launch
    from repro.plan.traffic import conv_traffic
    subject = subject or f"dataflow/{wl.name}"
    geo = check_conv_launch(wl, schedule, subject)
    if errors(geo):
        return DataflowReport(subject, tuple(geo), {})
    from repro.kernels.conv2d_psum import conv_launch_plan
    pad = wl.k // 2
    plan = conv_launch_plan(cin=wl.cin, hp=wl.hi + 2 * pad,
                            wp=wl.wi + 2 * pad, cout=wl.cout, kk=wl.k,
                            stride=wl.stride, block_m=schedule.bm,
                            block_n=schedule.bn)
    diags, ana = analyze_launch(plan, subject)
    if ana is None or errors(diags):
        return DataflowReport(subject, tuple(geo + diags), {})
    model = conv_traffic(wl, schedule, exact_iters=True)
    try:
        words = {
            "x": _in_words(ana, "x", wl.in_acts),
            "w": _in_words(ana, "w", wl.cout * (wl.cin // wl.groups)
                           * wl.k * wl.k),
            "out": _out_words(ana, "out", wl.out_acts),
        }
        acc_w, acc_r = _scratch_chain(ana, "acc", wl.out_acts)
    except _Unsupported as exc:
        diags.append(Diagnostic("RPC046", subject, str(exc)))
        return DataflowReport(subject, tuple(geo + diags), {})
    # eq (2): input words = the x operand's VMEM->compute reads.
    if words["x"].compute_reads != int(model.input_words):
        diags.append(_mismatch(subject, "B_i (eq 2) vs x loads",
                               words["x"].compute_reads,
                               int(model.input_words)))
    # eq (3): output words = the accumulator's step-level RMW traffic at the
    # memory that owns it (VMEM here, the far SRAM in the paper's SoC).
    b_o = acc_w if schedule.controller is Controller.ACTIVE else acc_w + acc_r
    if b_o != int(model.output_words):
        diags.append(_mismatch(subject, "B_o (eq 3) vs accumulator RMW",
                               b_o, int(model.output_words)))
    # The meter's SRAM columns, same events.
    sram_r = words["x"].compute_reads + acc_r
    if sram_r != int(model.sram_reads) or acc_w != int(model.sram_writes):
        diags.append(Diagnostic(
            "RPC043", subject,
            f"accumulator RMW counts (reads {sram_r}, writes {acc_w}) "
            f"disagree with the meter ({int(model.sram_reads)}, "
            f"{int(model.sram_writes)})"))
    # HBM side never exceeds the model (elision only removes transfers).
    if words["x"].hbm_reads > int(model.input_words):
        diags.append(_mismatch(subject, "x HBM fetches exceed B_i",
                               words["x"].hbm_reads, int(model.input_words)))
    if words["out"].hbm_writes + words["out"].hbm_reads > int(
            model.output_words):
        diags.append(_mismatch(
            subject, "out HBM traffic exceeds B_o",
            words["out"].hbm_writes + words["out"].hbm_reads,
            int(model.output_words)))
    return DataflowReport(subject, tuple(geo + diags), words,
                          sram_reads=sram_r, sram_writes=acc_w)


# ---------------------------------------------------------- matmul kernel
def _sum_words(parts: Sequence[RefWords]) -> RefWords:
    """One ref's words summed over the launches of a multi-launch kernel."""
    return RefWords(
        name=parts[0].name,
        compute_reads=sum(p.compute_reads for p in parts),
        compute_writes=sum(p.compute_writes for p in parts),
        hbm_reads=sum(p.hbm_reads for p in parts),
        hbm_writes=sum(p.hbm_writes for p in parts),
        hbm_model=sum(p.hbm_model for p in parts),
        hbm_equal=all(p.hbm_equal for p in parts))


def matmul_dataflow(wl: MatmulWorkload, schedule: Schedule,
                    subject: Optional[str] = None) -> DataflowReport:
    """Prove `psum_matmul` under ``schedule`` moves exactly the words
    `gemm_model.matmul_terms` charges, for either controller.

    The passive GEMM is one launch per k-step; every launch is traced and
    the words summed. Its partial sums are the step-0 output plus, at each
    later step, an input read back from HBM and aliased to the output — so
    their (gk, gk - 1) write/read chain is counted from real HBM transfers,
    never from output read-backs (Pallas issues none)."""
    from repro.check.kernels import check_matmul_launch
    from repro.plan.gemm_model import matmul_terms
    ctrl = schedule.controller.value
    subject = subject or f"dataflow/{wl.name}/{ctrl}"
    if wl.groups > 1:
        return DataflowReport(subject, (Diagnostic(
            "RPC046", subject,
            "grouped GEMM: its row tiles map to groups through "
            "scalar-prefetch operands computed on the device; no word-count "
            "proof"),), {})
    geo = check_matmul_launch(wl.m, wl.k, wl.n, schedule, subject)
    if errors(geo):
        return DataflowReport(subject, tuple(geo), {})
    from repro.kernels.psum_matmul import (matmul_launch_plan,
                                           reduction_launches)
    model = matmul_terms(wl, schedule.bm, schedule.bn, schedule.bk,
                         schedule.controller)
    acc_real = wl.m * wl.n
    diags: List[Diagnostic] = []
    parts: Dict[str, List[RefWords]] = {}
    acc_w = acc_r = 0
    for step in range(reduction_launches(wl.k, schedule.bk, ctrl)):
        plan = matmul_launch_plan(m=wl.m, k=wl.k, n=wl.n, bm=schedule.bm,
                                  bn=schedule.bn, bk=schedule.bk,
                                  controller=ctrl, k_step=step)
        d, ana = analyze_launch(plan, subject)
        diags += d
        if ana is None or errors(d):
            return DataflowReport(subject, tuple(geo + diags), {})
        # Real words a launch touches: all of K when active, its k-slab
        # when passive.
        k_real = wl.k if ctrl == "active" else \
            min(wl.k, (step + 1) * schedule.bk) - step * schedule.bk
        try:
            parts.setdefault("x", []).append(
                _in_words(ana, "x", wl.m * k_real))
            parts.setdefault("w", []).append(
                _in_words(ana, "w", k_real * wl.n))
            out = _out_words(ana, "out", acc_real)
            parts.setdefault("out", []).append(out)
            if ctrl == "active":
                acc_w, acc_r = _scratch_chain(ana, "acc", acc_real)
            else:
                acc_w += out.compute_writes
                if step:
                    psums = _in_words(ana, "psums", acc_real)
                    parts.setdefault("psums", []).append(psums)
                    acc_r += psums.compute_reads
        except _Unsupported as exc:
            diags.append(Diagnostic("RPC046", subject, str(exc)))
            return DataflowReport(subject, tuple(geo + diags), {})
    words = {name: _sum_words(p) for name, p in parts.items()}
    if words["x"].compute_reads != int(model.a_reads):
        diags.append(_mismatch(subject, "A reads vs x loads",
                               words["x"].compute_reads,
                               int(model.a_reads)))
    if words["w"].compute_reads != int(model.b_reads):
        diags.append(_mismatch(subject, "B reads vs w loads",
                               words["w"].compute_reads,
                               int(model.b_reads)))
    hbm_c = words["out"].hbm_writes + words["out"].hbm_reads
    if ctrl == "active":
        c_derived = hbm_c
    else:
        c_derived = acc_w + acc_r
        if "psums" in words:
            hbm_c += words["psums"].hbm_reads
        if hbm_c != c_derived:
            diags.append(_mismatch(
                subject, "passive C: HBM round-trips vs the RMW chain",
                hbm_c, c_derived))
    if c_derived != int(model.c_traffic):
        diags.append(_mismatch(subject, "C traffic vs accumulator RMW",
                               c_derived, int(model.c_traffic)))
    meter = (int(model.sram_writes), int(model.sram_reads))
    if (acc_w, acc_r) != meter:
        diags.append(Diagnostic(
            "RPC043", subject,
            f"accumulator RMW counts (writes {acc_w}, reads {acc_r}) "
            f"disagree with the meter {meter}"))
    for nm in ("x", "w"):
        if words[nm].hbm_reads > words[nm].hbm_model:
            diags.append(_mismatch(subject, f"{nm} HBM fetches exceed model",
                                   words[nm].hbm_reads, words[nm].hbm_model))
    return DataflowReport(subject, tuple(geo + diags), words,
                          sram_reads=acc_r, sram_writes=acc_w)


# ----------------------------------------------------------- flash kernel
def flash_dataflow(bh: int, sq: int, skv: int, d: int, bq: int = 128,
                   bk: int = 128, causal: bool = True, q_offset: int = 0,
                   subject: str = "dataflow/flash_attention"
                   ) -> DataflowReport:
    """Pin `flash_attention`'s traffic to its closed form: Q and O cross HBM
    once, K/V once per q block — or once in all when the kv axis has a
    single block, whose index then never changes across the q blocks — and
    the softmax state (acc, m, l) does the (L, L-1) VMEM RMW chain over kv
    blocks: the attention analogue of the paper's active accumulation."""
    from repro.check.kernels import check_flash_launch
    geo = check_flash_launch(bh, sq, skv, d, bq, bk, causal, q_offset,
                             subject)
    if errors(geo):
        return DataflowReport(subject, tuple(geo), {})
    from repro.kernels.flash_attention import flash_launch_plan
    plan = flash_launch_plan(bh=bh, sq=sq, skv=skv, d=d, bq=bq, bk=bk,
                             causal=causal, q_offset=q_offset)
    diags, ana = analyze_launch(plan, subject)
    if ana is None or errors(diags):
        return DataflowReport(subject, tuple(geo + diags), {})
    _, gq, gk = plan.grid
    q_real, kv_real, o_real = bh * sq * d, bh * skv * d, bh * sq * d
    try:
        words = {
            "q": _in_words(ana, "q", q_real),
            "k": _in_words(ana, "k", kv_real),
            "v": _in_words(ana, "v", kv_real),
            "out": _out_words(ana, "out", o_real),
        }
        acc_w, acc_r = _scratch_chain(ana, "acc", o_real)
    except _Unsupported as exc:
        diags.append(Diagnostic("RPC046", subject, str(exc)))
        return DataflowReport(subject, tuple(geo + diags), {})
    # K/V visit (head, kv block): they are re-fetched for each q block only
    # while the kv axis, their innermost visit axis, actually moves.
    kv_fetches = gq if gk > 1 else 1
    expect = {
        "q hbm": (words["q"].hbm_reads, q_real),
        "k hbm": (words["k"].hbm_reads, kv_fetches * kv_real),
        "v hbm": (words["v"].hbm_reads, kv_fetches * kv_real),
        "out hbm": (words["out"].hbm_writes + words["out"].hbm_reads,
                    o_real),
        "softmax-state RMW": ((acc_w, acc_r),
                              (gk * o_real, (gk - 1) * o_real)),
    }
    for what, (derived, want) in expect.items():
        if derived != want:
            diags.append(_mismatch(subject, what, derived, want))
    return DataflowReport(subject, tuple(geo + diags), words,
                          sram_reads=acc_r, sram_writes=acc_w)


# ------------------------------------------------- space-level certificates
@dataclasses.dataclass(frozen=True)
class SpaceCertificate:
    """One certified search space: every admitted candidate's model word
    counts proven equal to the trace-derived counting formulas."""

    subject: str
    kind: str
    controller: str
    n_candidates: int
    n_equal_hbm: int            # candidates with HBM == model on every ref
    n_bounded_hbm: int          # candidates where retention beats the model
    diagnostics: Tuple[Diagnostic, ...]

    @property
    def ok(self) -> bool:
        return not errors(self.diagnostics)


def _degeneracy_probes(*flags: np.ndarray) -> List[int]:
    """First candidate index of every present degeneracy class (which grid
    extents are 1) — one structural trace per class certifies them all."""
    sig = np.zeros(flags[0].shape, dtype=np.int64)
    for i, f in enumerate(flags):
        sig |= f.astype(np.int64) << i
    return [int(np.argmax(sig == s)) for s in np.unique(sig)]


def certify_conv_space(wl: ConvWorkload, budget: Optional[int] = None,
                       controller: "Controller | str" = Controller.PASSIVE,
                       space=None) -> SpaceCertificate:
    """Certify every candidate a conv search space admits for ``wl``: the
    traced kernel structure (one trace per degeneracy class) plus the
    vectorized counting formulas against `conv_terms`."""
    from repro.plan.conv_model import conv_terms
    from repro.plan.space import ConvExactSpace
    controller = Controller.coerce(controller)
    subject = f"certify/{wl.name}/{controller.value}"
    if budget is None:
        from repro.plan.api import default_budget
        budget = default_budget(wl)
    if space is None:
        space = ConvExactSpace()
    same_padded = ((wl.hi + 2 * (wl.k // 2) - wl.k) // wl.stride + 1 == wl.ho
                   and (wl.wi + 2 * (wl.k // 2) - wl.k) // wl.stride + 1
                   == wl.wo)
    if wl.groups != 1 or not same_padded:
        why = (f"groups={wl.groups}" if wl.groups != 1
               else "not 'same'-padded")
        return SpaceCertificate(subject, "conv", controller.value, 0, 0, 0, (
            Diagnostic("RPC046", subject,
                       f"{why}: conv2d_psum never launches this node; "
                       f"space not kernel-certifiable"),))
    cands = space(wl, int(budget))
    m = np.asarray(cands.bm, np.int64)
    n = np.asarray(cands.bn, np.int64)
    bm_eff = np.maximum(1, np.minimum(m, wl.cin))
    bn_eff = np.maximum(1, np.minimum(n, wl.cout))
    n_ci = -(-wl.cin // bm_eff)
    n_co = -(-wl.cout // bn_eff)
    diags: List[Diagnostic] = []
    # One full scalar proof per degeneracy class of the grid.
    for i in _degeneracy_probes(n_ci > 1, n_co > 1):
        rep = conv_dataflow(
            wl, Schedule(kind="conv", bm=int(m[i]), bn=int(n[i]),
                         controller=controller),
            subject=f"{subject}/m={int(m[i])},n={int(n[i])}")
        diags += list(rep.diagnostics)
    if errors(diags):
        return SpaceCertificate(subject, "conv", controller.value,
                                len(cands), 0, 0, tuple(diags))
    # Vectorized counting formulas (coefficients fixed by the traced
    # structure: one x load per step, an (L, L-1) accumulator chain) vs the
    # model, for every candidate.
    b_i_d = (wl.in_acts * n_co).astype(np.float64)
    acc_w = (n_ci * wl.out_acts).astype(np.float64)
    acc_r = ((n_ci - 1) * wl.out_acts).astype(np.float64)
    b_o_d = acc_w if controller is Controller.ACTIVE else acc_w + acc_r
    model = conv_terms(wl, m, n, controller)
    b_i_m, b_o_m = model.b_i, model.b_o
    for name, dv, mv in (("B_i (eq 2)", b_i_d, b_i_m),
                         ("B_o (eq 3)", b_o_d, b_o_m)):
        bad = np.nonzero(dv != mv)[0]
        if bad.size:
            i = int(bad[0])
            diags.append(_mismatch(
                f"{subject}/m={int(m[i])},n={int(n[i])}",
                f"{name} over the space ({bad.size} candidate(s))",
                dv[i], mv[i]))
    # HBM level: equal when retention-free, strictly bounded otherwise.
    hbm_x = np.where(n_ci > 1, wl.in_acts * n_co, wl.in_acts)
    over = np.nonzero(hbm_x > b_i_m)[0]
    if over.size:
        i = int(over[0])
        diags.append(_mismatch(f"{subject}/m={int(m[i])},n={int(n[i])}",
                               "x HBM fetches exceed B_i", int(hbm_x[i]),
                               b_i_m[i]))
    x_eq = hbm_x == b_i_m
    out_eq = (wl.out_acts == b_o_m)          # VMEM acc: HBM out = out_acts
    full_eq = x_eq & out_eq
    return SpaceCertificate(
        subject, "conv", controller.value, len(cands),
        int(full_eq.sum()), int(len(cands) - full_eq.sum()), tuple(diags))


def certify_matmul_space(wl: MatmulWorkload, budget: Optional[int] = None,
                         controller: "Controller | str" = Controller.ACTIVE,
                         space=None) -> SpaceCertificate:
    """Certify every VMEM-admitted candidate of a GEMM block space against
    `matmul_terms`, for either controller."""
    from repro.plan.dse import VmemBudget
    from repro.plan.gemm_model import DEFAULT_VMEM_BUDGET, matmul_terms
    from repro.plan.space import AlignedBlockSpace
    controller = Controller.coerce(controller)
    subject = f"certify/{wl.name}/{controller.value}"
    if budget is None:
        budget = DEFAULT_VMEM_BUDGET
    if space is None:
        space = AlignedBlockSpace()
    if wl.groups > 1:
        return SpaceCertificate(subject, "matmul", controller.value, 0, 0, 0, (
            Diagnostic("RPC046", subject,
                       "grouped GEMM: its row tiles map to groups on the "
                       "device; space not kernel-certifiable"),))
    cands = space(wl, int(budget))
    admitted = VmemBudget()(wl, cands, int(budget))
    bm = np.asarray(cands.bm, np.int64)[admitted]
    bn = np.asarray(cands.bn, np.int64)[admitted]
    bk = np.asarray(cands.bk, np.int64)[admitted]
    if bm.size == 0:
        return SpaceCertificate(subject, "matmul", controller.value, 0, 0, 0, (
            Diagnostic("RPC046", subject,
                       "no candidate fits the VMEM budget"),))
    gi = -(-wl.m // bm)
    gj = -(-wl.n // bn)
    gk = -(-wl.k // bk)
    diags: List[Diagnostic] = []
    for i in _degeneracy_probes(gi > 1, gj > 1, gk > 1):
        rep = matmul_dataflow(
            wl, Schedule(kind="matmul", bm=int(bm[i]), bn=int(bn[i]),
                         bk=int(bk[i]), controller=controller),
            subject=f"{subject}/{int(bm[i])}x{int(bn[i])}x{int(bk[i])}")
        diags += list(rep.diagnostics)
    if errors(diags):
        return SpaceCertificate(subject, "matmul", controller.value,
                                int(bm.size), 0, 0, tuple(diags))
    t = matmul_terms(wl, bm, bn, bk, controller)
    a_d = (gj * (wl.m * wl.k)).astype(np.float64)
    b_d = (gi * (wl.k * wl.n)).astype(np.float64)
    acc = wl.m * wl.n
    if controller is Controller.ACTIVE:
        c_d = np.full_like(a_d, float(acc))
    else:     # gk launches: gk psum writes, gk - 1 read-backs, all via HBM
        c_d = ((2 * gk - 1) * acc).astype(np.float64)
    for name, dv, mv in (("A reads", a_d, t.a_reads),
                         ("B reads", b_d, t.b_reads),
                         ("C traffic", c_d, t.c_traffic)):
        bad = np.nonzero(dv != mv)[0]
        if bad.size:
            i = int(bad[0])
            diags.append(_mismatch(
                f"{subject}/{int(bm[i])}x{int(bn[i])}x{int(bk[i])}",
                f"{name} over the space ({bad.size} candidate(s))",
                dv[i], mv[i]))
    # Retention: an operand's block is re-fetched only when an *effective*
    # visited axis sits at or inside its innermost varying axis.
    if controller is Controller.ACTIVE:       # grid (gm, gn, gk)
        x_eq = (gk > 1) | (gj == 1)           # x block (i, kk) vs inner j
        w_eq = (gj > 1) | (gk > 1) | (gi == 1)
        c_eq = np.ones_like(x_eq, dtype=bool)  # out crosses HBM once = model
    else:                                     # gk launches, grid (gm, gn)
        x_eq = (gj == 1)                      # x block (i, kk) constant in j
        w_eq = (gj > 1) | (gi == 1)           # w block (kk, j) re-fetched/i
        c_eq = np.ones_like(x_eq, dtype=bool)  # every psum crosses HBM
    full_eq = x_eq & w_eq & c_eq
    return SpaceCertificate(
        subject, "matmul", controller.value, int(bm.size),
        int(full_eq.sum()), int(bm.size - full_eq.sum()), tuple(diags))


# ------------------------------------------------------ network-level gate
@functools.lru_cache(maxsize=512)
def _conv_report_cached(cin, hi, wi, cout, k, stride, ho, wo, groups,
                        bm, bn, controller) -> Tuple[Diagnostic, ...]:
    wl = ConvWorkload(name="node", cin=cin, cout=cout, k=k, wi=wi, hi=hi,
                      wo=wo, ho=ho, stride=stride, groups=groups)
    sched = Schedule(kind="conv", bm=bm, bn=bn,
                     controller=Controller.coerce(controller))
    return conv_dataflow(wl, sched).diagnostics


def check_network_dataflow(graph, schedules) -> List[Diagnostic]:
    """Dataflow-certify every conv node `run_network_kernels` would launch
    (results cached per distinct launch geometry)."""
    if hasattr(schedules, "schedules"):
        schedules = schedules.schedules
    out: List[Diagnostic] = []
    for node in graph.workload_nodes:
        wl = node.workload
        if not isinstance(wl, ConvWorkload):
            continue
        sched = schedules.get(node.name) if schedules is not None else None
        if sched is None or sched.kind != "conv":
            continue            # geometry preflight already reports RPC033
        found = _conv_report_cached(
            wl.cin, wl.hi, wl.wi, wl.cout, wl.k, wl.stride, wl.ho, wl.wo,
            wl.groups, sched.bm, sched.bn, sched.controller.value)
        out += [dataclasses.replace(d, subject=node.name) for d in found]
    return out


@functools.lru_cache(maxsize=256)
def _flash_report_cached(bh, sq, skv, d, bq, bk, causal, q_offset
                         ) -> Tuple[Diagnostic, ...]:
    return flash_dataflow(bh, sq, skv, d, bq, bk, causal, q_offset
                          ).diagnostics


def preflight_flash_dataflow(bh: int, sq: int, skv: int, d: int,
                             bq: int = 128, bk: int = 128,
                             causal: bool = True, q_offset: int = 0) -> None:
    """Raise `CheckError` if the flash launch fails its dataflow proofs
    (cached per geometry; called from the kernel's preflight)."""
    raise_on_error(_flash_report_cached(bh, sq, skv, d, bq, bk, causal,
                                        q_offset),
                   context="flash_attention dataflow proof failed")


# ------------------------------------------------------------- CLI sweep
def check_dataflow(nets: Sequence[str] = ("resnet18",),
                   controllers: Sequence[str] = ("passive", "active"),
                   ) -> Tuple[List[Diagnostic], dict]:
    """The ``python -m repro.check --dataflow`` sweep.

    Certifies (1) one representative launch of each of the four kernels,
    (2) the full `ConvExactSpace` of every conv layer of each net under both
    controllers — every admitted candidate, not just the argmin — and
    (3) an `AlignedBlockSpace` GEMM under both controllers. Returns
    (diagnostics, {subject: seconds}) like `check_plans`.
    """
    from repro.obs.trace import Stopwatch
    from repro.plan.workload import conv_workloads
    diags: List[Diagnostic] = []
    timings: dict = {}
    counts: dict = {}

    with Stopwatch("check.dataflow/kernels", cat="check") as sw:
        rep = conv_dataflow(
            ConvWorkload(name="conv64", cin=64, cout=128, k=3, wi=16, hi=16,
                         wo=16, ho=16),
            Schedule(kind="conv", bm=32, bn=32,
                     controller=Controller.PASSIVE))
        diags += list(rep.diagnostics)
        for ctrl in ("active", "passive"):
            rep = matmul_dataflow(
                MatmulWorkload(m=512, n=512, k=1024),
                Schedule(kind="matmul", bm=128, bn=128, bk=256,
                         controller=Controller.coerce(ctrl)))
            diags += list(rep.diagnostics)
        diags += list(flash_dataflow(2, 256, 256, 64).diagnostics)
        diags += list(flash_dataflow(2, 1, 256, 64, bq=1,
                                     q_offset=255).diagnostics)
    timings["kernels"] = sw.s

    for net in nets:
        with Stopwatch(f"check.dataflow/space/{net}", cat="check") as sw:
            n_cand = n_eq = 0
            for wl in conv_workloads(net):
                launchable = (wl.groups == 1 and
                              (wl.hi + 2 * (wl.k // 2) - wl.k) // wl.stride
                              + 1 == wl.ho)
                if not launchable:
                    continue  # the runner never launches it; geometry reports
                for ctrl in controllers:
                    cert = certify_conv_space(wl, controller=ctrl)
                    diags += [d for d in cert.diagnostics]
                    n_cand += cert.n_candidates
                    n_eq += cert.n_equal_hbm
        timings[f"space/{net}"] = sw.s
        counts[net] = (n_cand, n_eq)

    with Stopwatch("check.dataflow/space/gemm", cat="check") as sw:
        for ctrl in controllers:
            cert = certify_matmul_space(
                MatmulWorkload(m=4096, n=4096, k=4096), controller=ctrl)
            diags += list(cert.diagnostics)
    timings["space/gemm"] = sw.s
    timings["_certified"] = sum(c for c, _ in counts.values())
    return diags, timings
