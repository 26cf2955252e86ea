"""Typed schedule vocabulary for the unified planning API.

`Strategy` and `Controller` type the ``strategy=``/``controller=``
arguments; both coerce from their string values.

`Schedule` is the single execution-schedule type consumed by every backend:
the AMC simulator, the Pallas kernels, and the traffic model. It subsumes

  * the paper's channel `Partition` (m input maps x n output maps, eq 1), and
  * the TPU `MatmulBlocks` (bm, bn, bk) VMEM tiling,

with one field convention: ``bm``/``bn`` are the two explicit block sizes of a
workload's partitioned axes and ``bk`` is the extra reduction block a GEMM has
(0 for convs, whose reduction axis *is* ``bm`` — the paper never tiles space).
"""

from __future__ import annotations

import dataclasses
import enum


class Strategy(enum.Enum):
    """Partition-selection policy (paper Section II + beyond-paper searches)."""

    MAX_INPUT = "max_input"            # maximize m first (paper baseline 1)
    MAX_OUTPUT = "max_output"          # maximize n first (paper baseline 2)
    EQUAL = "equal"                    # m = n = sqrt(P)/K  (paper baseline 3)
    PAPER_OPT = "paper_opt"            # eq (7) closed form, snapped to factors
    EXACT_OPT = "exact_opt"            # integer-exact search (beyond paper)
    FIRST_ORDER = "first_order"        # closed-form block rule (GEMM eq-7 analogue)
    EXHAUSTIVE_VMEM = "exhaustive_vmem"  # exact search over aligned VMEM blocks

    @classmethod
    def coerce(cls, value: "Strategy | str") -> "Strategy":
        if isinstance(value, cls):
            return value
        try:
            return cls(value)
        except ValueError:
            raise ValueError(
                f"unknown strategy {value!r}; known: {[s.value for s in cls]}"
            ) from None


class Controller(enum.Enum):
    """Memory-controller behaviour for partial sums (paper Section III)."""

    PASSIVE = "passive"   # read-before-update crosses the interconnect
    ACTIVE = "active"     # in-controller add; only new psums cross the bus

    @classmethod
    def coerce(cls, value: "Controller | str") -> "Controller":
        if isinstance(value, cls):
            return value
        try:
            return cls(value)
        except ValueError:
            raise ValueError(
                f"unknown controller {value!r}; known: {[c.value for c in cls]}"
            ) from None


@dataclasses.dataclass(frozen=True)
class Partition:
    """Channel partition: m input maps x n output maps per iteration.

    The paper's own vocabulary (``core.amc`` executes one); a `Schedule`
    carries the same blocks and round-trips via ``Schedule.from_partition`` /
    ``Schedule.as_partition``.
    """

    m: int
    n: int

    def macs(self, k: int) -> int:
        return k * k * self.m * self.n


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One execution schedule, for either workload kind.

    kind == "conv":    bm = m (input-map block, the reduction axis),
                       bn = n (output-map block), bk = 0 (space untiled).
    kind == "matmul":  bm x bn output tile, bk reduction tile.
    """

    kind: str                                  # "conv" | "matmul"
    bm: int
    bn: int
    bk: int = 0
    controller: Controller = Controller.PASSIVE

    def __post_init__(self):
        object.__setattr__(self, "controller",
                           Controller.coerce(self.controller))
        if self.kind not in ("conv", "matmul"):
            raise ValueError(f"unknown schedule kind {self.kind!r}")
        if self.bm < 1 or self.bn < 1 or self.bk < 0:
            raise ValueError(f"non-positive blocks in {self}")
        if self.kind == "matmul" and self.bk < 1:
            raise ValueError(f"matmul schedule needs a reduction block: {self}")

    # ---------------------------------------------------------- conv view
    @property
    def m(self) -> int:
        """The paper's m (input feature maps per iteration)."""
        return self.bm

    @property
    def n(self) -> int:
        """The paper's n (output feature maps per iteration)."""
        return self.bn

    def macs(self, k: int) -> int:
        """eq (1) left-hand side: K^2 * m * n."""
        return k * k * self.bm * self.bn

    @classmethod
    def from_partition(cls, part: Partition,
                       controller: Controller | str = Controller.PASSIVE) -> "Schedule":
        return cls(kind="conv", bm=part.m, bn=part.n, bk=0,
                   controller=Controller.coerce(controller))

    def as_partition(self) -> Partition:
        if self.kind != "conv":
            raise ValueError(f"not a conv schedule: {self}")
        return Partition(m=self.bm, n=self.bn)

    # -------------------------------------------------------- matmul view
    @classmethod
    def from_blocks(cls, blocks, controller: Controller | str = Controller.ACTIVE
                    ) -> "Schedule":
        """From a ``gemm_model.MatmulBlocks`` (duck-typed)."""
        return cls(kind="matmul", bm=blocks.bm, bn=blocks.bn, bk=blocks.bk,
                   controller=Controller.coerce(controller))

    def as_blocks(self):
        if self.kind != "matmul":
            raise ValueError(f"not a matmul schedule: {self}")
        from repro.plan.gemm_model import MatmulBlocks
        return MatmulBlocks(bm=self.bm, bn=self.bn, bk=self.bk)

    def vmem_bytes(self, in_bytes: int | None = None,
                   acc_bytes: int | None = None,
                   double_buffer: bool = True, *, workload=None) -> int:
        """VMEM footprint of a matmul schedule (input blocks double-buffered).

        Element widths resolve in order: explicit ``in_bytes``/``acc_bytes``
        argument > the ``workload``'s dtype sizes (pass the planned
        `MatmulWorkload` so fp32/int8 GEMMs report their true footprint) >
        the bf16-operand/fp32-accumulator defaults.
        """
        if workload is not None:
            in_bytes = workload.in_bytes if in_bytes is None else in_bytes
            acc_bytes = workload.acc_bytes if acc_bytes is None else acc_bytes
        in_bytes = 2 if in_bytes is None else in_bytes
        acc_bytes = 4 if acc_bytes is None else acc_bytes
        return self.as_blocks().vmem_bytes(in_bytes, acc_bytes, double_buffer)
