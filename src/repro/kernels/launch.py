"""`LaunchPlan`: a Pallas launch as inspectable data.

Every kernel in ``repro.kernels`` picks a grid, BlockSpecs, scratch shapes and
dimension semantics; until now that geometry lived only inside the
``pl.pallas_call`` expression, where nothing but Mosaic could see it. A
`LaunchPlan` lifts the whole launch into a frozen dataclass — grid, per-operand
(array shape, block shape, index map), scratch buffers, semantics, and the
kernel *body* itself (with its static keywords bound) — so that

  * the kernels execute it (`run` builds the one ``pl.pallas_call`` in the
    repo from a plan — lint rule RPL103 forbids direct calls elsewhere), and
  * the static verifier reads it (`repro.check.footprint` traces ``body``
    abstractly and `repro.check.dataflow` proves race-freedom, coverage and
    word-count equivalence from the same object that executes).

Builders (`conv_launch_plan` / `matmul_launch_plan` / `flash_launch_plan` /
`grouped_matmul_launch_plan`) take plain integers, apply exactly the
clamping/padding their kernel applies, and are therefore callable from the
checker without any arrays in hand.

A plan may carry scalar-prefetch operands (``prefetch``): small int32 arrays
that Pallas copies to SMEM before the grid starts, which the index maps and
the body read. Their block indices then depend on device data, which the
static checker cannot see (`repro.check` reports such a launch as unchecked
where it would otherwise prove).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple, Union

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.plan.gemm_model import VMEM_LIMIT_BYTES

IndexMap = Callable[..., Tuple[int, ...]]


@dataclasses.dataclass(frozen=True)
class OperandPlan:
    """One pallas_call operand: full (padded) array, its block, its map."""

    name: str
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    index_map: IndexMap
    dtype: Any = None            # jnp dtype for out_shape; None = caller's
    elem_bytes: int = 4

    @property
    def block_words(self) -> int:
        n = 1
        for d in self.block_shape:
            n *= d
        return n


@dataclasses.dataclass(frozen=True)
class ScratchPlan:
    """One VMEM scratch buffer."""

    name: str
    shape: Tuple[int, ...]
    dtype: Any = None            # jnp dtype; None = fp32 at run()

    @property
    def words(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n


@dataclasses.dataclass(frozen=True)
class PrefetchPlan:
    """One scalar-prefetch operand: an int32 array held in SMEM. Index maps
    receive these refs after the grid indices; the body receives them before
    the inputs."""

    name: str
    shape: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class LaunchPlan:
    """A complete, executable-and-checkable Pallas launch description.

    ``body`` is the kernel function with every static keyword already bound
    (``functools.partial``); its positional refs arrive in the pallas order:
    scalar-prefetch operands, inputs, then outputs, then scratch.
    ``input_output_aliases`` index ``inputs`` (not counting ``prefetch``).
    """

    name: str
    grid: Tuple[int, ...]
    body: Callable[..., None]
    inputs: Tuple[OperandPlan, ...]
    outputs: Tuple[OperandPlan, ...]
    scratch: Tuple[ScratchPlan, ...] = ()
    dimension_semantics: Tuple[str, ...] = ()
    input_output_aliases: Tuple[Tuple[int, int], ...] = ()
    prefetch: Tuple[PrefetchPlan, ...] = ()

    @property
    def operands(self) -> Tuple[OperandPlan, ...]:
        return self.inputs + self.outputs

    @property
    def parallel_axes(self) -> Tuple[int, ...]:
        return tuple(i for i, s in enumerate(self.dimension_semantics)
                     if s == "parallel")

    @property
    def arbitrary_axes(self) -> Tuple[int, ...]:
        return tuple(i for i, s in enumerate(self.dimension_semantics)
                     if s != "parallel")


# None: decided by `run` (compiled on a TPU backend, interpreted elsewhere).
Interpret = Optional[Union[bool, pltpu.InterpretParams]]


def run(plan: LaunchPlan, *operands: jax.Array,
        interpret: Interpret = None) -> jax.Array:
    """Execute a single-output `LaunchPlan` — the one place in the repo that
    invokes ``pl.pallas_call`` (RPL103 keeps it that way).

    ``operands`` are the plan's scalar-prefetch arrays (if any), then its
    inputs. ``interpret`` is where the library default is decided: ``None``
    compiles with Mosaic when JAX's default backend is a TPU and runs the
    Pallas interpreter otherwise. ``False`` always compiles (and fails
    off-TPU), ``True`` or a ``pltpu.InterpretParams`` always interprets.
    Every launch requests the one VMEM limit the planner budgets against."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_pre = len(plan.prefetch)
    if len(operands) != n_pre + len(plan.inputs):
        raise ValueError(f"{plan.name}: got {len(operands)} operands, plan "
                         f"has {n_pre} prefetch + {len(plan.inputs)} inputs")
    if len(plan.outputs) != 1:
        raise NotImplementedError("run() supports single-output plans")
    out = plan.outputs[0]
    out_dtype = out.dtype if out.dtype is not None else operands[n_pre].dtype
    kwargs: dict[str, Any] = {}
    if plan.input_output_aliases:
        kwargs["input_output_aliases"] = {
            i + n_pre: o for i, o in plan.input_output_aliases}
    import jax.numpy as jnp
    specs: dict[str, Any] = dict(
        grid=plan.grid,
        in_specs=[pl.BlockSpec(op.block_shape, op.index_map)
                  for op in plan.inputs],
        out_specs=pl.BlockSpec(out.block_shape, out.index_map),
        scratch_shapes=[
            pltpu.VMEM(s.shape, s.dtype if s.dtype is not None
                       else jnp.float32) for s in plan.scratch])
    if n_pre:
        specs = {"grid_spec": pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre, **specs)}
    return pl.pallas_call(
        plan.body,
        out_shape=jax.ShapeDtypeStruct(out.array_shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=plan.dimension_semantics,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        **specs,
        **kwargs,
    )(*operands)
