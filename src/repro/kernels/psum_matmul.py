"""Blocked matmul with partial-sum accumulation — the paper's technique at the
VMEM level.

Two grid schedules compute the identical GEMM but move partial sums through
different levels of the memory hierarchy:

* ``active``  — grid (gm, gn, gk), reduction innermost. The fp32 accumulator
  tile lives in a VMEM scratch buffer that is *revisited* across the k-steps:
  the addition happens at the memory closest to the data and the HBM output
  traffic is a single bf16 write of C. This is the TPU-native analogue of the
  paper's active memory controller (the controller that performs
  read-update-write locally), including the fused activation epilogue
  (the paper's ACT command).

* ``passive`` — reduction outermost: one launch per k-step, each over the
  (gm, gn) output grid. Step 0 writes the fp32 partial sums to HBM; every
  later step reads them back as an input aliased to its output, adds its
  contribution and writes them again — exactly the paper's "partial sums must
  be read before being updated". This is the baseline whose traffic the paper
  (and our ``repro.plan.gemm_model``) charges at ``(2*gk - 1) * M * N``
  words, and every one of those words really crosses HBM: a Pallas TPU
  kernel never fetches an output block, so a single launch with the
  reduction outermost could not read its partial sums back.

Schedules come from the unified planner: pass ``schedule=`` a
``repro.plan.Schedule`` (e.g. ``plan.plan(MatmulWorkload(...)).schedule``) —
the integer-exact generalization of the paper's eq (7).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch

ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def _active_kernel(x_ref, w_ref, o_ref, acc_ref, *, act: str, n_k: int):
    """Reduction-innermost: acc tile stays resident in VMEM across k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        # The paper's ACT command: activation applied at the accumulator,
        # no extra HBM round-trip.
        o_ref[...] = ACTIVATIONS[act](acc_ref[...]).astype(o_ref.dtype)


def _passive_first_kernel(x_ref, w_ref, o_ref):
    """Passive step 0: the first partial sums go straight out to HBM."""
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)


def _passive_kernel(x_ref, w_ref, p_ref, o_ref):
    """Passive step k > 0: read the partial sums back, update, spill again."""
    o_ref[...] = p_ref[...] + jnp.dot(x_ref[...], w_ref[...],
                                      preferred_element_type=jnp.float32)


def reduction_launches(k: int, bk: int, controller: str) -> int:
    """How many launches one GEMM takes: one, or one per k-step (passive)."""
    return -(-k // bk) if controller == "passive" else 1


def matmul_launch_plan(*, m: int, k: int, n: int, bm: int, bn: int, bk: int,
                       controller: str = "active", act: str = "none",
                       dtype=None, k_step: int = 0) -> launch.LaunchPlan:
    """The launch `psum_matmul` executes for one controller, from plain
    integers — shapes padded to block multiples exactly as the entry pads.
    A passive GEMM is `reduction_launches` launches; ``k_step`` picks one."""
    mp = m + (-m) % bm
    kp = k + (-k) % bk
    np_ = n + (-n) % bn
    gm, gn, gk = mp // bm, np_ // bn, kp // bk
    if controller == "active":
        return launch.LaunchPlan(
            name="psum_matmul/active",
            grid=(gm, gn, gk),
            body=functools.partial(_active_kernel, act=act, n_k=gk),
            inputs=(
                launch.OperandPlan("x", (mp, kp), (bm, bk),
                                   lambda i, j, kk: (i, kk), elem_bytes=2),
                launch.OperandPlan("w", (kp, np_), (bk, bn),
                                   lambda i, j, kk: (kk, j), elem_bytes=2),
            ),
            outputs=(
                launch.OperandPlan("out", (mp, np_), (bm, bn),
                                   lambda i, j, kk: (i, j), dtype=dtype,
                                   elem_bytes=2),
            ),
            scratch=(launch.ScratchPlan("acc", (bm, bn), jnp.float32),),
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )
    if controller == "passive":
        if not 0 <= k_step < gk:
            raise ValueError(f"k_step {k_step} outside the {gk} k-steps")
        inputs = (
            launch.OperandPlan("x", (mp, kp), (bm, bk),
                               lambda i, j: (i, k_step), elem_bytes=2),
            launch.OperandPlan("w", (kp, np_), (bk, bn),
                               lambda i, j: (k_step, j), elem_bytes=2),
        )
        if k_step:
            inputs += (launch.OperandPlan("psums", (mp, np_), (bm, bn),
                                          lambda i, j: (i, j)),)
        return launch.LaunchPlan(
            name=f"psum_matmul/passive/k{k_step}",
            grid=(gm, gn),
            body=_passive_kernel if k_step else _passive_first_kernel,
            inputs=inputs,
            outputs=(
                launch.OperandPlan("out", (mp, np_), (bm, bn),
                                   lambda i, j: (i, j), dtype=jnp.float32),
            ),
            dimension_semantics=("parallel", "parallel"),
            input_output_aliases=((2, 0),) if k_step else (),
        )
    raise ValueError(controller)


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("schedule", "bm", "bn", "bk",
                                             "act", "controller", "interpret",
                                             "out_dtype"))
def psum_matmul(x: jax.Array, w: jax.Array, *, schedule=None, bm: int = 256,
                bn: int = 256, bk: int = 256, act: str = "none",
                controller: str = "active", interpret: launch.Interpret = None,
                out_dtype=None) -> jax.Array:
    """C = act(x @ w) with explicit partial-sum schedule.

    x: (M, K), w: (K, N). Shapes are zero-padded to block multiples; the
    result is sliced back. Pass a ``repro.plan.Schedule`` (kind="matmul") as
    ``schedule=`` — its blocks and controller override the raw ints; or set
    ``bm``/``bn``/``bk`` and ``controller`` directly (legacy interface).
    """
    if schedule is not None:
        if schedule.kind != "matmul":
            raise ValueError(f"psum_matmul needs a matmul schedule, got {schedule}")
        bm, bn, bk = schedule.bm, schedule.bn, schedule.bk
        controller = schedule.controller.value
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if out_dtype is None:
        out_dtype = x.dtype
    xp = _pad_to(x, bm, bk)
    wp = _pad_to(w, bk, bn)
    kw = dict(m=m, k=k, n=n, bm=bm, bn=bn, bk=bk, controller=controller,
              act=act)
    if controller == "active":
        out = launch.run(matmul_launch_plan(dtype=out_dtype, **kw), xp, wp,
                         interpret=interpret)
    else:
        psums = launch.run(matmul_launch_plan(**kw), xp, wp,
                           interpret=interpret)
        for step in range(1, reduction_launches(k, bk, controller)):
            psums = launch.run(matmul_launch_plan(k_step=step, **kw), xp, wp,
                               psums, interpret=interpret)
        # Passive engines apply the activation after reading the final psums
        # back — an extra HBM round-trip the active schedule fuses away.
        out = ACTIVATIONS[act](psums).astype(out_dtype)
    return out[:m, :n]


def hbm_traffic_bytes(m: int, n: int, k: int, *, bm: int, bn: int, bk: int,
                      controller: str, in_bytes: int = 2,
                      out_bytes: int = 2) -> float:
    """Analytical HBM traffic of the schedules above — the dtype-weighted
    byte model lives in one place (`repro.plan.gemm_model`); this is a view
    of it, not a second copy (passive spills are fp32 accumulators)."""
    from repro.plan.gemm_model import MatmulBlocks, traffic_model_bytes
    return traffic_model_bytes(m, n, k, MatmulBlocks(bm, bn, bk), controller,
                               in_bytes=in_bytes, out_bytes=out_bytes,
                               acc_bytes=4)
