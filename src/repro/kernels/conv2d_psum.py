"""Channel-partitioned conv2d — the paper's exact loop nest on the MXU.

The paper's accelerator processes m input maps x n output maps per iteration
(eq 1: K^2*m*n <= P). Here the grid is (cout_blocks x cin_blocks) with the
input-channel (reduction) dimension innermost; the n-channel output tile is a
VMEM-resident fp32 accumulator revisited across cin blocks (the active memory
controller), with the activation fused into the final step (ACT command).

Spatial dims are not tiled (the paper never tiles space); each grid step does
a K*K static unroll of (n x m) @ (m x L) MXU matmuls over shifted input
views — the TPU-native formulation of `p_sum[co] += f_in * wt`.

Layout: callers pass x (Cin, Hp, Wp) spatially padded and w (Cout, Cin, K, K)
OIHW — the paper's indexing. The wrapper rearranges both so that the kernel
body does no relayout (Mosaic refuses in-kernel reshapes of the spatial
dims):

  * x becomes ``stride**2`` phase planes, phase (py, px) = x[:, py::s, px::s],
    each flattened row-major with row length Wq = ceil(Wp / s). Tap (ky, kx)
    of output pixel (oy, ox) is then phase (ky % s, kx % s) at flat index
    ``oy * Wq + ox + (ky // s) * Wq + kx // s`` — one static lane offset per
    tap, the same for every pixel.
  * The accumulator and output are flat (n, L) with L >= Ho * Wq: rows are
    computed Wq wide and the wrapper drops the Wq - Wo wrapped columns.
  * Weights become (K*K, n, m) tap matrices per channel block.

Channel blocks sit on leading array dimensions, so any (m, n) partition is a
legal BlockSpec; the grid and the per-block real words are the paper's.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch
from repro.kernels.psum_matmul import ACTIVATIONS


def _conv_kernel(x_ref, w_ref, o_ref, acc_ref, *, kk: int, stride: int,
                 wq: int, act: str, n_ci: int):
    """One (cout-block, cin-block) step over the full spatial extent.

    x_ref: (s*s, 1, m, Lx) flattened stride phases of the padded input
    w_ref: (1, 1, K*K, n, m) tap matrices
    o_ref: (1, n, L)
    acc_ref: (n, L) fp32 scratch, VMEM-resident across cin blocks.
    """
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    span = acc_ref.shape[1]
    x = x_ref[...]
    w = w_ref[...]
    acc = acc_ref[...]
    for ky in range(kk):
        for kx in range(kk):
            phase = (ky % stride) * stride + kx % stride
            off = (ky // stride) * wq + kx // stride
            acc += jnp.dot(w[0, 0, ky * kk + kx],
                           x[phase, 0, :, off:off + span],
                           preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(ci == n_ci - 1)
    def _epilogue():
        o_ref[0] = ACTIVATIONS[act](acc_ref[...]).astype(o_ref.dtype)


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """The flattened spatial layout of one conv launch (see module doc)."""

    ho: int
    wo: int
    hq: int         # phase-plane rows, ceil(Hp / s)
    wq: int         # phase-plane row length, ceil(Wp / s)
    span: int       # accumulator/output lanes, Ho * Wq rounded up to 128
    lx: int         # phase-plane lanes: the plane and every tap window fit

    @classmethod
    def of(cls, hp: int, wp: int, kk: int, stride: int) -> "ConvGeometry":
        hq, wq = -(-hp // stride), -(-wp // stride)
        ho = (hp - kk) // stride + 1
        span = _round_up(ho * wq, 128)
        reach = (kk - 1) // stride
        return cls(ho=ho, wo=(wp - kk) // stride + 1, hq=hq, wq=wq, span=span,
                   lx=_round_up(max(hq * wq, reach * wq + reach + span), 128))


def conv_launch_plan(*, cin: int, hp: int, wp: int, cout: int, kk: int,
                     stride: int = 1, block_m: int = 32, block_n: int = 32,
                     act: str = "none", dtype=None) -> launch.LaunchPlan:
    """The launch `conv2d_psum` executes, from plain integers — same clamping
    and channel padding the entry point applies, checkable without arrays."""
    g = ConvGeometry.of(hp, wp, kk, stride)
    bm = max(1, min(block_m, cin))
    bn = max(1, min(block_n, cout))
    n_ci = -(-cin // bm)
    n_co = -(-cout // bn)
    phases = stride * stride
    nbytes = 4 if dtype is None else jnp.dtype(dtype).itemsize
    return launch.LaunchPlan(
        name="conv2d_psum",
        grid=(n_co, n_ci),
        body=functools.partial(_conv_kernel, kk=kk, stride=stride, wq=g.wq,
                               act=act, n_ci=n_ci),
        inputs=(
            launch.OperandPlan("x", (phases, n_ci, bm, g.lx),
                               (phases, 1, bm, g.lx),
                               lambda co, ci: (0, ci, 0, 0),
                               elem_bytes=nbytes),
            launch.OperandPlan("w", (n_co, n_ci, kk * kk, bn, bm),
                               (1, 1, kk * kk, bn, bm),
                               lambda co, ci: (co, ci, 0, 0, 0),
                               elem_bytes=nbytes),
        ),
        outputs=(
            launch.OperandPlan("out", (n_co, bn, g.span), (1, bn, g.span),
                               lambda co, ci: (co, 0, 0), dtype=dtype,
                               elem_bytes=nbytes),
        ),
        scratch=(launch.ScratchPlan("acc", (bn, g.span), jnp.float32),),
        dimension_semantics=("parallel", "arbitrary"),
    )


@functools.partial(jax.jit, static_argnames=("schedule", "block_m", "block_n",
                                             "stride", "act", "interpret"))
def conv2d_psum(x: jax.Array, w: jax.Array, *, schedule=None, block_m: int = 32,
                block_n: int = 32, stride: int = 1, act: str = "none",
                interpret: launch.Interpret = None) -> jax.Array:
    """Partitioned conv for a single image: x (Cin, Hp, Wp) already padded,
    w (Cout, Cin, K, K). Pass a ``repro.plan.Schedule`` (kind="conv") as
    ``schedule=`` — its (m, n) channel blocks override block_m/block_n (this
    kernel always accumulates VMEM-resident, i.e. the active controller)."""
    if schedule is not None:
        if schedule.kind != "conv":
            raise ValueError(f"conv2d_psum needs a conv schedule, got {schedule}")
        block_m, block_n = schedule.m, schedule.n
    cin, hp, wp = x.shape
    cout, cin2, kk, _ = w.shape
    assert cin == cin2
    plan = conv_launch_plan(cin=cin, hp=hp, wp=wp, cout=cout, kk=kk,
                            stride=stride, block_m=block_m, block_n=block_n,
                            act=act, dtype=x.dtype)
    g = ConvGeometry.of(hp, wp, kk, stride)
    s = stride
    phases, n_ci, bm, lx = plan.inputs[0].array_shape
    n_co, _, _, bn, _ = plan.inputs[1].array_shape
    # Pad channels to block multiples (zero channels contribute zero psums)
    # and space to whole phase planes, then split the stride phases.
    x = jnp.pad(x, ((0, n_ci * bm - cin), (0, s * g.hq - hp),
                    (0, s * g.wq - wp)))
    x = x.reshape(n_ci * bm, g.hq, s, g.wq, s).transpose(2, 4, 0, 1, 3)
    x = x.reshape(phases, n_ci * bm, g.hq * g.wq)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, lx - g.hq * g.wq)))
    x = x.reshape(phases, n_ci, bm, lx)
    w = jnp.pad(w, ((0, n_co * bn - cout), (0, n_ci * bm - cin), (0, 0),
                    (0, 0)))
    w = w.transpose(2, 3, 0, 1).reshape(kk * kk, n_co, bn, n_ci, bm)
    w = w.transpose(1, 3, 0, 2, 4)
    out = launch.run(plan, x, w, interpret=interpret)
    out = out.reshape(n_co * bn, g.span)[:cout, :g.ho * g.wq]
    return out.reshape(cout, g.ho, g.wq)[:, :, :g.wo]
