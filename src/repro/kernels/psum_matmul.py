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

`psum_grouped_matmul` is the grouped form (a mixture of experts' FFN): rows
sorted by group, one K x N weight per group, each row multiplied by its own
group's weight. The same two controllers keep each group's partial sums in
VMEM or spill them to HBM. Its row tiles map to groups through metadata
computed on the device (`grouped_tiles`) and handed to the launch as
scalar-prefetch operands, over a static worst-case grid, so no group size
ever returns to the host.
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


# ----------------------------------------------------------- grouped GEMM
def grouped_row_tiles(rows: int, bm: int, groups: int) -> int:
    """Row tiles a grouped launch's grid holds: every row tile, plus one
    partial tile for each boundary between two groups inside a tile — the
    static worst case over all group sizes that sum to ``rows``."""
    return -(-rows // bm) + groups - 1


def grouped_tiles(group_sizes: jax.Array, *, bm: int, tiles: int
                  ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The tile -> (group, row block) map of a grouped launch, on the device.

    Group g owns rows ``[bounds[g], bounds[g + 1])``; it is visited once per
    row block it touches, in row order, empty groups not at all. Returns
    (``tile_group``, ``tile_block``, ``bounds``, ``live``): tile t computes
    group ``tile_group[t]`` on row block ``tile_block[t]``; tiles from
    ``live[0]`` on repeat the last live tile's indices and do nothing (with
    one K block, their blocks are never fetched again)."""
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // bm
    count = jnp.where(sizes > 0, (ends - 1) // bm - first + 1, 0)
    tile_end = jnp.cumsum(count)
    live = tile_end[-1]
    t = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                    jnp.maximum(live - 1, 0))
    group = jnp.minimum(jnp.sum(tile_end[None, :] <= t[:, None], axis=1,
                                dtype=jnp.int32), sizes.shape[0] - 1)
    block = first[group] + t - (tile_end[group] - count[group])
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return group, block, bounds, live[None]


def grouped_weight_fetches(tile_group: jax.Array, live: jax.Array, *,
                           gn: int, gk: int) -> jax.Array:
    """The weight blocks a grouped launch's live tiles fetch, on the device.

    The grid (``gn`` N blocks, row tiles, ``gk`` K blocks) fetches a weight
    block whenever a live tile's index (group, K block, N block) differs
    from the previous grid step's. With ``gk`` > 1 the K block changes at
    every step; with ``gk`` = 1 only a tile that starts its group does."""
    t = jnp.arange(tile_group.shape[0], dtype=jnp.int32)
    fetch = t < live[0]
    if gk == 1:
        fetch &= jnp.concatenate([jnp.ones(1, bool),
                                  tile_group[1:] != tile_group[:-1]])
    return gn * gk * jnp.sum(fetch)


def _group_rows(tile_group, tile_block, bounds, t, shape, bm: int
                ) -> jax.Array:
    """The rows of tile ``t``'s block that belong to its group."""
    g = tile_group[t]
    row = tile_block[t] * bm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= bounds[g]) & (row < bounds[g + 1])


def _grouped_active_kernel(tile_group, tile_block, bounds, live, x_ref,
                           w_ref, o_ref, acc_ref, *, act: str, n_k: int,
                           bm: int):
    """One group's rows of one row block: the f32 partial sums stay in VMEM
    across k; the epilogue stores only the group's rows, so a block that two
    groups share collects both while it stays resident."""
    t, k = pl.program_id(1), pl.program_id(2)

    @pl.when(t < live[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(k == n_k - 1)
        def _epilogue():
            mine = _group_rows(tile_group, tile_block, bounds, t,
                               acc_ref.shape, bm)
            o_ref[...] = jnp.where(
                mine, ACTIVATIONS[act](acc_ref[...]),
                o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _grouped_passive_first_kernel(tile_group, tile_block, bounds, live,
                                  x_ref, w_ref, o_ref, *, bm: int):
    """Passive step 0: each group's first partial sums go out to HBM."""
    t = pl.program_id(1)

    @pl.when(t < live[0])
    def _tile():
        mine = _group_rows(tile_group, tile_block, bounds, t, o_ref.shape, bm)
        o_ref[...] = jnp.where(
            mine, jnp.dot(x_ref[...], w_ref[0],
                          preferred_element_type=jnp.float32), o_ref[...])


def _grouped_passive_kernel(tile_group, tile_block, bounds, live, x_ref,
                            w_ref, p_ref, o_ref, *, bm: int):
    """Passive step k > 0: read each group's partial sums back, update,
    spill again."""
    t = pl.program_id(1)

    @pl.when(t < live[0])
    def _tile():
        mine = _group_rows(tile_group, tile_block, bounds, t, o_ref.shape, bm)
        o_ref[...] = jnp.where(
            mine, p_ref[...] + jnp.dot(x_ref[...], w_ref[0],
                                       preferred_element_type=jnp.float32),
            o_ref[...])


def grouped_matmul_launch_plan(*, rows: int, k: int, n: int, groups: int,
                               bm: int, bn: int, bk: int,
                               controller: str = "active", act: str = "none",
                               dtype=None, k_step: int = 0
                               ) -> launch.LaunchPlan:
    """The launch `psum_grouped_matmul` executes, from plain integers.

    Grid (N blocks, row tiles, K blocks): the row tiles of one N block run
    consecutively, so a row block that two groups share keeps its output
    block resident between their visits. ``bn`` and ``bk`` must divide
    ``n`` and ``k``: the weights are never padded. Rows are padded to a
    multiple of ``bm``, as the entry pads them. A passive GEMM is
    `reduction_launches` launches; ``k_step`` picks one."""
    if n % bn or k % bk:
        raise ValueError(f"grouped GEMM blocks ({bn}, {bk}) must divide "
                         f"(n, k) = ({n}, {k})")
    rp = rows + (-rows) % bm
    gn, gk = n // bn, k // bk
    tiles = grouped_row_tiles(rows, bm, groups)
    prefetch = (launch.PrefetchPlan("tile_group", (tiles,)),
                launch.PrefetchPlan("tile_block", (tiles,)),
                launch.PrefetchPlan("bounds", (groups + 1,)),
                launch.PrefetchPlan("live", (1,)))
    if controller == "active":
        return launch.LaunchPlan(
            name="psum_grouped_matmul/active",
            grid=(gn, tiles, gk),
            body=functools.partial(_grouped_active_kernel, act=act, n_k=gk,
                                   bm=bm),
            inputs=(
                launch.OperandPlan(
                    "x", (rp, k), (bm, bk),
                    lambda j, t, kk, tg, tb, *_: (tb[t], kk), elem_bytes=2),
                launch.OperandPlan(
                    "w", (groups, k, n), (1, bk, bn),
                    lambda j, t, kk, tg, tb, *_: (tg[t], kk, j),
                    elem_bytes=2),
            ),
            outputs=(
                launch.OperandPlan(
                    "out", (rp, n), (bm, bn),
                    lambda j, t, kk, tg, tb, *_: (tb[t], j), dtype=dtype,
                    elem_bytes=2),
            ),
            scratch=(launch.ScratchPlan("acc", (bm, bn), jnp.float32),),
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            prefetch=prefetch,
        )
    if controller == "passive":
        if not 0 <= k_step < gk:
            raise ValueError(f"k_step {k_step} outside the {gk} k-steps")
        inputs = (
            launch.OperandPlan(
                "x", (rp, k), (bm, bk),
                lambda j, t, tg, tb, *_: (tb[t], k_step), elem_bytes=2),
            launch.OperandPlan(
                "w", (groups, k, n), (1, bk, bn),
                lambda j, t, tg, tb, *_: (tg[t], k_step, j), elem_bytes=2),
        )
        if k_step:
            inputs += (launch.OperandPlan(
                "psums", (rp, n), (bm, bn),
                lambda j, t, tg, tb, *_: (tb[t], j)),)
        return launch.LaunchPlan(
            name=f"psum_grouped_matmul/passive/k{k_step}",
            grid=(gn, tiles),
            body=functools.partial(
                _grouped_passive_kernel if k_step
                else _grouped_passive_first_kernel, bm=bm),
            inputs=inputs,
            outputs=(
                launch.OperandPlan(
                    "out", (rp, n), (bm, bn),
                    lambda j, t, tg, tb, *_: (tb[t], j), dtype=jnp.float32),
            ),
            dimension_semantics=("parallel", "arbitrary"),
            input_output_aliases=((2, 0),) if k_step else (),
            prefetch=prefetch,
        )
    raise ValueError(controller)


@functools.partial(jax.jit, static_argnames=("schedule", "act", "interpret"))
def psum_grouped_matmul(x_sorted: jax.Array, w: jax.Array,
                        group_sizes: jax.Array, *, schedule, act: str = "none",
                        interpret: launch.Interpret = None) -> jax.Array:
    """Per-group ``act(x_g @ w[g])`` with an explicit partial-sum schedule.

    x_sorted: (R, K), rows sorted by group; w: (G, K, N); group_sizes: (G,)
    int32 on the device, summing to R (rows past their sum come back
    unspecified). The schedule's blocks come from ``plan.plan(
    MatmulWorkload(m=R, k=K, n=N, groups=G))``; its controller keeps each
    group's f32 partial sums in VMEM (active) or spills them to HBM once per
    k-step (passive). Returns (R, N) in ``x_sorted``'s dtype."""
    if schedule.kind != "matmul":
        raise ValueError(f"psum_grouped_matmul needs a matmul schedule, got "
                         f"{schedule}")
    bm, bn, bk = schedule.bm, schedule.bn, schedule.bk
    controller = schedule.controller.value
    rows, k = x_sorted.shape
    groups, k2, n = w.shape
    assert k == k2 and group_sizes.shape == (groups,), (
        x_sorted.shape, w.shape, group_sizes.shape)
    meta = grouped_tiles(group_sizes, bm=bm,
                         tiles=grouped_row_tiles(rows, bm, groups))
    xp = _pad_to(x_sorted, bm, 1)
    kw = dict(rows=rows, k=k, n=n, groups=groups, bm=bm, bn=bn, bk=bk,
              controller=controller, act=act)
    if controller == "active":
        out = launch.run(
            grouped_matmul_launch_plan(dtype=x_sorted.dtype, **kw), *meta, xp,
            w, interpret=interpret)
    else:
        psums = launch.run(grouped_matmul_launch_plan(**kw), *meta, xp, w,
                           interpret=interpret)
        for step in range(1, reduction_launches(k, bk, controller)):
            psums = launch.run(grouped_matmul_launch_plan(k_step=step, **kw),
                               *meta, xp, w, psums, interpret=interpret)
        out = ACTIVATIONS[act](psums).astype(x_sorted.dtype)
    return out[:rows]


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
