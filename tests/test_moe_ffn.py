"""The grouped partial-sum GEMM and the MoE FFN layer built on it.

`psum_grouped_matmul` (interpret mode) against per-group ``jnp.dot`` under
both controllers; `moe_layer` against the plain `moe_layer_ref` under
uniform and skewed routing, and the tolerance catching a layer that leaves
out its shared expert or a routed pick; the planner's ``groups`` (unchanged
plans at ``groups=1``, the grouped traffic by hand); scalar-prefetch launches
through `launch.run` and the static checker's treatment of them.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import plan
from repro.check.dataflow import analyze_launch, matmul_dataflow
from repro.check.diagnostics import errors
from repro.check.kernels import check_launch
from repro.kernels import launch, moe_ffn
from repro.kernels.psum_matmul import (grouped_matmul_launch_plan,
                                       grouped_row_tiles, grouped_tiles,
                                       psum_grouped_matmul)
from repro.kernels.ref import moe_layer_ref
from repro.plan.gemm_model import matmul_traffic, plan_matmul_blocks_scalar

KEY = jax.random.PRNGKey(15)


def _schedule(controller, bm=128, bn=128, bk=128):
    return plan.Schedule(kind="matmul", bm=bm, bn=bn, bk=bk,
                         controller=plan.Controller(controller))


def _per_group(x, w, sizes):
    out, start = [], 0
    for g, rows in enumerate(sizes):
        out.append(jnp.dot(x[start:start + rows].astype(jnp.float32),
                           w[g].astype(jnp.float32)))
        start += rows
    return jnp.concatenate(out)


# ------------------------------------------------------ the grouped GEMM
@pytest.mark.parametrize("controller", ["active", "passive"])
@pytest.mark.parametrize("sizes", [
    (100, 0, 37, 119),       # an empty group; sizes not multiples of bm
    (1, 2, 3, 250),          # three groups inside one row tile
    (0, 256, 0, 0),          # one group holds every row
    (256,),                  # a single group
    (130, 126),              # a boundary two rows into the second tile
], ids=["empty", "tiny", "one_of_four", "single", "straddle"])
def test_grouped_matmul_matches_per_group_dots(controller, sizes):
    rows, groups = sum(sizes), len(sizes)
    x = jax.random.normal(KEY, (rows, 384)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.fold_in(KEY, 1),
                          (groups, 384, 256)).astype(jnp.bfloat16)
    got = psum_grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                              schedule=_schedule(controller),
                              interpret=pltpu.InterpretParams())
    want = _per_group(x, w, sizes)
    assert got.shape == (rows, 256) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2 * float(
                                   jnp.max(jnp.abs(want))))


def test_grouped_matmul_pads_rows_to_the_row_block():
    """200 rows in 128-row tiles: the last tile is half padding."""
    sizes = (70, 130)
    x = jax.random.normal(KEY, (200, 128)).astype(jnp.bfloat16)
    w = jax.random.normal(KEY, (2, 128, 128)).astype(jnp.bfloat16)
    got = psum_grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                              schedule=_schedule("active"))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               _per_group(x, w, sizes), rtol=2e-2, atol=0.3)


def test_tile_map_visits_each_group_once_per_row_block():
    group, block, bounds, live = grouped_tiles(
        jnp.asarray([3, 0, 5], jnp.int32), bm=4, tiles=4)
    assert live.tolist() == [3]
    assert group.tolist() == [0, 2, 2, 2]          # the fourth tile repeats
    assert block.tolist() == [0, 0, 1, 1]
    assert bounds.tolist() == [0, 3, 3, 8]
    assert grouped_row_tiles(8, 4, 3) == 4


def test_grouped_blocks_must_divide_the_weights():
    with pytest.raises(ValueError, match="must divide"):
        grouped_matmul_launch_plan(rows=256, k=256, n=384, groups=4, bm=128,
                                   bn=256, bk=128)


# ----------------------------------------------------- scalar prefetch
def _shift_body(perm_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...] + perm_ref[pl.program_id(0)].astype(jnp.float32)


def test_a_scalar_prefetch_plan_runs_in_interpret_mode():
    """The prefetched scalars pick the block (index map) and enter the body
    (value): block i of the output is block ``perm[i]`` of x plus
    ``perm[i]``."""
    perm = jnp.asarray([2, 0, 1], jnp.int32)
    x = jnp.arange(3 * 8 * 128, dtype=jnp.float32).reshape(24, 128)
    p = launch.LaunchPlan(
        name="shift", grid=(3,), body=_shift_body,
        inputs=(launch.OperandPlan("x", (24, 128), (8, 128),
                                   lambda i, pr: (pr[i], 0)),),
        outputs=(launch.OperandPlan("out", (24, 128), (8, 128),
                                    lambda i, pr: (i, 0)),),
        dimension_semantics=("arbitrary",),
        prefetch=(launch.PrefetchPlan("perm", (3,)),))
    got = launch.run(p, perm, x, interpret=True)
    want = jnp.concatenate([x[8 * int(j):8 * int(j) + 8] + int(j)
                            for j in perm])
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="1 prefetch"):
        launch.run(p, x, interpret=True)


def test_the_checker_never_proves_a_prefetch_launch():
    p = grouped_matmul_launch_plan(rows=12288, k=2048, n=1408, groups=64,
                                   bm=512, bn=1408, bk=128)
    geo = check_launch(p)
    assert [d.code for d in geo] == ["RPC034"] and not errors(geo)
    assert "tile_group" in geo[0].message
    diags, ana = analyze_launch(p)
    assert ana is None and [d.code for d in diags] == ["RPC046"]
    rep = matmul_dataflow(plan.MatmulWorkload(m=12288, k=2048, n=1408,
                                              groups=64),
                          _schedule("passive", 512, 1408, 128))
    assert [d.code for d in rep.diagnostics] == ["RPC046"]
    assert rep.words == {}
    # what does not depend on device data is still checked
    bad = dict(rows=12288, k=2048, n=2816, groups=64, bm=4096, bn=2816,
               bk=2048)
    assert "RPC032" in {d.code for d in check_launch(
        grouped_matmul_launch_plan(**bad))}


# --------------------------------------------------------- the planner
@pytest.mark.parametrize("m,n,k", [(2048, 8960, 1536), (512, 384, 640),
                                   (300, 1000, 77)])
@pytest.mark.parametrize("controller", ["active", "passive"])
def test_an_ungrouped_plan_is_unchanged(m, n, k, controller):
    wl = plan.MatmulWorkload(m=m, n=n, k=k)
    assert wl == plan.MatmulWorkload(m=m, n=n, k=k, groups=1)
    got = plan.plan(wl, strategy="exhaustive_vmem", controller=controller)
    want = plan_matmul_blocks_scalar(m, n, k, controller=controller)
    assert (got.schedule.bm, got.schedule.bn, got.schedule.bk) == (
        want.bm, want.bn, want.bk)
    gi, gj = -(-m // want.bm), -(-n // want.bn)
    gk = -(-k // want.bk)
    c = m * n if controller == "active" else (2 * gk - 1) * m * n
    assert got.traffic.interconnect_words == gj * m * k + gi * k * n + c


def test_grouped_traffic_by_hand():
    """DeepSeek-V2-Lite's expert up-projection: 12288 routed rows, 64
    experts. Worst case 24 + 63 row tiles of 512, each reading its expert's
    2048 x 1408 weight; 63 partial tiles re-read 512 rows of x."""
    t = matmul_traffic(12288, 1408, 2048,
                       _schedule("active", 512, 1408, 128), "active", 64)
    assert t["a_reads"] == (12288 + 63 * 512) * 2048
    assert t["b_reads"] == (24 + 63) * 2048 * 1408
    assert t["c_traffic"] == 12288 * 1408
    p = plan.plan(plan.MatmulWorkload(m=12288, k=2048, n=1408, groups=64),
                  strategy="exhaustive_vmem", controller="active")
    s = p.schedule
    assert 1408 % s.bn == 0 and 2048 % s.bk == 0        # weights unpadded
    assert (s.bm, s.bn) == (512, 1408)
    assert p.traffic.interconnect_words == t["total"]
    assert p.traffic.bytes == 2 * (t["a_reads"] + t["b_reads"]
                                   + t["c_traffic"])


# ---------------------------------------------------------- the layer
T, D, F, E, K, S = 128, 256, 128, 8, 2, 128


def _params(seed):
    key = jax.random.PRNGKey(seed)

    def w(i, shape):
        return (jax.random.normal(jax.random.fold_in(key, i), shape)
                / math.sqrt(shape[-2])).astype(jnp.bfloat16)
    return {"router": jax.random.normal(jax.random.fold_in(key, 9), (D, E))
            / math.sqrt(D),
            "gate": w(1, (E, D, F)), "up": w(2, (E, D, F)),
            "down": w(3, (E, F, D)),
            "shared": {"gate": w(4, (D, S)), "up": w(5, (D, S)),
                       "down": w(6, (S, D))}}


def _hidden(skewed, seed=3):
    key = jax.random.PRNGKey(seed)
    z = jax.random.normal(key, (T, D))
    if skewed:      # three topics, one of them for most tokens
        topic = jax.random.categorical(jax.random.fold_in(key, 1),
                                       jnp.log(jnp.asarray([.7, .2, .1])),
                                       shape=(T,))
        centres = jax.random.normal(jax.random.fold_in(key, 2), (3, D))
        z = math.sqrt(0.5) * centres[topic] + math.sqrt(0.5) * z
    return z.astype(jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _layer_case(skewed):
    params, x = _params(7), _hidden(skewed)
    schedules = moe_ffn.moe_schedules(T, D, F, E, K, S)
    ref = moe_layer_ref(x, params, top_k=K, eps=1e-6)
    return params, x, schedules, ref


def _rel_delta(got, x, ref):
    """Error over the layer's own contribution (y - x), which the residual
    would otherwise hide."""
    delta = ref - x.astype(jnp.float32)
    return float(jnp.linalg.norm(got.astype(jnp.float32) - ref)
                 / jnp.linalg.norm(delta))


TOL = 0.03          # bf16 rounding of n and each GEMM reads about 0.006


@pytest.mark.parametrize("skewed", [False, True], ids=["uniform", "skewed"])
def test_moe_layer_matches_the_reference(skewed):
    params, x, schedules, ref = _layer_case(skewed)
    got = moe_ffn.moe_layer(x, params, schedules, top_k=K, eps=1e-6)
    assert got.dtype == jnp.bfloat16 and got.shape == (T, D)
    assert _rel_delta(got, x, ref) < TOL
    stats = moe_ffn.routing_stats(
        x, [moe_ffn.ffn_layer("moe", params, schedules, eps=1e-6, top_k=K)],
        eps=1e-6)[0]
    assert stats["mean_rows"] == T * K / E
    assert (stats["max_rows"] / stats["mean_rows"] > 1.8) == skewed
    assert stats["tile_rows_per_row"] >= 1.0


@pytest.mark.parametrize("left_out", ["shared", "last_pick"])
def test_a_layer_missing_a_part_fails_the_tolerance(left_out):
    params, x, schedules, ref = _layer_case(True)
    if left_out == "shared":
        params = dict(params, shared={
            k: jnp.zeros_like(v) for k, v in params["shared"].items()})
        got = moe_ffn.moe_layer(x, params, schedules, top_k=K, eps=1e-6)
    else:
        got = moe_ffn.moe_layer(x, params, schedules, top_k=K - 1, eps=1e-6)
    assert _rel_delta(got, x, ref) > 3 * TOL


def test_the_stack_spans_each_layer_under_a_tracer():
    from repro.obs import trace
    params, x, schedules, _ = _layer_case(False)
    dense = {k: params["shared"][k] for k in ("gate", "up", "down")}
    layers = [moe_ffn.ffn_layer("dense", dense, {
                  "up": schedules["shared_up"],
                  "down": schedules["shared_down"]}, eps=1e-6),
              moe_ffn.ffn_layer("moe", params, schedules, eps=1e-6, top_k=K)]
    want = moe_ffn.moe_layer(
        moe_ffn.dense_layer(x, dense, {"up": schedules["shared_up"],
                                       "down": schedules["shared_down"]},
                            eps=1e-6),
        params, schedules, top_k=K, eps=1e-6)
    with trace.tracing() as tracer:
        got = moe_ffn.run_ffn_stack(x, layers)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    spans = [s for s in tracer.spans if s.name.startswith("ffn.")]
    assert [s.name for s in spans] == ["ffn.dense", "ffn.moe", "ffn.step"]
    assert dict(spans[1].attrs) == {"layer": 1, "experts": E, "top_k": K}
