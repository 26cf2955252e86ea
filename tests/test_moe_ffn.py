"""The grouped partial-sum GEMM and the MoE FFN layer built on it.

`psum_grouped_matmul` (interpret mode) against per-group ``jnp.dot`` under
both controllers; `moe_layer` against the plain `moe_layer_ref` under
uniform and skewed routing, and the tolerance catching a layer that leaves
out its shared expert or a routed pick; the planner's ``groups`` (unchanged
plans at ``groups=1``, the grouped traffic by hand, the weight reads the
model charges against those the launch makes); scalar-prefetch launches
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

import _seed_model as seed
from repro import plan
from repro.check.dataflow import analyze_launch, matmul_dataflow
from repro.check.diagnostics import errors
from repro.check.kernels import check_launch
from repro.kernels import launch, moe_ffn
from repro.kernels.psum_matmul import (grouped_matmul_launch_plan,
                                       grouped_row_tiles, grouped_tiles,
                                       grouped_weight_fetches,
                                       psum_grouped_matmul)
from repro.kernels.ref import moe_layer_ref
from repro.plan.gemm_model import matmul_terms

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
@pytest.mark.parametrize("m,n,k", [
    (2048, 8960, 1536), (512, 384, 640), (300, 1000, 77),
    (2048, 1536, 8960),             # Qwen2-1.5B down (gate and up above)
    (2048, 10944, 2048),            # DeepSeek-V2-Lite dense gate and up
    (2048, 2048, 10944),            # ... dense down
    (2048, 2816, 2048),             # ... shared experts' gate and up
    (2048, 2048, 2816),             # ... shared experts' down
])
@pytest.mark.parametrize("controller", ["active", "passive"])
def test_an_ungrouped_plan_is_unchanged(m, n, k, controller):
    wl = plan.MatmulWorkload(m=m, n=n, k=k)
    assert wl == plan.MatmulWorkload(m=m, n=n, k=k, groups=1)
    got = plan.plan(wl, strategy="exhaustive_vmem", controller=controller)
    want = seed.plan_matmul_blocks(m, n, k, controller=controller)
    assert (got.schedule.bm, got.schedule.bn, got.schedule.bk) == (
        want.bm, want.bn, want.bk)
    gi, gj = -(-m // want.bm), -(-n // want.bn)
    gk = -(-k // want.bk)
    c = m * n if controller == "active" else (2 * gk - 1) * m * n
    assert got.traffic.interconnect_words == gj * m * k + gi * k * n + c


@pytest.mark.parametrize("m,k,n,blocks,weight_reads", [
    (12288, 2048, 1408, (512, 1408, 128), 24 + 63),
    (12288, 2048, 1408, (128, 1408, 2048), 64),
    (12288, 1408, 2048, (128, 2048, 1408), 64),
], ids=["up-bk128", "up-bk2048", "down-bk1408"])
def test_grouped_traffic_by_hand(m, k, n, blocks, weight_reads):
    """DeepSeek-V2-Lite's expert GEMMs: 12288 routed rows, 64 experts.
    With 16 K blocks, each of the worst case's 24 + 63 row tiles of 512
    reads its expert's weight; with one K block, each expert's weight is
    read once. 63 partial tiles re-read bm rows of x."""
    bm, bn, bk = blocks
    wl = plan.MatmulWorkload(m=m, n=n, k=k, groups=64)
    t = matmul_terms(wl, bm, bn, bk, "active")
    assert t.a_reads == (n // bn) * (m + 63 * bm) * k
    assert t.b_reads == weight_reads * k * n
    assert t.c_traffic == m * n
    for controller in ("active", "passive"):
        grid = matmul_terms(wl, [bm], [bn], [bk], controller)
        one = matmul_terms(wl, bm, bn, bk, controller)
        assert all(g.shape == (1,) and g[0] == o for g, o in zip(grid, one))


@pytest.mark.parametrize("controller", ["active", "passive"])
def test_the_planner_reads_each_experts_weight_once(controller):
    """At bk = K the weight reads fall to one per expert, and small row
    tiles then cost the least: (128, ., K) for both expert GEMMs."""
    got = moe_ffn.moe_schedules(2048, 2048, 1408, 64, 6, 2816,
                                controller=controller)
    for name, (k, n), want in [("expert_up", (2048, 1408), (128, 1408, 2048)),
                               ("expert_down", (1408, 2048),
                                (128, 2048, 1408))]:
        s = got[name]
        assert (s.bm, s.bn, s.bk) == want, name
        wl = plan.MatmulWorkload(m=12288, k=k, n=n, groups=64)
        p = plan.plan(wl, strategy="exhaustive_vmem", controller=controller)
        t = matmul_terms(wl, s.bm, s.bn, s.bk, controller)
        assert t.b_reads == 64 * k * n
        assert p.traffic.interconnect_words == t.total
    up = plan.plan(plan.MatmulWorkload(m=12288, k=2048, n=1408, groups=64),
                   strategy="exhaustive_vmem", controller="active")
    s = up.schedule
    t = matmul_terms(up.workload, s.bm, s.bn, s.bk, "active")
    assert up.traffic.bytes == 2 * (t.a_reads + t.b_reads + t.c_traffic)


def _walk_weight_fetches(sizes, *, k, n, bm, bn, bk):
    """Walk the grouped launch's grid in order through its own ``w`` index
    map and the device tile map; count the live steps whose weight block
    differs from the previous step's (the ones the pipeline fetches)."""
    p = grouped_matmul_launch_plan(rows=sum(sizes), k=k, n=n,
                                   groups=len(sizes), bm=bm, bn=bn, bk=bk)
    meta = [np.asarray(a) for a in grouped_tiles(
        jnp.asarray(sizes, jnp.int32), bm=bm, tiles=p.grid[1])]
    live = int(meta[3][0])
    w_map = next(op for op in p.inputs if op.name == "w").index_map
    fetches, prev = 0, None
    for j in range(p.grid[0]):
        for t in range(p.grid[1]):
            for kk in range(p.grid[2]):
                idx = tuple(int(i) for i in w_map(j, t, kk, *meta))
                fetches += t < live and idx != prev
                prev = idx
    return fetches, live, p.grid


@pytest.mark.parametrize("sizes", [
    (300, 0, 37, 0, 500, 12, 0, 175),   # skewed, empty experts between
    (0, 0, 0, 1024),                     # one expert takes every row
    (5, 700, 3, 3, 3, 300, 0, 10),       # tiny experts inside one tile
], ids=["skewed", "one", "tiny"])
@pytest.mark.parametrize("blocks", [(128, 128, 256), (128, 256, 256),
                                    (128, 128, 128), (256, 256, 64)],
                         ids=["gk1-gn2", "gk1-gn1", "gk2-gn2", "gk4-gn1"])
def test_the_model_bounds_the_weight_reads_the_launch_makes(sizes, blocks):
    bm, bn, bk = blocks
    k = n = 256
    fetches, live, (gn, _, gk) = _walk_weight_fetches(
        sizes, k=k, n=n, bm=bm, bn=bn, bk=bk)
    wl = plan.MatmulWorkload(m=sum(sizes), n=n, k=k, groups=len(sizes))
    model = matmul_terms(wl, bm, bn, bk, "active").b_reads / (bk * bn)
    experts = sum(1 for r in sizes if r)
    if gk == 1:
        assert fetches == experts * gn <= model
    else:
        assert fetches == live * gk * gn <= model
    tile_group, _, _, live_dev = grouped_tiles(
        jnp.asarray(sizes, jnp.int32), bm=bm,
        tiles=grouped_row_tiles(sum(sizes), bm, len(sizes)))
    assert int(grouped_weight_fetches(tile_group, live_dev, gn=gn,
                                      gk=gk)) == fetches


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


@pytest.mark.parametrize("expert_up", [None, (128, 128, 128)],
                         ids=["planned", "bk128"])
def test_routing_stats_count_the_weight_reads_of_the_up_launch(expert_up):
    """``weight_reads_per_expert`` is the walked launch's fetches over one
    read of every expert that receives rows: 1 at the planned bk = K, the
    live tiles over the experts with rows at two K blocks."""
    params, x, schedules, _ = _layer_case(True)
    if expert_up is not None:
        schedules = dict(schedules, expert_up=_schedule("active", *expert_up))
    up = schedules["expert_up"]
    stats = moe_ffn.routing_stats(
        x, [moe_ffn.ffn_layer("moe", params, schedules, eps=1e-6, top_k=K)],
        eps=1e-6)[0]
    _, picks = moe_ffn.route(moe_ffn.rmsnorm(x, 1e-6).astype(jnp.bfloat16),
                             params["router"], K)
    sizes = np.bincount(np.asarray(picks).ravel(), minlength=E).tolist()
    fetches, live, (gn, _, gk) = _walk_weight_fetches(
        sizes, k=D, n=F, bm=up.bm, bn=up.bn, bk=up.bk)
    experts = sum(1 for r in sizes if r)
    assert stats["weight_reads_per_expert"] == fetches / (experts * gk * gn)
    assert stats["tile_rows_per_row"] == live * up.bm / (T * K)
    if expert_up is None:
        assert up.bk == D and stats["weight_reads_per_expert"] == 1.0
    else:
        assert stats["weight_reads_per_expert"] == live / experts > 1.0


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
