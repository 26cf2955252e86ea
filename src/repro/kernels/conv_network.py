"""Whole-network kernel runner: chain `conv2d_psum` over a `NetworkGraph`.

The per-layer kernels execute one conv under one `Schedule`; this module
walks a planned network graph (``repro.plan.netplan.NetPlan`` or an explicit
{node name: Schedule} mapping) and runs every conv node through the Pallas
kernel under its planned channel partition, materializing the branch
structure the graph records — residual adds, fire/inception concats (a
multi-input conv reads the channel-concatenated branch tensors) and
shape-preserving pools.

The kernel accumulates in a VMEM-resident fp32 scratch (the active memory
controller / fused-residency analogue), so this is the executable TPU-side
counterpart of the planner's residency model. Graphs must be dense
(groups == 1) with "same"-padded shapes — use ``NetworkGraph.shrink()`` on
zoo nets. ``interpret`` is passed to every launch (`repro.kernels.launch.run`
decides the default).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.conv2d_psum import conv2d_psum
from repro.kernels.launch import Interpret
from repro.obs.trace import span


def init_network_params(graph, rng_seed: int = 0) -> dict[str, jax.Array]:
    """Fan-in-scaled random weights for every conv node: {node name:
    (Cout, Cin, K, K) float32}."""
    params: dict[str, jax.Array] = {}
    key = jax.random.PRNGKey(rng_seed)
    for node in graph.nodes:
        wl = node.workload
        if wl is None:
            continue
        key, sub = jax.random.split(key)
        params[node.name] = (
            jax.random.normal(sub, (wl.cout, wl.cin, wl.k, wl.k), jnp.float32)
            / math.sqrt(wl.cin * wl.k * wl.k))
    return params


def run_network_kernels(graph, schedules, params: dict[str, jax.Array],
                        inputs: dict[str, jax.Array] | None = None,
                        rng_seed: int = 0, interpret: Interpret = None
                        ) -> dict[str, jax.Array]:
    """Execute every conv of a planned graph with `conv2d_psum`.

    ``schedules`` is a `NetPlan` or a {conv node name: Schedule} mapping
    (conv-kind schedules; the kernel always accumulates VMEM-resident).
    Returns {tensor name: value} for every tensor in the graph.

    Every launch is statically pre-flighted first (`repro.check`): missing
    schedules/weights, weight-shape mismatches, non-dense or non-"same"
    shapes, BlockSpec geometry and VMEM footprint all raise a
    `repro.check.CheckError` *before* the first `pallas_call` compiles.
    The geometry proof is memoized per distinct launch, as the dataflow
    proof is, so after the first call each image pays only the lookups.

    Spans (`repro.obs.span`, in the profiler's trace while it records):
    ``network.step`` around the whole call, holding ``kernel.preflight``,
    one ``network.conv`` per conv dispatch and one ``network.glue`` per
    eager op group (``op``: input, concat_pad, add, pool). The per-node
    spans are opened only while ``network.step`` records: off, each node
    enters the step's shared no-op instead of calling ``span()``, which
    inside this loop cost about 2 µs a call on a TPU v5e host.
    """
    if hasattr(schedules, "schedules"):      # a NetPlan
        schedules = schedules.schedules
    from repro.check import preflight_network_kernels
    with span("network.step", cat="network", graph=graph.name) as step:
        traced = step.recording
        preflight_network_kernels(graph, schedules, params)
        values: dict[str, jax.Array] = {}
        key = jax.random.PRNGKey(rng_seed)
        for node in graph.nodes:
            if node.op == "input":
                with (span("network.glue", cat="network", node=node.name,
                           op="input") if traced else step):
                    if inputs is not None and node.out in inputs:
                        values[node.out] = jnp.asarray(inputs[node.out],
                                                       jnp.float32)
                    else:
                        t = graph.tensors[node.out]
                        key, sub = jax.random.split(key)
                        values[node.out] = jax.random.normal(
                            sub, (t.channels, t.h, t.w), jnp.float32)
                continue
            if node.workload is None:
                ins = [values[t] for t in node.ins]
                with (span("network.glue", cat="network", node=node.name,
                           op=node.op) if traced else step):
                    if node.op == "add":
                        values[node.out] = ins[0] + ins[1]
                    elif node.op == "pool":
                        t = graph.tensors[node.out]
                        if ins[0].shape != (t.channels, t.h, t.w):
                            raise NotImplementedError(
                                f"{node.name}: shape-changing pools are not "
                                f"executable; shrink() the graph first")
                        values[node.out] = ins[0]
                    else:
                        raise NotImplementedError(f"virtual op {node.op!r}")
                continue
            wl = node.workload
            if wl.groups != 1:
                raise NotImplementedError("kernel runner is for dense convs")
            pad = wl.k // 2
            if (wl.hi + 2 * pad - wl.k) // wl.stride + 1 != wl.ho:
                raise ValueError(
                    f"{node.name}: not 'same'-padded; shrink() first")
            with (span("network.glue", cat="network", node=node.name,
                       op="concat_pad") if traced else step):
                x = jnp.concatenate([values[t] for t in node.ins], axis=0)
                if pad:
                    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)))
            with (span("network.conv", cat="network", node=node.name)
                  if traced else step):
                values[node.out] = conv2d_psum(
                    x, params[node.name], schedule=schedules[node.name],
                    stride=wl.stride, interpret=interpret)
    return values
