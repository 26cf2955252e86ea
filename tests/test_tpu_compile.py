"""Compile the main path's Pallas kernels for a described TPU v5e, at real
widths and with the planner's schedules — no chip needed.

Mosaic refuses here what the interpreter accepts (unsupported relayouts,
more VMEM than a launch may use, grid semantics a TPU kernel cannot have),
so these compiles guard the chip path of every change. The topology is
described inside a fixture, never at import: only the worker that runs this
file loads the TPU compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import plan
from repro.plan import netplan
from repro.plan.graph import NetworkGraph

FFN = plan.MatmulWorkload(m=2048, n=8960, k=1536)     # Qwen2-1.5B FFN up


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these compiles.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, one_chip, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _resnet18_layer(graph: NetworkGraph, name: str):
    node = next(n for n in graph.workload_nodes if n.name == name)
    sched = netplan.plan_graph(graph, 2048, "exact_opt",
                               "active").schedules[name]
    return node.workload, sched


@pytest.mark.parametrize("graph,layer", [
    ("shrunk", "resnet18.conv1"),        # 7x7, Cin=3, m=1
    ("shrunk", "resnet18.conv7"),        # 128 -> 128, m=128, n=1
    ("shrunk", "resnet18.conv18"),       # 1x1, 256 -> 512, m=43, n=47
    ("full", "resnet18.conv6"),          # 3x3 stride 2, 56x56 -> 28x28
])
def test_conv2d_psum_compiles(one_chip, graph, layer):
    from repro.kernels.conv2d_psum import conv2d_psum
    g = NetworkGraph.from_cnn("resnet18")
    if graph == "shrunk":
        g = g.shrink(56, 1)
    wl, sched = _resnet18_layer(g, layer)
    if graph == "full":
        assert (wl.stride, wl.hi, wl.ho) == (2, 56, 28)
    pad = wl.k // 2
    _compile(conv2d_psum,
             ((wl.cin, wl.hi + 2 * pad, wl.wi + 2 * pad), jnp.float32),
             ((wl.cout, wl.cin, wl.k, wl.k), jnp.float32),
             one_chip=one_chip, schedule=sched, stride=wl.stride)


@pytest.mark.parametrize("controller,blocks", [
    ("active", "active"),
    ("passive", "passive"),
    ("passive", "active"),       # many k-steps: the aliased psum read-back
])
def test_psum_matmul_compiles(one_chip, controller, blocks):
    from repro.kernels.psum_matmul import psum_matmul
    sched = plan.plan(FFN, strategy="exhaustive_vmem",
                      controller=blocks).schedule
    sched = plan.Schedule(kind="matmul", bm=sched.bm, bn=sched.bn,
                          bk=sched.bk,
                          controller=plan.Controller.coerce(controller))
    _compile(psum_matmul, ((FFN.m, FFN.k), jnp.bfloat16),
             ((FFN.k, FFN.n), jnp.bfloat16), one_chip=one_chip,
             schedule=sched)


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention
    shape = ((12, 2048, 128), jnp.bfloat16)
    _compile(flash_attention, shape, shape, shape, one_chip=one_chip,
             causal=True)
