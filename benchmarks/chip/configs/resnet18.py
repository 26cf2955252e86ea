"""ResNet-18 after its stem, at published sizes and strides, one image a step.

Sizes, cuts and limits are in ``resnet18.json`` beside this file; its
``nodes`` describe the network as it is run, from the stem's 64x56x56
output through the four stages (56, 28, 14, 7 pixels) to the last residual
add. The reference below reads them and nothing of the program.

  build      the ``nodes`` as the program's ``NetworkGraph``, planned in
             set-up by ``netplan.plan_graph`` with the mix's budget, strategy
             and controller; a step is one call of ``run_network_kernels``
             (every conv through ``conv2d_psum``, the residual adds in
             between) on one image of the pool.
  answers    every tensor the step produced; the check compares those the
             reference has: each conv's output and each residual add.
  reference  the same network in plain ``jax.lax`` float32 at HIGHEST
             precision; ``control=True`` rounds each conv's operands to
             float8 e4m3 first (the precision below the path's bfloat16).
  work       per conv, 2 * Cout * Cin * K^2 * Ho * Wo FLOPs and the float32
             input, weights and output once each as compulsory bytes.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from common import fake_quant_fp8, key_of

FLOAT32_BYTES = 4


def _convs(cfg: Dict[str, Any]) -> List[Dict[str, Any]]:
    return [n for n in cfg["nodes"] if n["op"] == "conv"]


def _input(cfg: Dict[str, Any]) -> Dict[str, Any]:
    (node,) = [n for n in cfg["nodes"] if n["op"] == "input"]
    return node


@functools.lru_cache(maxsize=None)
def _weights_fn(shapes: tuple) -> Any:
    def make(seed_key: jax.Array) -> Dict[str, jax.Array]:
        out = {}
        for i, (name, cout, cin, k) in enumerate(shapes):
            w = jax.random.normal(jax.random.fold_in(seed_key, i),
                                  (cout, cin, k, k), jnp.float32)
            out[name] = w / math.sqrt(cin * k * k)
        return out
    return jax.jit(make)


def weights(cfg: Dict[str, Any], seed: int) -> Dict[str, jax.Array]:
    """Every conv's (Cout, Cin, K, K) float32 weights, in one jitted call."""
    shapes = tuple((n["name"], n["cout"], n["cin"], n["k"])
                   for n in _convs(cfg))
    return _weights_fn(shapes)(key_of(seed, 1))


def images(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int
           ) -> List[jax.Array]:
    """The pool of step inputs, (C, H, W) float32 each."""
    node = _input(cfg)
    pool = jax.random.normal(
        key_of(seed, 2), (mix["pool"], node["channels"], node["hw"],
                          node["hw"]), jnp.float32)
    return [pool[i] for i in range(mix["pool"])]


def graph(cfg: Dict[str, Any]) -> Any:
    """The ``nodes`` as the program's ``NetworkGraph``."""
    from repro.plan.graph import NetworkGraph, Node, Tensor
    from repro.plan.workload import ConvWorkload

    tensors: Dict[str, Any] = {}
    nodes = []
    for n in cfg["nodes"]:
        workload = None
        if n["op"] == "input":
            channels, hw = n["channels"], n["hw"]
        elif n["op"] == "conv":
            channels, hw = n["cout"], n["ho"]
            workload = ConvWorkload(
                name=n["name"], cin=n["cin"], cout=n["cout"], k=n["k"],
                wi=n["hi"], hi=n["hi"], wo=n["ho"], ho=n["ho"],
                stride=n["stride"])
        else:                                   # add: the shape of its inputs
            first = tensors[n["ins"][0]]
            channels, hw = first.channels, first.h
        tensors[n["out"]] = Tensor(name=n["out"], channels=channels, h=hw,
                                   w=hw)
        nodes.append(Node(name=n["name"], op=n["op"], ins=tuple(n["ins"]),
                          out=n["out"], workload=workload))
    return NetworkGraph(name=cfg["name"], nodes=tuple(nodes), tensors=tensors)


class Built:
    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        from repro.kernels.conv_network import run_network_kernels
        from repro.plan import netplan

        net = graph(cfg)
        sched = mix["schedule"]
        self.plan = netplan.plan_graph(net, sched["budget_macs"],
                                       sched["strategy"], sched["controller"])
        params = weights(cfg, seed)
        name = _input(cfg)["out"]
        self.pool = images(cfg, mix, seed)
        self.step = lambda x: run_network_kernels(net, self.plan, params,
                                                  inputs={name: x})

    def describe(self) -> Dict[str, Any]:
        return {"schedules": {k: [s.m, s.n] for k, s in
                              self.plan.schedules.items()}}


def build(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int) -> Built:
    return Built(cfg, mix, seed)


def answers(out: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return dict(out)


@functools.partial(jax.jit, static_argnames="stride")
def _conv(x: jax.Array, w: jax.Array, stride: int) -> jax.Array:
    pad = w.shape[-1] // 2
    return jax.lax.conv_general_dilated(
        x[None], w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=jax.lax.Precision.HIGHEST)[0]


def reference(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
              index: int, control: bool = False) -> Dict[str, jax.Array]:
    """Every tensor of the network for pool image ``index``, layer by
    layer in float32."""
    params = weights(cfg, seed)
    name = _input(cfg)["out"]
    values = {name: images(cfg, mix, seed)[index]}
    for node in cfg["nodes"]:
        ins = [values[t] for t in node["ins"]]
        if node["op"] == "conv":
            x = jnp.concatenate(ins, axis=0)
            w = params[node["name"]]
            if control:
                x, w = fake_quant_fp8(x), fake_quant_fp8(w)
            values[node["out"]] = _conv(x, w, node["stride"])
        elif node["op"] == "add":
            values[node["out"]] = ins[0] + ins[1]
    del values[name]
    return values


def work(cfg: Dict[str, Any]) -> Dict[str, Any]:
    calls = [[2.0 * n["cout"] * n["cin"] * n["k"] ** 2 * n["ho"] ** 2,
              FLOAT32_BYTES * (n["cin"] * n["hi"] ** 2 + n["cout"] * n["cin"]
                               * n["k"] ** 2 + n["cout"] * n["ho"] ** 2)]
             for n in _convs(cfg)]
    return {"units_per_step": cfg["batch"],
            "flops_per_step": sum(f for f, _ in calls),
            "kernels": {"conv2d_psum": calls}}
