"""The FFN half of Qwen2-1.5B's 28 layers over a 2048-token prefill chunk.

Sizes, cuts and limits are in ``qwen2-1.5b-ffn.json`` beside this file.
Layer l maps the (tokens, hidden_size) bfloat16 hidden state x to

    n = rmsnorm(x);  x + down(silu(gate(n)) * up(n))

with gate, up (hidden_size -> intermediate_size) and down (back) each one
``psum_matmul``: bfloat16 in and out, float32 accumulation.

  build      ``plan.plan(MatmulWorkload(...), strategy, controller)`` in
             set-up for each of the two GEMM shapes, with the mix's
             schedule (``blocks_of``: plan under that controller and keep
             its blocks). Every layer's weights stay on the device; a step
             dispatches the 28 layers, one jitted layer call each.
  answers    the last layer's (tokens, hidden_size) hidden state.
  reference  the same layers in plain float32 at HIGHEST precision from the
             same bfloat16 weights and input; ``control=True`` rounds each
             GEMM's operands to float8 e4m3 first.
  work       2 * M * K * N FLOPs per GEMM; compulsory bytes are x, w and the
             bfloat16 output once each, so partial sums spilled to HBM count
             against the kernel's roofline share.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from common import fake_quant_fp8, key_of

BF16_BYTES = 2
PROJECTIONS = ("gate", "up", "down")


def _shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, int]]:
    """(K, N) of each projection's weight."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return {"gate": (d, f), "up": (d, f), "down": (f, d)}


@functools.lru_cache(maxsize=None)
def _weights_fn(layers: int, shapes: Tuple[Tuple[str, int, int], ...]) -> Any:
    def make(seed_key: jax.Array) -> List[Dict[str, jax.Array]]:
        out = []
        for layer in range(layers):
            ws = {}
            for j, (name, k, n) in enumerate(shapes):
                key = jax.random.fold_in(seed_key, layer * len(shapes) + j)
                ws[name] = (jax.random.normal(key, (k, n), jnp.float32)
                            / math.sqrt(k)).astype(jnp.bfloat16)
            out.append(ws)
        return out
    return jax.jit(make)


def weights(cfg: Dict[str, Any], seed: int) -> List[Dict[str, jax.Array]]:
    """Every layer's bfloat16 gate, up and down weights, in one jitted
    call."""
    shapes = tuple((p, *_shapes(cfg)[p]) for p in PROJECTIONS)
    return _weights_fn(cfg["num_hidden_layers"], shapes)(key_of(seed, 1))


@functools.lru_cache(maxsize=None)
def _hidden_fn(shape: Tuple[int, int]) -> Any:
    return jax.jit(lambda key: jax.random.normal(key, shape, jnp.float32)
                   .astype(jnp.bfloat16))


def hidden_states(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int
                  ) -> List[jax.Array]:
    shape = (cfg["tokens"], cfg["hidden_size"])
    return [_hidden_fn(shape)(key_of(seed, 2, i)) for i in range(mix["pool"])]


def _rmsnorm(x: jax.Array, eps: float) -> jax.Array:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _layer(x: jax.Array, w: Dict[str, jax.Array], *, schedules: Any,
           eps: float) -> jax.Array:
    from repro.kernels.psum_matmul import psum_matmul

    s = dict(schedules)
    n = _rmsnorm(x, eps).astype(jnp.bfloat16)
    gate = psum_matmul(n, w["gate"], schedule=s["gate"])
    up = psum_matmul(n, w["up"], schedule=s["up"])
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(jnp.bfloat16)
    down = psum_matmul(act, w["down"], schedule=s["down"])
    return (x.astype(jnp.float32) + down.astype(jnp.float32)
            ).astype(jnp.bfloat16)


def _schedule(cfg: Dict[str, Any], sched: Dict[str, Any], k: int, n: int
              ) -> Any:
    from repro import plan

    wl = plan.MatmulWorkload(m=cfg["tokens"], n=n, k=k, name=cfg["name"])
    s = plan.plan(wl, strategy=sched["strategy"],
                  controller=sched.get("blocks_of",
                                       sched["controller"])).schedule
    return dataclasses.replace(s, controller=plan.Controller(
        sched["controller"]))


class Built:
    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        self.schedules = tuple(
            (p, _schedule(cfg, mix["schedule"], *_shapes(cfg)[p]))
            for p in PROJECTIONS)
        layer = jax.jit(functools.partial(_layer, schedules=self.schedules,
                                          eps=cfg["rms_norm_eps"]))
        ws = weights(cfg, seed)
        self.pool = hidden_states(cfg, mix, seed)

        def step(x: jax.Array) -> jax.Array:
            for w in ws:
                x = layer(x, w)
            return x
        self.step = step

    def describe(self) -> Dict[str, Any]:
        return {"schedules": {p: {"bm": s.bm, "bn": s.bn, "bk": s.bk,
                                  "controller": s.controller.value}
                              for p, s in self.schedules}}


def build(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int) -> Built:
    return Built(cfg, mix, seed)


def answers(out: jax.Array) -> Dict[str, jax.Array]:
    return {"y": out}


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _reference_layer(x: jax.Array, w: Dict[str, jax.Array], eps: float,
                     control: bool) -> jax.Array:
    def dot(a: jax.Array, b: jax.Array) -> jax.Array:
        b = b.astype(jnp.float32)
        if control:
            a, b = fake_quant_fp8(a), fake_quant_fp8(b)
        return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)

    n = _rmsnorm(x, eps)
    act = jax.nn.silu(dot(n, w["gate"])) * dot(n, w["up"])
    return x + dot(act, w["down"])


def reference(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
              index: int, control: bool = False) -> Dict[str, jax.Array]:
    """The last hidden state for pool input ``index``, layer by layer in
    float32."""
    x = hidden_states(cfg, mix, seed)[index].astype(jnp.float32)
    for w in weights(cfg, seed):
        x = _reference_layer(x, w, cfg["rms_norm_eps"], control)
    return {"y": x}


def work(cfg: Dict[str, Any]) -> Dict[str, Any]:
    m = cfg["tokens"]
    calls = [[2.0 * m * k * n, BF16_BYTES * (m * k + k * n + m * n)]
             for k, n in (_shapes(cfg)[p] for p in PROJECTIONS)]
    calls *= cfg["num_hidden_layers"]
    return {"units_per_step": m,
            "flops_per_step": sum(f for f, _ in calls),
            "kernels": {"psum_matmul": calls}}
