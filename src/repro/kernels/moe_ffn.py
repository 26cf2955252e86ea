"""A DeepSeek-V2-style FFN stack on the planner -> Pallas path.

Layer kinds (DeepSeek-V2, arXiv:2405.04434, §2.2), on a (tokens, hidden)
bfloat16 hidden state x with n = rmsnorm(x):

  dense  x + down(silu(gate n) * up n)                     (`dense_layer`)
  moe    x + sum over the top-k experts i of s_i * E_i(n) + S(n)
                                                           (`moe_layer`)

where s = softmax(n . W_r) over the routed experts, picked greedily and not
renormalised, E_i is expert i's SwiGLU and S the shared experts' SwiGLU.
Every projection is a partial-sum GEMM of `repro.kernels.psum_matmul`: the
routed experts' through `psum_grouped_matmul` over the rows sorted by
expert, the shared and dense ones through `psum_matmul`, each with blocks
the planner chose (`moe_schedules`, `dense_schedules`).

`run_ffn_stack` dispatches one jitted call per layer inside the spans
``ffn.step`` (the parent), ``ffn.dense`` and ``ffn.moe`` (attributes
``layer``, ``experts``, ``top_k``). `routing_stats` reads, once and outside
any timed loop, how evenly each MoE layer's router loads its experts.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.launch import Interpret
from repro.kernels.psum_matmul import (grouped_row_tiles, grouped_tiles,
                                       grouped_weight_fetches,
                                       psum_grouped_matmul, psum_matmul)
from repro.obs.trace import span

Params = Dict[str, Any]


def rmsnorm(x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with unit scale, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def route(n: jax.Array, router: jax.Array, top_k: int
          ) -> tuple[jax.Array, jax.Array]:
    """The gate as the published model computes it: float32 logits at
    HIGHEST precision from the float32 router weight, softmax over the
    experts, greedy top-k, weights not renormalised. Returns (weights,
    experts), each (tokens, top_k)."""
    logits = jnp.dot(n.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)


def _swiglu(n: jax.Array, w: Params, up: Any, down: Any,
            interpret: Interpret) -> jax.Array:
    """down(silu(gate n) * up n), three `psum_matmul`s, bfloat16 out."""
    gate = psum_matmul(n, w["gate"], schedule=up, interpret=interpret)
    upv = psum_matmul(n, w["up"], schedule=up, interpret=interpret)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * upv.astype(jnp.float32)).astype(jnp.bfloat16)
    return psum_matmul(act, w["down"], schedule=down, interpret=interpret)


def dense_layer(x: jax.Array, params: Params, schedules: Mapping[str, Any],
                *, eps: float, interpret: Interpret = None) -> jax.Array:
    """x + down(silu(gate n) * up n): the dense FFN layer."""
    n = rmsnorm(x, eps).astype(jnp.bfloat16)
    y = _swiglu(n, params, schedules["up"], schedules["down"], interpret)
    return (x.astype(jnp.float32) + y.astype(jnp.float32)
            ).astype(jnp.bfloat16)


def moe_layer(x: jax.Array, params: Params, schedules: Mapping[str, Any],
              *, top_k: int, eps: float, interpret: Interpret = None
              ) -> jax.Array:
    """x + sum_{i in topk(s)} s_i E_i(n) + S(n), bfloat16 in and out.

    ``params``: ``router`` (hidden, experts) float32; ``gate``, ``up``
    (experts, hidden, expert_ff) and ``down`` (experts, expert_ff, hidden)
    bfloat16; ``shared`` with the shared experts' ``gate``, ``up``, ``down``.
    ``schedules``: ``expert_up`` (gate and up), ``expert_down``,
    ``shared_up``, ``shared_down``.

    The token -> expert picks are sorted by expert (stable), the rows
    gathered into that order and run through the grouped GEMMs; their
    outputs return to token order through the inverse permutation, and each
    token sums its picks weighted by their gate scores in float32 (the
    scatter-add, written as a gather, so the sum's order is fixed)."""
    tokens = x.shape[0]
    experts = params["router"].shape[1]
    n = rmsnorm(x, eps).astype(jnp.bfloat16)
    weights, picks = route(n, params["router"], top_k)
    flat = picks.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=experts).astype(jnp.int32)
    rows = n[order // top_k]
    gate = psum_grouped_matmul(rows, params["gate"], sizes,
                               schedule=schedules["expert_up"],
                               interpret=interpret)
    up = psum_grouped_matmul(rows, params["up"], sizes,
                             schedule=schedules["expert_up"],
                             interpret=interpret)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(jnp.bfloat16)
    out = psum_grouped_matmul(act, params["down"], sizes,
                              schedule=schedules["expert_down"],
                              interpret=interpret)
    picked = out[jnp.argsort(order)].reshape(tokens, top_k, -1)
    routed = jnp.sum(picked.astype(jnp.float32) * weights[..., None], axis=1)
    shared = _swiglu(n, params["shared"], schedules["shared_up"],
                     schedules["shared_down"], interpret)
    return (x.astype(jnp.float32) + routed + shared.astype(jnp.float32)
            ).astype(jnp.bfloat16)


# ------------------------------------------------------------- planning
def _plan(m: int, k: int, n: int, groups: int, strategy: str,
          controller: str) -> Any:
    from repro import plan
    return plan.plan(plan.MatmulWorkload(m=m, k=k, n=n, groups=groups,
                                         name=f"ffn{m}x{k}x{n}/{groups}"),
                     strategy=strategy, controller=controller).schedule


def dense_schedules(tokens: int, hidden: int, ff: int, *,
                    strategy: str = "exhaustive_vmem",
                    controller: str = "active") -> Dict[str, Any]:
    """The planner's schedules of a dense FFN layer: ``up`` (gate and up,
    hidden -> ff) and ``down``."""
    return {"up": _plan(tokens, hidden, ff, 1, strategy, controller),
            "down": _plan(tokens, ff, hidden, 1, strategy, controller)}


def moe_schedules(tokens: int, hidden: int, expert_ff: int, experts: int,
                  top_k: int, shared_ff: int, *,
                  strategy: str = "exhaustive_vmem",
                  controller: str = "active") -> Dict[str, Any]:
    """The planner's schedules of a MoE layer: the grouped GEMMs over all
    ``tokens * top_k`` routed rows and ``experts`` groups, and the shared
    experts' GEMMs."""
    rows = tokens * top_k
    shared = dense_schedules(tokens, hidden, shared_ff, strategy=strategy,
                             controller=controller)
    return {"expert_up": _plan(rows, hidden, expert_ff, experts, strategy,
                               controller),
            "expert_down": _plan(rows, expert_ff, hidden, experts, strategy,
                                 controller),
            "shared_up": shared["up"], "shared_down": shared["down"]}


# ------------------------------------------------------------- the stack
@dataclasses.dataclass(frozen=True)
class FfnLayer:
    """One layer of the stack: its kind, its parameters and the jitted call
    ``call(x, params) -> x``."""

    kind: str                          # "dense" | "moe"
    params: Params
    call: Callable[[jax.Array, Params], jax.Array]
    schedules: Mapping[str, Any]
    top_k: int = 0

    @property
    def experts(self) -> int:
        return int(self.params["router"].shape[1]) if self.kind == "moe" \
            else 0


@functools.lru_cache(maxsize=None)
def _layer_fn(kind: str, schedules: tuple, top_k: int, eps: float,
              interpret: Interpret
              ) -> Callable[[jax.Array, Params], jax.Array]:
    s = dict(schedules)
    if kind == "dense":
        return jax.jit(functools.partial(dense_layer, schedules=s, eps=eps,
                                         interpret=interpret))
    if kind == "moe":
        return jax.jit(functools.partial(moe_layer, schedules=s,
                                         top_k=top_k, eps=eps,
                                         interpret=interpret))
    raise ValueError(f"unknown FFN layer kind {kind!r}")


def ffn_layer(kind: str, params: Params, schedules: Mapping[str, Any], *,
              eps: float, top_k: int = 0, interpret: Interpret = None
              ) -> FfnLayer:
    """A layer of the stack; layers of one kind and schedules share one
    jitted call. ``interpret`` reaches every launch
    (`repro.kernels.launch.run` decides the default)."""
    return FfnLayer(kind, params,
                    _layer_fn(kind, tuple(sorted(schedules.items())), top_k,
                              eps, interpret),
                    schedules, top_k)


def run_ffn_stack(x: jax.Array, layers: List[FfnLayer]) -> jax.Array:
    """Dispatch every layer in order, one jitted call each; returns the last
    hidden state without waiting for it.

    ``ffn.step`` spans the whole call; while it records (the profiler or a
    tracer), each layer's dispatch sits in ``ffn.dense`` or ``ffn.moe``.
    Off, the layers enter the step's shared no-op, checked once a step."""
    with span("ffn.step", cat="ffn", layers=len(layers)) as step:
        traced = step.recording
        for i, layer in enumerate(layers):
            with (span(f"ffn.{layer.kind}", cat="ffn", layer=i,
                       experts=layer.experts, top_k=layer.top_k)
                  if traced else step):
                x = layer.call(x, layer.params)
    return x


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _expert_rows(x: jax.Array, router: jax.Array, *, top_k: int,
                 eps: float) -> jax.Array:
    _, picks = route(rmsnorm(x, eps).astype(jnp.bfloat16), router, top_k)
    return jnp.bincount(picks.reshape(-1), length=router.shape[1])


def routing_stats(x: jax.Array, layers: List[FfnLayer], *, eps: float
                  ) -> List[Dict[str, Any]]:
    """For each MoE layer of the stack, on input ``x``: the most and the
    mean rows an expert receives, the experts that receive none, and, for
    the grouped gate (or up) launch at the ``expert_up`` schedule, the rows
    it computes (its live row tiles of ``bm`` rows each) over the rows
    routed and the weight blocks it fetches over those of one read of every
    expert that receives rows. Runs the stack once and waits for it: a
    set-up measurement, never timed."""
    out: List[Dict[str, Any]] = []
    for i, layer in enumerate(layers):
        if layer.kind == "moe":
            sizes = _expert_rows(x, layer.params["router"], top_k=layer.top_k,
                                 eps=eps)
            up = layer.schedules["expert_up"]
            rows = x.shape[0] * layer.top_k
            k, n = layer.params["gate"].shape[1:]
            gk, gn = k // up.bk, n // up.bn
            tile_group, _, _, live = grouped_tiles(
                sizes, bm=up.bm,
                tiles=grouped_row_tiles(rows, up.bm, layer.experts))
            fetches = grouped_weight_fetches(tile_group, live, gn=gn, gk=gk)
            sizes, live, fetches = jax.device_get((sizes, live, fetches))
            out.append({"layer": i, "max_rows": int(sizes.max()),
                        "mean_rows": rows / layer.experts,
                        "empty_experts": int(np.sum(sizes == 0)),
                        "tile_rows_per_row": int(live[0]) * up.bm / rows,
                        "weight_reads_per_expert": int(fetches) / (
                            int(np.sum(sizes > 0)) * gk * gn)})
        x = layer.call(x, layer.params)
    jax.block_until_ready(x)
    return out
