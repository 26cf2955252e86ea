"""The FFN half of DeepSeek-V2-Lite's first 11 layers over a 2048-token prefill chunk.

Sizes, cuts and limits are in ``deepseek-v2-lite-moe.json`` beside this
file. Layer 0 is dense, layers 1-10 are MoE (DeepSeek-V2, arXiv:2405.04434,
§2.2); on the (tokens, hidden_size) bfloat16 hidden state x, n = rmsnorm(x):

  dense  x + down(silu(gate n) * up n)
  moe    x + sum over the top-6 experts i of s_i E_i(n) + S(n),
         s = softmax(n . W_r) over 64 experts, greedy, not renormalised

with E_i the routed experts' SwiGLU (width 1408) and S the shared experts'
(width 2816).

  build      `repro.kernels.moe_ffn`: the planner's schedules for every GEMM
             shape (``moe_schedules``, ``dense_schedules``) under the mix's
             schedule, every layer's weights on the device, one jitted call
             per layer (``run_ffn_stack``); the routing counters of each MoE
             layer over the pool (``routing_stats``) in set-up.
  answers    the last layer's (tokens, hidden_size) hidden state.
  reference  the same layers in plain float32 at HIGHEST precision from the
             same weights and input, each layer's weights made afresh and one
             expert at a time over every token, weighted by its gate score
             where the token picked it and by 0 elsewhere; its routing comes
             from its own float32 hidden state. ``control=True`` rounds every
             FFN GEMM's operands to float8 e4m3 first (the router as before).
  work       2 * rows * K * N FLOPs per GEMM, rows the routed rows (6 a token)
             for the grouped GEMMs; compulsory bytes are the rows, every
             expert's weights and the output once each, in bfloat16.

The pool's hidden states cluster by topic (``mixes/skew.json``'s ``skew``):
token i of an input has a topic t(i), drawn from a Zipf law over ``topics``
topics, and the state sqrt(share) c_t + sqrt(1 - share) z_i, with topic
centres c_t and noise z_i N(0, I): tokens of one topic share their routing.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from common import fake_quant_fp8, key_of

BF16_BYTES = 2
PROJECTIONS = ("gate", "up", "down")


def _dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"d": cfg["hidden_size"], "dense": cfg["intermediate_size"],
            "ff": cfg["moe_intermediate_size"],
            "experts": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "dense_layers": cfg["first_k_dense_replace"],
            "layers": cfg["num_hidden_layers"]}


def _swiglu_weights(key: jax.Array, d: int, f: int, lead: tuple = ()
                    ) -> Dict[str, jax.Array]:
    shapes = {"gate": (d, f), "up": (d, f), "down": (f, d)}
    return {p: (jax.random.normal(jax.random.fold_in(key, j),
                                  lead + shapes[p], jnp.float32)
                / math.sqrt(shapes[p][0])).astype(jnp.bfloat16)
            for j, p in enumerate(PROJECTIONS)}


@functools.lru_cache(maxsize=None)
def _layer_weights_fn(kind: str, d: int, f: int, experts: int, shared: int
                      ) -> Any:
    def make(key: jax.Array) -> Dict[str, Any]:
        if kind == "dense":
            return _swiglu_weights(key, d, f)
        w = _swiglu_weights(jax.random.fold_in(key, 0), d, f, (experts,))
        w["shared"] = _swiglu_weights(jax.random.fold_in(key, 1), d, shared)
        w["router"] = jax.random.normal(jax.random.fold_in(key, 2),
                                        (d, experts), jnp.float32) \
            / math.sqrt(d)
        return w
    return jax.jit(make)


def layer_kind(cfg: Dict[str, Any], layer: int) -> str:
    return "dense" if layer < _dims(cfg)["dense_layers"] else "moe"


def layer_weights(cfg: Dict[str, Any], seed: int, layer: int
                  ) -> Dict[str, Any]:
    """One layer's weights, made on the device from the seed: bfloat16
    SwiGLU projections (``gate``, ``up``, ``down``; the routed experts'
    stacked on a leading expert axis), and for a MoE layer ``shared`` and a
    float32 ``router``."""
    dm = _dims(cfg)
    kind = layer_kind(cfg, layer)
    fn = _layer_weights_fn(kind, dm["d"],
                           dm["dense"] if kind == "dense" else dm["ff"],
                           dm["experts"], dm["shared"])
    return fn(key_of(seed, 1, layer))


@functools.lru_cache(maxsize=None)
def _hidden_fn(tokens: int, d: int, topics: int, zipf: float, share: float
               ) -> Any:
    def make(centres: jax.Array, key: jax.Array) -> jax.Array:
        logits = -zipf * jnp.log(jnp.arange(1, topics + 1, dtype=jnp.float32))
        topic = jax.random.categorical(jax.random.fold_in(key, 0), logits,
                                       shape=(tokens,))
        noise = jax.random.normal(jax.random.fold_in(key, 1), (tokens, d),
                                  jnp.float32)
        return (math.sqrt(share) * centres[topic]
                + math.sqrt(1.0 - share) * noise).astype(jnp.bfloat16)
    return jax.jit(make)


def hidden_states(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int
                  ) -> List[jax.Array]:
    """The pool: ``mix["pool"]`` (tokens, hidden_size) bfloat16 inputs whose
    tokens cluster by topic, all from the seed."""
    sk = mix["skew"]
    d = cfg["hidden_size"]
    centres = jax.random.normal(key_of(seed, 2), (sk["topics"], d),
                                jnp.float32)
    fn = _hidden_fn(cfg["tokens"], d, sk["topics"], float(sk["topic_zipf"]),
                    float(sk["topic_share"]))
    return [fn(centres, key_of(seed, 3, i)) for i in range(mix["pool"])]


class Built:
    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int):
        from repro.kernels import moe_ffn

        dm = _dims(cfg)
        sched = dict(strategy=mix["schedule"]["strategy"],
                     controller=mix["schedule"]["controller"])
        eps = cfg["rms_norm_eps"]
        self.schedules = {
            "dense": moe_ffn.dense_schedules(cfg["tokens"], dm["d"],
                                             dm["dense"], **sched),
            "moe": moe_ffn.moe_schedules(cfg["tokens"], dm["d"], dm["ff"],
                                         dm["experts"], dm["top_k"],
                                         dm["shared"], **sched)}
        kinds = [layer_kind(cfg, i) for i in range(dm["layers"])]
        self.layers = [
            moe_ffn.ffn_layer(kind, layer_weights(cfg, seed, i),
                              self.schedules[kind], eps=eps,
                              top_k=dm["top_k"] if kind == "moe" else 0)
            for i, kind in enumerate(kinds)]
        self.pool = hidden_states(cfg, mix, seed)
        per_input = [moe_ffn.routing_stats(x, self.layers, eps=eps)
                     for x in self.pool]
        self.routing = [
            {"layer": layer[0]["layer"],
             **{k: float(np.mean([r[k] for r in layer]))
                for k in ("max_rows", "mean_rows", "empty_experts",
                          "tile_rows_per_row")}}
            for layer in zip(*per_input)]
        layers = self.layers
        self.step = lambda x: moe_ffn.run_ffn_stack(x, layers)

    def describe(self) -> Dict[str, Any]:
        return {"schedules": {
                    f"{kind}.{p}": {"bm": s.bm, "bn": s.bn, "bk": s.bk,
                                    "controller": s.controller.value}
                    for kind, by in self.schedules.items()
                    for p, s in by.items()},
                "routing": [dict(r, max_over_mean=r["max_rows"]
                                 / r["mean_rows"]) for r in self.routing]}


def build(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int) -> Built:
    return Built(cfg, mix, seed)


def answers(out: jax.Array) -> Dict[str, jax.Array]:
    return {"y": out}


# ------------------------------------------------------- the reference
def _rmsnorm(x: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _dot(a: jax.Array, b: jax.Array, control: bool) -> jax.Array:
    b = b.astype(jnp.float32)
    if control:
        a, b = fake_quant_fp8(a), fake_quant_fp8(b)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _swiglu(n: jax.Array, w: Dict[str, jax.Array], control: bool
            ) -> jax.Array:
    act = jax.nn.silu(_dot(n, w["gate"], control)) * _dot(n, w["up"],
                                                            control)
    return _dot(act, w["down"], control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _reference_dense(x: jax.Array, w: Dict[str, jax.Array], eps: float,
                     control: bool) -> jax.Array:
    return x + _swiglu(_rmsnorm(x, eps), w, control)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "control"))
def _reference_moe(x: jax.Array, w: Dict[str, Any], eps: float, top_k: int,
                   control: bool) -> jax.Array:
    n = _rmsnorm(x, eps)
    scores = jax.nn.softmax(jnp.dot(n, w["router"],
                                    precision=jax.lax.Precision.HIGHEST),
                            axis=-1)
    top, picks = jax.lax.top_k(scores, top_k)
    experts = w["router"].shape[1]
    gate_of = jnp.sum(jax.nn.one_hot(picks, experts) * top[..., None],
                      axis=1)                       # (tokens, experts)

    def expert(y, e):
        wg, wu, wd, g = e
        return y + g[:, None] * _swiglu(n, {"gate": wg, "up": wu,
                                            "down": wd}, control), None

    y, _ = jax.lax.scan(expert, x + _swiglu(n, w["shared"], control),
                        (w["gate"], w["up"], w["down"], gate_of.T))
    return y


def reference(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
              index: int, control: bool = False) -> Dict[str, jax.Array]:
    """The last hidden state for pool input ``index``, layer by layer in
    float32, one layer's weights on the device at a time."""
    dm = _dims(cfg)
    eps = cfg["rms_norm_eps"]
    x = hidden_states(cfg, mix, seed)[index].astype(jnp.float32)
    for layer in range(dm["layers"]):
        w = layer_weights(cfg, seed, layer)
        if layer_kind(cfg, layer) == "dense":
            x = _reference_dense(x, w, eps, control)
        else:
            x = _reference_moe(x, w, eps, dm["top_k"], control)
        del w
    return {"y": x}


def work(cfg: Dict[str, Any]) -> Dict[str, Any]:
    dm = _dims(cfg)
    t, d = cfg["tokens"], dm["d"]
    rows = t * dm["top_k"]

    def gemm(m: int, k: int, n: int) -> List[float]:
        return [2.0 * m * k * n, BF16_BYTES * (m * k + k * n + m * n)]

    def grouped(k: int, n: int) -> List[float]:
        return [2.0 * rows * k * n,
                BF16_BYTES * (rows * k + dm["experts"] * k * n + rows * n)]

    def swiglu(f: int) -> List[List[float]]:
        return [gemm(t, d, f), gemm(t, d, f), gemm(t, f, d)]

    dense: List[List[float]] = []
    experts: List[List[float]] = []
    for layer in range(dm["layers"]):
        if layer_kind(cfg, layer) == "dense":
            dense += swiglu(dm["dense"])
        else:
            experts += [grouped(d, dm["ff"]), grouped(d, dm["ff"]),
                        grouped(dm["ff"], d)]
            dense += swiglu(dm["shared"])
    return {"units_per_step": t,
            "flops_per_step": sum(f for f, _ in dense + experts),
            "kernels": {"psum_matmul": dense,
                        "psum_grouped_matmul": experts}}
