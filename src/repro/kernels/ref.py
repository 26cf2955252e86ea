"""Pure-jnp oracles for every kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.psum_matmul import ACTIVATIONS


def matmul_ref(x: jax.Array, w: jax.Array, act: str = "none",
               out_dtype=None) -> jax.Array:
    out = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
    out = ACTIVATIONS[act](out)
    return out.astype(out_dtype or x.dtype)


def conv2d_ref(x: jax.Array, w: jax.Array, stride: int = 1,
               act: str = "none") -> jax.Array:
    """x: (Cin, Hp, Wp) pre-padded, w: (Cout, Cin, K, K) -> (Cout, Ho, Wo)."""
    out = jax.lax.conv_general_dilated(
        x[None].astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
    return ACTIVATIONS[act](out).astype(x.dtype)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True, q_offset: int = 0) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Skv, D)."""
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) / (q.shape[-1] ** 0.5)
    if causal:
        qi = jnp.arange(q.shape[1])[:, None] + q_offset
        ki = jnp.arange(k.shape[1])[None, :]
        s = jnp.where(qi >= ki, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, vf).astype(q.dtype)


def _swiglu_ref(n: jax.Array, w: dict) -> jax.Array:
    hi = jax.lax.Precision.HIGHEST

    def dot(a, b):
        return jnp.dot(a, b.astype(jnp.float32), precision=hi)
    return dot(jax.nn.silu(dot(n, w["gate"])) * dot(n, w["up"]), w["down"])


def moe_layer_ref(x: jax.Array, params: dict, *, top_k: int,
                  eps: float) -> jax.Array:
    """DeepSeek-V2's MoE FFN layer (arXiv:2405.04434, eq. 2.2) in float32 at
    HIGHEST precision: x + sum_{i in top_k(s)} s_i E_i(n) + S(n), with
    n = rmsnorm(x) (unit scale), s = softmax(n . W_r) over all experts,
    greedy top-k, weights not renormalised, E_i and S SwiGLU. ``params`` as
    `repro.kernels.moe_ffn.moe_layer` takes them.

    No kernel, sort or padding: one expert at a time over every token, each
    token weighted by its gate score where it picked the expert and by 0
    elsewhere, which is the expert applied to the tokens routed to it.
    Departures from the published layer: the RMSNorm scale is 1 and the
    result is float32 (the program rounds n and each GEMM to bfloat16)."""
    x = x.astype(jnp.float32)
    n = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    logits = jnp.dot(n, params["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    top, picks = jax.lax.top_k(scores, top_k)
    y = x + _swiglu_ref(n, params["shared"])
    for e in range(params["router"].shape[1]):
        weight = jnp.sum(jnp.where(picks == e, top, 0.0), axis=-1)
        y = y + weight[:, None] * _swiglu_ref(
            n, {p: params[p][e] for p in ("gate", "up", "down")})
    return y
