"""Public jit'd wrappers around the Pallas kernels.

These choose execution schedules via the unified planner (``repro.plan``) —
the paper's partitioning policy applied to TPU tiles — and handle
padding/layout so callers see plain array ops.

``interpret`` is passed through to `repro.kernels.launch.run`, which decides
the default (compiled on a TPU backend, the Pallas interpreter elsewhere).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro import plan as _plan
from repro.plan import gemm_model as _gemm
from repro.kernels import conv2d_psum as _conv
from repro.kernels import flash_attention as _flash
from repro.kernels import psum_matmul as _mm
from repro.kernels.launch import Interpret


def matmul(x: jax.Array, w: jax.Array, *, act: str = "none",
           controller: str = "active", vmem_budget: int | None = None,
           interpret: Interpret = None) -> jax.Array:
    """Partial-sum-scheduled GEMM with planner-chosen blocks."""
    m, k = x.shape
    n = w.shape[1]
    wl = _plan.MatmulWorkload(m=m, n=n, k=k)
    sched = _gemm.plan_gemm(
        wl, vmem_budget if vmem_budget is not None else _plan.DEFAULT_VMEM_BUDGET,
        _plan.Strategy.EXHAUSTIVE_VMEM, _plan.Controller.coerce(controller),
        max_block=512)
    # clamp to the (rounded-up) problem so tiny shapes keep tiny grids
    sched = dataclasses.replace(
        sched, bm=min(sched.bm, _round_up(m, 8)),
        bn=min(sched.bn, _round_up(n, 128)),
        bk=min(sched.bk, _round_up(k, 128)))
    return _mm.psum_matmul(x, w, schedule=sched, act=act, interpret=interpret)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1, pad: int | None = None,
           p_macs: int = 2048, strategy: str = "paper_opt", act: str = "none",
           interpret: Interpret = None) -> jax.Array:
    """Partitioned conv2d for one image. x: (Cin, H, W), w: (Cout, Cin, K, K).
    The (m, n) channel schedule comes from the paper's strategy at `p_macs`."""
    cin, h, w_sp = x.shape
    cout, _, kk, _ = w.shape
    pad = kk // 2 if pad is None else pad
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)))
    hp = h + 2 * pad
    ho = (hp - kk) // stride + 1
    wl = _plan.ConvWorkload(name="op", cin=cin, cout=cout, k=kk, wi=h, hi=h,
                            wo=ho, ho=ho, stride=stride)
    # The kernel's VMEM-resident accumulator is the active controller.
    sched = _plan.plan(wl, p_macs, strategy, "active").schedule
    return _conv.conv2d_psum(x, w, schedule=sched, stride=stride, act=act,
                             interpret=interpret)


def gqa_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, q_offset: int = 0,
                        bq: int = 128, bk: int = 128,
                        interpret: Interpret = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D) with Hq % Hkv == 0."""
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    out = _flash.flash_attention(
        q.reshape(b * hq, sq, d), k.reshape(b * hq, skv, d),
        v.reshape(b * hq, skv, d), causal=causal, q_offset=q_offset,
        bq=bq, bk=bk, interpret=interpret)
    return out.reshape(b, hq, sq, d)
