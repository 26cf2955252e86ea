"""Flash attention — the paper's active-accumulation principle applied to
attention: the (running max, running denominator, weighted-value accumulator)
triple is the partial sum, kept VMEM-resident across KV blocks instead of
materializing S = QK^T to HBM (which would be the passive schedule).

Grid: (batch*heads, q_blocks, kv_blocks), kv innermost ('arbitrary'); causal
masking skips fully-masked kv blocks' contribution via the mask itself (the
index space is rectangular; masked blocks contribute exp(-inf)=0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import launch

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int, n_kv: int,
                  q_offset: int, skv: int):
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal:
        iq = pl.program_id(1)
        q_ids = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
        k_ids = kv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        # k_ids < skv also masks the zero-padded kv tail, which the causal
        # triangle alone leaves visible whenever q_offset + sq > skv (decode
        # with a padded cache) — padded keys would contribute exp(0) weight.
        s = jnp.where((q_ids >= k_ids) & (k_ids < skv), s, NEG_INF)

    m_prev = m_ref[...]                        # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                     # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)            # rescale old partial sums
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jnp.dot(p, v_ref[0].astype(jnp.float32),
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kv == n_kv - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_launch_plan(*, bh: int, sq: int, skv: int, d: int, bq: int = 128,
                      bk: int = 128, causal: bool = True, q_offset: int = 0,
                      dtype=None) -> launch.LaunchPlan:
    """The launch `flash_attention` executes, from plain integers — same
    block clamping and sequence padding the entry point applies."""
    bq = max(1, min(bq, sq))
    bk = max(1, min(bk, skv))
    sq_p = sq + (-sq) % bq
    skv_p = skv + (-skv) % bk
    gq = sq_p // bq
    gk = skv_p // bk
    scale = 1.0 / (d ** 0.5)
    return launch.LaunchPlan(
        name="flash_attention",
        grid=(bh, gq, gk),
        body=functools.partial(_flash_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, n_kv=gk, q_offset=q_offset,
                               skv=skv),
        inputs=(
            launch.OperandPlan("q", (bh, sq_p, d), (1, bq, d),
                               lambda b, iq, ik: (b, iq, 0)),
            launch.OperandPlan("k", (bh, skv_p, d), (1, bk, d),
                               lambda b, iq, ik: (b, ik, 0)),
            launch.OperandPlan("v", (bh, skv_p, d), (1, bk, d),
                               lambda b, iq, ik: (b, ik, 0)),
        ),
        outputs=(
            launch.OperandPlan("out", (bh, sq_p, d), (1, bq, d),
                               lambda b, iq, ik: (b, iq, 0), dtype=dtype),
        ),
        scratch=(
            launch.ScratchPlan("acc", (bq, d), jnp.float32),
            launch.ScratchPlan("m", (bq, 1), jnp.float32),
            launch.ScratchPlan("l", (bq, 1), jnp.float32),
        ),
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret",
                                             "q_offset"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    q_offset: int = 0,
                    interpret: launch.Interpret = None) -> jax.Array:
    """q: (BH, Sq, D), k/v: (BH, Skv, D). GQA is handled by the caller
    (reshape/broadcast of kv heads). q_offset shifts causal indices for
    decode (q positions start at q_offset).

    The launch is statically pre-flighted (`repro.check`): malformed
    grids/BlockSpecs and the unmaskable non-causal padded-kv case raise a
    `CheckError` before anything compiles, and the kernel body's dataflow
    proofs (RPC04x: race/init/coverage/accumulation and the closed-form
    traffic pins) run once per launch geometry. Padded kv keys are masked
    inside the kernel (``k_ids < skv``) when causal."""
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    from repro.check import preflight_flash_dataflow
    preflight_flash_dataflow(bh, sq, skv, d, bq=bq, bk=bk, causal=causal,
                             q_offset=q_offset)
    plan = flash_launch_plan(bh=bh, sq=sq, skv=skv, d=d, bq=bq, bk=bk,
                             causal=causal, q_offset=q_offset, dtype=q.dtype)
    pq = plan.inputs[0].array_shape[1] - sq
    pk = plan.inputs[1].array_shape[1] - skv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        # zero-padded keys/values; the kernel masks k_ids >= skv when causal
        # (the non-causal padded case is rejected by the preflight above).
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    out = launch.run(plan, q, k, v, interpret=interpret)
    return out[:, :sq]
