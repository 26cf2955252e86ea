"""Per-kernel allclose sweeps (shapes x dtypes) against the jnp oracles,
executed in interpret mode on CPU. Plus property tests on the schedules, and
each kernel under the TPU-faithful interpreter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:   # optional dep: fall back to the vendored stub
    from _hypothesis_stub import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.psum_matmul import hbm_traffic_bytes, psum_matmul

jax.config.update("jax_enable_x64", False)


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


MM_SHAPES = [(16, 16, 16), (128, 128, 128), (256, 384, 512), (100, 130, 70),
             (8, 512, 256), (512, 8, 8)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("controller", ["active", "passive"])
def test_psum_matmul_allclose(m, k, n, dtype, controller):
    rng = np.random.default_rng(m * 7 + k + n)
    x = _rand(rng, (m, k), dtype)
    w = _rand(rng, (k, n), dtype)
    got = psum_matmul(x, w, bm=64, bn=128, bk=64, controller=controller)
    want = ref.matmul_ref(x, w)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("act", ["relu", "silu", "gelu"])
@pytest.mark.parametrize("controller", ["active", "passive"])
def test_psum_matmul_fused_activation(act, controller):
    rng = np.random.default_rng(0)
    x = _rand(rng, (96, 160), jnp.float32)
    w = _rand(rng, (160, 224), jnp.float32)
    got = psum_matmul(x, w, bm=32, bn=64, bk=64, act=act, controller=controller)
    want = ref.matmul_ref(x, w, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-4)


def test_active_passive_identical_results():
    """The two schedules are numerically equivalent (both fp32 accumulate)."""
    rng = np.random.default_rng(3)
    x = _rand(rng, (192, 320), jnp.bfloat16)
    w = _rand(rng, (320, 256), jnp.bfloat16)
    a = psum_matmul(x, w, bm=64, bn=128, bk=64, controller="active")
    p = psum_matmul(x, w, bm=64, bn=128, bk=64, controller="passive")
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(p, np.float32), rtol=1e-2, atol=1e-2)


def test_traffic_model_active_saves():
    m = n = k = 2048
    kw = dict(bm=256, bn=256, bk=256)
    ta = hbm_traffic_bytes(m, n, k, controller="active", **kw)
    tp = hbm_traffic_bytes(m, n, k, controller="passive", **kw)
    assert ta < tp
    # with gk=8 reduction steps, passive pays (2*8-1)*4 bytes vs 2 bytes out
    assert tp - ta == ((2 * 8 - 1) * 4 - 2) * m * n


@settings(max_examples=25, deadline=None)
@given(m=st.integers(8, 160), k=st.integers(8, 160), n=st.integers(8, 160),
       bm=st.sampled_from([16, 32, 64]), bk=st.sampled_from([16, 32, 64]),
       bn=st.sampled_from([32, 64, 128]))
def test_property_matmul_any_blocking(m, k, n, bm, bk, bn):
    """Result is block-shape-independent (paper: partitioning changes traffic,
    never the math)."""
    rng = np.random.default_rng(m + k + n)
    x = _rand(rng, (m, k), jnp.float32)
    w = _rand(rng, (k, n), jnp.float32)
    got = psum_matmul(x, w, bm=bm, bn=bn, bk=bk)
    want = ref.matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-4)


CONV_CASES = [
    # (cin, cout, k, h, stride, block_m, block_n)
    (8, 16, 3, 12, 1, 4, 8),
    (16, 32, 1, 10, 1, 8, 16),
    (6, 10, 5, 16, 2, 3, 5),
    (32, 24, 3, 14, 1, 32, 24),   # single iteration
    (12, 20, 3, 9, 1, 5, 7),      # non-dividing blocks
]


@pytest.mark.parametrize("cin,cout,k,h,stride,bm,bn", CONV_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_conv2d_psum_allclose(cin, cout, k, h, stride, bm, bn, dtype):
    from repro.kernels.conv2d_psum import conv2d_psum
    rng = np.random.default_rng(cin * cout)
    pad = k // 2
    x = _rand(rng, (cin, h + 2 * pad, h + 2 * pad), dtype)
    w = _rand(rng, (cout, cin, k, k), dtype)
    got = conv2d_psum(x, w, block_m=bm, block_n=bn, stride=stride)
    want = ref.conv2d_ref(x, w, stride=stride)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_conv2d_fused_relu():
    from repro.kernels.conv2d_psum import conv2d_psum
    rng = np.random.default_rng(5)
    x = _rand(rng, (8, 14, 14), jnp.float32)
    w = _rand(rng, (16, 8, 3, 3), jnp.float32)
    got = conv2d_psum(x, w, block_m=4, block_n=8, act="relu")
    want = ref.conv2d_ref(x, w, act="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(got) >= 0).all()


def test_conv2d_ops_wrapper_uses_paper_partition():
    rng = np.random.default_rng(7)
    x = _rand(rng, (24, 16, 16), jnp.float32)
    w = _rand(rng, (48, 24, 3, 3), jnp.float32)
    got = ops.conv2d(x, w, p_macs=512, strategy="paper_opt")
    want = jax.lax.conv_general_dilated(
        x[None], w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


ATTN_CASES = [
    # (bh, sq, skv, d, causal, bq, bk)
    (2, 128, 128, 64, True, 64, 64),
    (1, 64, 64, 32, False, 32, 32),
    (3, 100, 100, 64, True, 32, 32),     # padded q
    (2, 1, 256, 64, True, 1, 64),        # decode: q_len=1
    (2, 8, 384, 128, True, 8, 128),      # speculative block decode
]


@pytest.mark.parametrize("bh,sq,skv,d,causal,bq,bk", ATTN_CASES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention_allclose(bh, sq, skv, d, causal, bq, bk, dtype):
    from repro.kernels.flash_attention import flash_attention
    rng = np.random.default_rng(bh + sq + d)
    q = _rand(rng, (bh, sq, d), dtype)
    k = _rand(rng, (bh, skv, d), dtype)
    v = _rand(rng, (bh, skv, d), dtype)
    q_off = skv - sq if causal else 0
    got = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk, q_offset=q_off)
    want = ref.attention_ref(q, k, v, causal=causal, q_offset=q_off)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


def test_gqa_wrapper():
    rng = np.random.default_rng(11)
    b, hq, hkv, s, d = 2, 8, 2, 64, 32
    q = _rand(rng, (b, hq, s, d), jnp.float32)
    k = _rand(rng, (b, hkv, s, d), jnp.float32)
    v = _rand(rng, (b, hkv, s, d), jnp.float32)
    got = ops.gqa_flash_attention(q, k, v, bq=32, bk=32)
    kr = jnp.repeat(k, hq // hkv, axis=1).reshape(b * hq, s, d)
    vr = jnp.repeat(v, hq // hkv, axis=1).reshape(b * hq, s, d)
    want = ref.attention_ref(q.reshape(b * hq, s, d), kr, vr).reshape(b, hq, s, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(sq=st.integers(16, 96), d=st.sampled_from([32, 64]),
       bq=st.sampled_from([16, 32]), bk=st.sampled_from([16, 32]))
def test_property_flash_block_invariance(sq, d, bq, bk):
    from repro.kernels.flash_attention import flash_attention
    rng = np.random.default_rng(sq * d)
    q = _rand(rng, (1, sq, d), jnp.float32)
    k = _rand(rng, (1, sq, d), jnp.float32)
    v = _rand(rng, (1, sq, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


# The TPU-faithful interpreter models what Mosaic does and the plain one
# does not: grid semantics must be legal, and an output block is never
# fetched from HBM, so a kernel that reads back an output block it did not
# write in the same visit sees garbage (uninitialized memory is NaN).
TPU_INTERPRETER_CASES = {
    "matmul-active": dict(m=256, k=384, n=256, controller="active"),
    # gm = gn = 2 output blocks, gk = 3 k-steps of HBM partial sums
    "matmul-passive": dict(m=256, k=384, n=256, controller="passive"),
    "conv-stride1": dict(cin=8, cout=16, k=3, h=12, stride=1, bm=4, bn=8),
    "conv-stride2": dict(cin=6, cout=10, k=5, h=16, stride=2, bm=3, bn=5),
    "flash": dict(bh=2, s=128, d=64, bq=64, bk=64),
}


@pytest.mark.parametrize("case", sorted(TPU_INTERPRETER_CASES))
def test_tpu_interpreter_matches_ref(case):
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.conv2d_psum import conv2d_psum
    from repro.kernels.flash_attention import flash_attention
    tpu = pltpu.InterpretParams()
    c = TPU_INTERPRETER_CASES[case]
    rng = np.random.default_rng(len(case))
    if case.startswith("matmul"):
        x = _rand(rng, (c["m"], c["k"]), jnp.float32)
        w = _rand(rng, (c["k"], c["n"]), jnp.float32)
        got = psum_matmul(x, w, bm=128, bn=128, bk=128,
                          controller=c["controller"], interpret=tpu)
        want = ref.matmul_ref(x, w)
    elif case.startswith("conv"):
        side = c["h"] + 2 * (c["k"] // 2)
        x = _rand(rng, (c["cin"], side, side), jnp.float32)
        w = _rand(rng, (c["cout"], c["cin"], c["k"], c["k"]), jnp.float32)
        got = conv2d_psum(x, w, block_m=c["bm"], block_n=c["bn"],
                          stride=c["stride"], interpret=tpu)
        want = ref.conv2d_ref(x, w, stride=c["stride"])
    else:
        q, k, v = (_rand(rng, (c["bh"], c["s"], c["d"]), jnp.float32)
                   for _ in range(3))
        got = flash_attention(q, k, v, bq=c["bq"], bk=c["bk"], interpret=tpu)
        want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_launch_requests_the_planner_vmem_limit(monkeypatch):
    """One VMEM constant: what every launch asks Mosaic for is the planner's
    budget and the preflight's limit."""
    from repro import plan
    from repro.check.kernels import check_launch
    from repro.kernels import launch
    from repro.kernels.psum_matmul import matmul_launch_plan
    from repro.plan.gemm_model import VMEM_LIMIT_BYTES
    seen = []
    real = launch.pl.pallas_call

    def spy(*args, **kwargs):
        seen.append(kwargs["compiler_params"].vmem_limit_bytes)
        return real(*args, **kwargs)

    monkeypatch.setattr(launch.pl, "pallas_call", spy)
    x = jnp.ones((24, 40), jnp.float32)       # a shape no other test traces
    psum_matmul(x, x.T, bm=8, bn=128, bk=128)
    assert seen == [VMEM_LIMIT_BYTES] == [plan.DEFAULT_VMEM_BUDGET]
    big = matmul_launch_plan(m=4096, k=4096, n=4096, bm=4096, bn=4096,
                             bk=4096)
    assert "RPC032" in {d.code for d in check_launch(big)}
