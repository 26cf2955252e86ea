"""The instrumented active-memory-controller simulation must (a) compute the
same convolution as the jnp oracle and (b) meter exactly the traffic that the
analytical model of `repro.plan.conv_model` predicts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.amc import (MemoryController, analytical_interconnect_words,
                            run_partitioned_conv)
from repro.core.cnn_zoo import ConvLayer
from repro.plan import Partition


def _oracle_conv(x, w, stride, pad):
    out = jax.lax.conv_general_dilated(
        jnp.asarray(x)[None], jnp.asarray(w), window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return np.asarray(out[0])


def _mk(cin, cout, k, wi, stride=1):
    pad = k // 2
    wo = (wi + 2 * pad - k) // stride + 1
    return ConvLayer(name="t", cin=cin, cout=cout, k=k, wi=wi, hi=wi,
                     wo=wo, ho=wo, stride=stride)


CASES = [
    (_mk(8, 16, 3, 12), Partition(2, 4)),
    (_mk(6, 10, 1, 9), Partition(3, 5)),
    (_mk(16, 8, 5, 10, stride=2), Partition(4, 8)),
    (_mk(7, 9, 3, 11), Partition(3, 4)),     # non-dividing partitions
    (_mk(8, 16, 3, 12), Partition(8, 16)),   # single iteration: no psums
]


@pytest.mark.parametrize("layer,part", CASES)
@pytest.mark.parametrize("active", [False, True])
def test_amc_matches_oracle_and_model(layer, part, active):
    rng = np.random.default_rng(42)
    x = rng.standard_normal((layer.cin, layer.hi, layer.wi)).astype(np.float32)
    w = rng.standard_normal((layer.cout, layer.cin, layer.k, layer.k)).astype(np.float32)
    out, meter = run_partitioned_conv(layer, part, x, w, active=active)
    ref = _oracle_conv(x, w, layer.stride, layer.k // 2)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
    predicted = analytical_interconnect_words(layer, part, active)
    assert meter.interconnect_words == predicted, (
        f"metered {meter.interconnect_words} != model {predicted}")


@pytest.mark.parametrize("layer,part", CASES[:2])
def test_active_saves_interconnect_not_sram_writes(layer, part):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((layer.cin, layer.hi, layer.wi)).astype(np.float32)
    w = rng.standard_normal((layer.cout, layer.cin, layer.k, layer.k)).astype(np.float32)
    _, mp = run_partitioned_conv(layer, part, x, w, active=False)
    _, ma = run_partitioned_conv(layer, part, x, w, active=True)
    assert ma.interconnect_words < mp.interconnect_words
    assert ma.sram_writes == mp.sram_writes  # the work still happens, locally


def test_activation_offload():
    """ACT command: in-controller ReLU produces relu(conv) with no extra bus
    words for the active controller (passive pays read+write)."""
    layer, part = CASES[0]
    rng = np.random.default_rng(1)
    x = rng.standard_normal((layer.cin, layer.hi, layer.wi)).astype(np.float32)
    w = rng.standard_normal((layer.cout, layer.cin, layer.k, layer.k)).astype(np.float32)
    out_a, meter_a = run_partitioned_conv(layer, part, x, w, active=True, act=True)
    out_p, meter_p = run_partitioned_conv(layer, part, x, w, active=False, act=True)
    ref = np.maximum(_oracle_conv(x, w, layer.stride, layer.k // 2), 0.0)
    np.testing.assert_allclose(out_a, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out_p, ref, rtol=1e-4, atol=1e-4)
    base_a = analytical_interconnect_words(layer, part, True)
    base_p = analytical_interconnect_words(layer, part, False)
    n_out = layer.wo * layer.ho * layer.cout
    assert meter_a.interconnect_words == base_a            # free for active
    assert meter_p.interconnect_words == base_p + 2 * n_out  # read+write extra


def test_controller_normal_mode():
    mc = MemoryController((4, 4), active=True)
    vals = np.ones((2, 4), np.float32)
    mc.write(np.s_[0:2], vals)
    got = mc.read(np.s_[0:2])
    np.testing.assert_array_equal(got, vals)
    assert mc.meter.interconnect_words == 16
