"""Self-checks of the ``dsv2-lite-moe.skew`` cell. Run with an explicit path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On the CPU with the Pallas kernels interpreted, at a size a test run holds:
a whole run of the cell reads ``correct: true``; the float8 control fails
the limits; runs whose MoE layers leave out the sixth pick or the shared
expert read ``correct: false``; ``work()`` against a hand count; and the
skew generator loads its busiest expert well above the mean.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
CELL = "dsv2-lite-moe.skew"


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, str(HERE))       # as when run.py is the script
bench = _load(HERE / "run.py", "bench_run")

V5E = bench.load_peaks("TPU v5 lite")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 31 + 7


def small(cell: "bench.Cell", layers: int = 3) -> "bench.Cell":
    """The cell at a size the Pallas interpreter runs in seconds: hidden 256,
    a dense layer of 512, then MoE layers of 8 experts of 128, top-2, one
    shared expert, over 128 tokens of 4 topics; limits, loop and schedule as
    committed."""
    cfg, mix = copy.deepcopy(cell.cfg), copy.deepcopy(cell.mix)
    cfg.update(tokens=128, hidden_size=256, intermediate_size=512,
               moe_intermediate_size=128, n_routed_experts=8,
               num_experts_per_tok=2, n_shared_experts=1,
               num_hidden_layers=layers)
    mix.update(pool=2, sample=2)
    mix["skew"] = dict(mix["skew"], topics=4)
    return dataclasses.replace(cell, cfg=cfg, mix=mix)


def run_small(layers: int = 3):
    return bench.run_cell(small(bench.load_cell(CELL), layers), SEED, 0.2,
                          False, CPU, V5E, log=lambda line: None)


@pytest.fixture
def moe_ffn():
    """The program's MoE module, its jitted layers dropped before and after
    so that a patched layer function is the one traced."""
    from repro.kernels import moe_ffn as mod
    mod._layer_fn.cache_clear()
    yield mod
    mod._layer_fn.cache_clear()


def test_a_small_run_of_the_cell_is_correct(moe_ffn):
    res = run_small()
    assert res["correct"] is True, res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"ffn_tokens_per_s", "setup_s"}


def test_control_fails_the_limits():
    """The reference with float8 FFN GEMM operands in the program's place
    fails a limit, on three seeds, through as many layers as committed."""
    committed = bench.load_cell(CELL)
    cell = small(committed, committed.cfg["num_hidden_layers"])
    for seed in (1, 2 ** 31 + 11, 987654321987):
        nums = bench.compare(
            cell.config.reference(cell.cfg, cell.mix, seed, 0, control=True),
            cell.config.reference(cell.cfg, cell.mix, seed, 0))
        assert any(nums[k] > v for k, v in cell.cfg["limits"].items()), nums


def test_a_layer_without_its_last_pick_is_caught(monkeypatch, moe_ffn):
    """Each token's sixth (here second) expert weighted by 0."""
    real = moe_ffn.route

    def five(n, router, top_k):
        weights, picks = real(n, router, top_k)
        return weights.at[:, -1].set(0.0), picks

    monkeypatch.setattr(moe_ffn, "route", five)
    res = run_small()
    assert res["correct"] is False


def test_a_layer_without_its_shared_expert_is_caught(monkeypatch, moe_ffn):
    real = moe_ffn.moe_layer

    def unshared(x, params, schedules, **kw):
        zero = {k: jnp.zeros_like(v) for k, v in params["shared"].items()}
        return real(x, dict(params, shared=zero), schedules, **kw)

    monkeypatch.setattr(moe_ffn, "moe_layer", unshared)
    res = run_small()
    assert res["correct"] is False


def test_work_counts_match_hand_counts():
    cell = bench.load_cell(CELL)
    w = cell.config.work(cell.cfg)
    dense = w["kernels"]["psum_matmul"]
    grouped = w["kernels"]["psum_grouped_matmul"]
    # layer 0: three dense GEMMs of 2048 x 2048 x 10944; layers 1-10: three
    # shared-expert GEMMs of 2048 x 2048 x 2816 and three grouped GEMMs over
    # 12288 routed rows and 64 experts of 1408
    assert len(dense) == 3 + 10 * 3 and len(grouped) == 10 * 3
    up = [2.0 * 2048 * 2048 * 10944,
          2 * (2048 * 2048 + 2048 * 10944 + 2048 * 10944)]
    assert dense[0] == dense[1] == up
    assert dense[3] == [2.0 * 2048 * 2048 * 2816,
                        2 * (2048 * 2048 + 2048 * 2816 + 2048 * 2816)]
    assert grouped[0] == [2.0 * 12288 * 2048 * 1408,
                          2 * (12288 * 2048 + 64 * 2048 * 1408
                               + 12288 * 1408)]
    assert grouped[2] == [2.0 * 12288 * 1408 * 2048,
                          2 * (12288 * 1408 + 64 * 1408 * 2048
                               + 12288 * 2048)]
    assert w["units_per_step"] == 2048
    assert w["flops_per_step"] == 3 * 2 * 2048 * 2048 * 10944 + 10 * 3 * 2 * (
        2048 * 2048 * 2816 + 12288 * 2048 * 1408)     # 3.12 TFLOP


def test_the_skew_loads_the_busiest_expert_above_twice_the_mean():
    """Layer 1's router on the generator's hidden states at a small width
    (64 experts, top-6, 2048 tokens of 16 topics, hidden 256)."""
    cell = bench.load_cell(CELL)
    cfg = dict(cell.cfg, hidden_size=256, moe_intermediate_size=128)
    x = cell.config.hidden_states(cfg, dict(cell.mix, pool=1), 12345)[0]
    router = cell.config.layer_weights(cfg, 12345, 1)["router"]
    x = x.astype(jnp.float32)
    n = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    _, picks = jax.lax.top_k(jax.nn.softmax(n @ router, -1), 6)
    rows = np.bincount(np.asarray(picks).ravel(), minlength=64)
    assert rows.max() / rows.mean() > 2.0, rows
