"""Self-checks of the chip benchmark. Run with an explicit path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On the CPU, at sizes a test run holds, with the Pallas kernels interpreted:
the work counts against hand counts, the peaks table, the trace reduction
(on synthetic intervals and on a trace recorded on a TPU v5e), a whole run
of each cell with the chip check skipped, each configuration's control
(its reference one precision lower) failing the configuration's limits, and
runs with the timed path broken underneath reading ``correct: false``.
"""

from __future__ import annotations

import copy
import dataclasses
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, str(HERE))       # as when run.py is the script
bench = _load(HERE / "run.py", "bench_run")
import xplane  # noqa: E402

V5E = bench.load_peaks("TPU v5 lite")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELLS = ("resnet18.p2048", "qwen2-ffn.active", "qwen2-ffn.passive")


def small(cell: "bench.Cell") -> "bench.Cell":
    """The cell at a size the Pallas interpreter runs in seconds: ResNet-18
    with a sixteenth of its channels at its published sizes and strides,
    two FFN layers of 384 -> 640 -> 384 over 256 tokens in 128-blocks (3 and
    5 k-steps); limits, loop and schedule kinds as committed."""
    cfg, mix = copy.deepcopy(cell.cfg), copy.deepcopy(cell.mix)
    if "nodes" in cfg:
        for node in cfg["nodes"]:
            for key in ("cin", "cout", "channels"):
                if key in node:
                    node[key] = max(1, node[key] // 16)
    else:
        cfg.update(tokens=256, hidden_size=384, intermediate_size=640,
                   num_hidden_layers=2)
        planned = cell.config._schedule      # a fresh module for each cell
        cell.config._schedule = lambda *a: dataclasses.replace(
            planned(*a), bm=128, bn=128, bk=128)
    mix.update(pool=2, sample=2)
    return dataclasses.replace(cell, cfg=cfg, mix=mix)


def run_small(name: str, seed: int = 2 ** 31 + 7, traced: bool = False):
    return bench.run_cell(small(bench.load_cell(name)), seed, 0.2, traced,
                          CPU, V5E, log=lambda line: None)


# ------------------------------------------------------------ work counts
def test_work_counts_match_hand_counts():
    net = bench.load_cell("resnet18.p2048")
    ffn = bench.load_cell("qwen2-ffn.active")
    w_net = net.config.work(net.cfg)
    w_ffn = ffn.config.work(ffn.cfg)
    # sum over the 19 convs after the stem of 2 * Cout * Cin * K^2 * Ho * Wo
    assert w_net["flops_per_step"] == 3_391_094_784
    assert len(w_net["kernels"]["conv2d_psum"]) == 19
    assert w_net["units_per_step"] == 1
    convs = {n["name"]: i for i, n in enumerate(
        n for n in net.cfg["nodes"] if n["op"] == "conv")}
    # conv20: 512 -> 512, 3x3 at 7x7: x, w, out in float32
    f, b = w_net["kernels"]["conv2d_psum"][convs["resnet18.conv20"]]
    assert f == 2 * 512 * 512 * 9 * 7 * 7
    assert b == 4 * (512 * 49 + 512 * 512 * 9 + 512 * 49)
    # conv8: the 1x1 stride-2 shortcut, 64 x 56 x 56 -> 128 x 28 x 28
    f, b = w_net["kernels"]["conv2d_psum"][convs["resnet18.conv8"]]
    assert f == 2 * 128 * 64 * 28 * 28
    assert b == 4 * (64 * 56 * 56 + 128 * 64 + 128 * 28 * 28)
    gemm = 2 * 2048 * 1536 * 8960
    assert gemm == 56_371_445_760
    assert w_ffn["flops_per_step"] == 28 * 3 * gemm == 4_735_201_443_840
    calls = w_ffn["kernels"]["psum_matmul"]
    assert len(calls) == 84
    up = [gemm, 2 * (2048 * 1536 + 1536 * 8960 + 2048 * 8960)]
    down = [gemm, 2 * (2048 * 8960 + 8960 * 1536 + 2048 * 1536)]
    assert calls[:3] == [up, up, down] and calls[-3:] == [up, up, down]
    assert w_ffn["units_per_step"] == 2048


def test_roofline_bound_of_the_ffn_gemms():
    ffn = bench.load_cell("qwen2-ffn.active")
    run = bench.Run(setup_s=0.0, window=bench.Window(1, 1.0, [], 0),
                    work=ffn.config.work(ffn.cfg), peaks=V5E)
    # compute-bound: 84 GEMMs of 56.37 GFLOP at 197 TFLOP/s
    assert run.kernel_min_s("psum_matmul") == pytest.approx(
        84 * 56.37e9 / 197e12, rel=1e-3)


def test_the_resnet_nodes_are_the_programs_resnet18_after_its_stem():
    """The configuration's nodes, the program's ResNet-18 (`from_cnn`) from
    the max-pool's output on: the same convs, shapes, strides and edges."""
    from repro.plan.graph import NetworkGraph

    net = bench.load_cell("resnet18.p2048")
    zoo = NetworkGraph.from_cnn("resnet18")
    ours = net.config.graph(net.cfg)
    start = zoo.producer[ours.inputs[0]]
    assert [(n.name, n.op, n.ins, n.out) for n in ours.nodes[1:]] == [
        (n.name, n.op, n.ins, n.out) for n in zoo.nodes[start + 1:]]
    assert [dataclasses.replace(wl, word_bytes=4) for wl in ours.workloads] \
        == list(zoo.workloads[1:])
    assert ours.tensors[ours.inputs[0]] == zoo.tensors[ours.inputs[0]]


# ------------------------------------------------------------------ peaks
def test_peaks_refuse_an_unknown_device():
    assert V5E["flops_per_s"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(bench.NoChipError, match="not in peaks.json"):
        bench.load_peaks("TPU v9 imaginary")


def test_the_harness_refuses_the_cpu():
    with pytest.raises(bench.NoChipError, match="needs a TPU.*cpu"):
        bench.require_tpu(1)


# ------------------------------------------------------- trace reduction
def test_union_and_gaps():
    busy = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert busy == [(0, 3), (5, 8)]
    assert xplane.gaps(busy, (0, 10)) == [(3, 5), (8, 10)]
    assert xplane.gaps(busy, (1, 6)) == [(3, 5)]
    assert xplane.gaps([], (0, 4)) == [(0, 4)]
    assert xplane.base_name("conv2d_psum.12") == "conv2d_psum"
    assert xplane.base_name("fusion") == "fusion"
    hlo = ("%conv2d_psum.1 = f32[32,16,3328]{2,1,0:T(8,128)S(1)} custom-call("
           "f32[1,37,14,3456]{3,2,1,0:T(8,128)S(1)} %copy.5)")
    assert xplane.base_name(hlo) == "conv2d_psum"
    assert xplane.label(hlo) == "conv2d_psum f32[32,16,3328]"


def test_summary_attributes_gaps_to_the_innermost_span():
    E = xplane.Event
    s = xplane.TraceSummary(
        window=(0.0, 100.0),
        devices=((E("conv2d_psum.1", 10, 30), E("fusion.2", 30, 45),
                  E("conv2d_psum.3", 60, 90)),),
        spans=(E("bench.window", 0, 100), E("bench.step", 0, 100),
               E("bench.runner", 0, 50), E("bench.sync", 50, 100)))
    assert s.busy_s == pytest.approx(65e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.op_seconds(lambda n: xplane.base_name(n) == "conv2d_psum") \
        == pytest.approx(50e-9)
    assert s.spans_at([5, 49, 75, 101]) == [
        "bench.runner", "bench.runner", "bench.sync", "outside bench spans"]
    idle = s.idle_by_span()
    assert idle["bench.runner"][1] == 1           # (0, 10)
    assert idle["bench.sync"] == (pytest.approx(25e-9), 2)  # (45, 60), (90, 100)
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["conv2d_psum", pytest.approx(50e-9)]


RECORDED = HERE / "tests" / "data" / "gemm_active.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_reduction_of_a_trace_recorded_on_the_chip():
    """A short window of one FFN GEMM under the active controller, recorded on one TPU v5e: the
    GEMM kernel's launches are found by name and fill most of the window."""
    s = xplane.load(RECORDED)
    assert len(s.devices) == 1
    names = [xplane.base_name(e.name) for e in s.devices[0]]
    assert names.count("psum_matmul") >= 1
    assert set(names) == {"psum_matmul", "pad", "slice"}
    assert 0 < s.busy_s <= s.window_s
    assert s.op_seconds(lambda n: True) >= s.busy_s * 0.999
    assert s.breakdown()["device_ops"][0][0].startswith("psum_matmul ")


# ------------------------------------------------------------ the loops
@pytest.mark.parametrize("step_s, ahead", ((0.0409, 196), (0.1922, 42),
                                           (12.0, 1), (0.0, 8_000_000)))
def test_steps_ahead_make_the_seconds_ahead(step_s, ahead):
    assert bench.steps_ahead(step_s) == ahead


def test_back_to_back_waits_for_each_step_ahead_steps_late(monkeypatch):
    """Each step is waited for once, in order, after ``ahead`` more were
    sent; the drain waits for the last ``ahead`` together."""
    waited = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: waited.append(x) or x)
    sent = iter(range(10 ** 9))

    class Built:
        pool = [None]

        @staticmethod
        def step(x):
            return next(sent)

    steps, seconds, lat = bench.back_to_back(
        Built(), 0.05, bench.Reservoir(1, 0), bench._span_factory(False), 3)
    assert steps > 3 and seconds >= 0.05 and lat == []
    assert waited[:-1] == list(range(steps - 3))
    assert waited[-1] == list(range(steps - 3, steps))


# ------------------------------------------------ whole runs on the CPU
@pytest.mark.parametrize("name", CELLS)
def test_a_small_run_of_each_cell_is_correct(name):
    res = run_small(name)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in bench.load_cell(name).end_to_end}
    if name.startswith("qwen2"):
        want.discard("image_p95_ms")
    assert set(res["metrics"]) == want
    assert list(res)[-1] == "check"


@pytest.mark.parametrize("name", ("resnet18.p2048", "qwen2-ffn.active"))
def test_control_fails_the_limits(name):
    """The reference one precision lower (float8 operands) in the
    program's place fails a limit, on three seeds, through as many layers
    as the configuration has."""
    committed = bench.load_cell(name)
    cell = small(committed)
    if "num_hidden_layers" in cell.cfg:
        cell.cfg["num_hidden_layers"] = committed.cfg["num_hidden_layers"]
    for seed in (1, 2 ** 31 + 11, 987654321987):
        nums = bench.compare(
            cell.config.reference(cell.cfg, cell.mix, seed, 0, control=True),
            cell.config.reference(cell.cfg, cell.mix, seed, 0))
        assert any(nums[k] > v for k, v in cell.cfg["limits"].items()), nums


# ----------------------------------------------------------- faults
def _perturb_one(out):
    return out.reshape(-1).at[7].add(1e3 * (1 + jnp.max(jnp.abs(out)))
                                     ).reshape(out.shape)


def test_an_altered_conv_answer_is_caught(monkeypatch):
    import repro.kernels.conv_network as net
    real = net.conv2d_psum
    calls = []

    def altered(x, w, **kw):
        calls.append(1)
        y = real(x, w, **kw)
        return _perturb_one(y) if len(calls) % 19 == 11 else y

    monkeypatch.setattr(net, "conv2d_psum", altered)
    res = run_small("resnet18.p2048")
    assert res["correct"] is False and res["failed"] >= 1


def test_an_altered_gemm_answer_is_caught(monkeypatch):
    import repro.kernels.psum_matmul as pm
    real = pm.psum_matmul
    monkeypatch.setattr(pm, "psum_matmul",
                        lambda x, w, **kw: _perturb_one(real(x, w, **kw)))
    res = run_small("qwen2-ffn.passive")
    assert res["correct"] is False


def test_half_of_the_tokens_left_out_is_caught(monkeypatch):
    """Half of the rows computed, the other half left as zeros."""
    import repro.kernels.psum_matmul as pm
    real = pm.psum_matmul

    def half(x, w, **kw):
        m = x.shape[0] // 2
        return jnp.zeros((x.shape[0], w.shape[1]), x.dtype).at[:m].set(
            real(x[:m], w, **kw))

    monkeypatch.setattr(pm, "psum_matmul", half)
    res = run_small("qwen2-ffn.active")
    assert res["correct"] is False
    assert res["check"]["rel_l2"]["value"] > 4 * res["check"]["rel_l2"]["limit"]


def test_a_layer_that_returns_its_input_is_caught(monkeypatch):
    """Every FFN layer's projections read as zeros, so each layer hands its
    input on unchanged."""
    import repro.kernels.psum_matmul as pm
    monkeypatch.setattr(pm, "psum_matmul", lambda x, w, **kw: jnp.zeros(
        (x.shape[0], w.shape[1]), x.dtype))
    res = run_small("qwen2-ffn.passive")
    assert res["correct"] is False


@pytest.mark.parametrize("seed", (0, 3, 2 ** 40 + 1))
def test_seeds_of_any_size_give_distinct_inputs(seed):
    common = _load(HERE / "common.py", "bench_common")
    a = jax.random.normal(common.key_of(seed), (4,))
    b = jax.random.normal(common.key_of(seed + 2 ** 32), (4,))
    assert not bool(jnp.all(a == b))
