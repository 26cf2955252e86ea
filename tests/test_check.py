"""Tests for `repro.check` — the static plan/kernel verifier and lint.

Covers: every planner output verifying clean (property tests over random
valid workloads for both plan() and plan_graph()), one deliberately corrupted
input per diagnostic code (>= 10 distinct codes), the Pallas pre-flight gate
rejecting a malformed launch *before* any kernel compiles, the checked=True
modes on plan()/plan_graph()/simulate(), the AST lint rules on synthetic
sources plus the repo itself being lint-clean, and the regression pin for the
`hbm_traffic_bytes` delegation the lint forced.
"""

import ast
import dataclasses

import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:   # optional dep: fall back to the vendored stub
    from _hypothesis_stub import given, settings, st

import repro.check as rc
from repro import plan
from repro.check import lint as rlint
from repro.check.diagnostics import CODES, Severity
from repro.plan.schedule import Controller, Schedule
from repro.plan.workload import ConvWorkload, MatmulWorkload


def _codes(diags):
    return {d.code for d in diags}


def _conv_wl(mg=16, ng=32, g=1, k=3, s=28):
    return ConvWorkload(name="t", cin=g * mg, cout=g * ng, k=k,
                        wi=s, hi=s, wo=s, ho=s, groups=g)


# ---------------------------------------------------------------- registry
def test_code_registry_is_stable():
    # renaming/renumbering a code is an API break — pin the published set
    assert {"RPC001", "RPC002", "RPC003", "RPC004", "RPC005", "RPC006",
            "RPC007", "RPC008", "RPC010", "RPC011", "RPC012", "RPC013",
            "RPC020", "RPC021", "RPC022", "RPC030", "RPC031", "RPC032",
            "RPC033", "RPL100", "RPL101", "RPL102", "RPL110"} <= set(CODES)
    assert CODES["RPC001"].slug == "mac-budget-exceeded"
    assert CODES["RPC010"].slug == "words-bytes-mix"
    assert CODES["RPC020"].slug == "residency-overlap"
    for info in CODES.values():
        assert info.summary and info.hint


def test_diagnostic_rendering():
    d = rc.Diagnostic("RPC001", "conv1", "too big", file="src/x.py", line=3)
    assert d.severity is Severity.ERROR
    assert "RPC001 mac-budget-exceeded [conv1]" in d.render()
    gh = d.render_github()
    assert gh.startswith("::error file=src/x.py,line=3::RPC001")
    with pytest.raises(ValueError):
        rc.Diagnostic("RPC999", "x", "no such code")


# -------------------------------------------------- clean planner outputs
@pytest.mark.parametrize("net", ["alexnet", "squeezenet", "mobilenet"])
@pytest.mark.parametrize("ctrl", ["passive", "active"])
def test_zoo_plans_verify_clean(net, ctrl):
    for wl in plan.conv_workloads(net):
        assert rc.check(plan.plan(wl, controller=ctrl)) == []


@pytest.mark.parametrize("ctrl", ["passive", "active"])
def test_zoo_netplans_verify_clean(ctrl):
    netp = plan.plan_graph("squeezenet", controller=ctrl, checked=True)
    assert rc.check(netp) == []


conv_wl_st = st.builds(
    _conv_wl,
    mg=st.integers(1, 96), ng=st.integers(1, 96),
    g=st.sampled_from([1, 2, 4]),
    k=st.sampled_from([1, 3, 5, 7]),
    s=st.integers(4, 40))


@settings(max_examples=40, deadline=None)
@given(wl=conv_wl_st,
       strategy=st.sampled_from(["paper_opt", "exact_opt", "max_input",
                                 "equal"]),
       controller=st.sampled_from(["passive", "active"]),
       budget=st.sampled_from([512, 2048, 8192]))
def test_property_conv_plans_verify_clean(wl, strategy, controller, budget):
    # any plan over a valid workload and a feasible budget must prove clean
    p = plan.plan(wl, budget, strategy, controller, checked=True)
    assert rc.check(p) == []


@settings(max_examples=20, deadline=None)
@given(m=st.integers(64, 4096), n=st.integers(64, 4096),
       k=st.integers(64, 4096),
       controller=st.sampled_from(["passive", "active"]))
def test_property_gemm_plans_have_no_errors(m, n, k, controller):
    wl = MatmulWorkload(m=m, n=n, k=k)
    p = plan.plan(wl, strategy="exhaustive_vmem", controller=controller)
    assert rc.errors(rc.check(p)) == []


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       residency_kib=st.sampled_from([0, 64, 2048]),
       controller=st.sampled_from(["passive", "active"]))
def test_property_random_graphs_plan_clean(seed, residency_kib, controller):
    import random
    rng = random.Random(seed)
    layers = []
    c_in, s = rng.choice([3, 8, 16]), rng.choice([16, 28, 32])
    for i in range(rng.randint(2, 5)):
        c_out = rng.choice([8, 16, 24, 32])
        k = rng.choice([1, 3])
        layers.append(ConvWorkload(name=f"l{i}", cin=c_in, cout=c_out, k=k,
                                   wi=s, hi=s, wo=s, ho=s))
        c_in = c_out
    netp = plan.plan_graph(layers, controller=controller,
                           residency_bytes=residency_kib * 1024,
                           checked=True)
    assert rc.check(netp) == []


# ------------------------------------------- corrupted inputs trip codes
def test_rpc001_mac_budget_exceeded():
    wl = _conv_wl()
    sched = Schedule(kind="conv", bm=16, bn=32)   # K^2*m*n = 4608 > 512
    assert "RPC001" in _codes(rc.check_schedule(wl, sched, budget=512))


def test_rpc002_block_exceeds_extent():
    wl = _conv_wl(mg=8, ng=8)
    got = rc.check_schedule(wl, Schedule(kind="conv", bm=16, bn=4),
                            budget=4096)
    assert "RPC002" in _codes(got)
    got = rc.check_schedule(wl, Schedule(kind="conv", bm=4, bn=4, bk=2),
                            budget=4096)
    assert "RPC002" in _codes(got)   # convs never tile the reduction


def test_rpc003_schedule_kind_mismatch():
    wl = _conv_wl()
    bad = Schedule(kind="matmul", bm=128, bn=128, bk=128)
    assert _codes(rc.check_schedule(wl, bad)) == {"RPC003"}
    with pytest.raises(rc.CheckError):
        from repro.sim import simulate
        simulate(wl, bad, checked=True)


def test_rpc004_group_indivisible():
    wl = _conv_wl()
    object.__setattr__(wl, "groups", 3)          # 3 does not divide 16/32
    assert "RPC004" in _codes(rc.check_workload(wl))


def test_rpc005_lane_misaligned_warns():
    wl = MatmulWorkload(m=512, n=512, k=512)
    got = rc.check_schedule(wl, Schedule(kind="matmul", bm=100, bn=128,
                                         bk=128))
    assert "RPC005" in _codes(got)
    assert all(d.severity is Severity.WARNING for d in got)


def test_rpc006_vmem_budget_exceeded():
    wl = MatmulWorkload(m=4096, n=4096, k=4096)
    big = Schedule(kind="matmul", bm=4096, bn=4096, bk=4096)
    assert "RPC006" in _codes(rc.check_schedule(wl, big, budget=2**20))


def test_rpc007_traffic_mismatch():
    p = plan.plan(_conv_wl())
    bad = dataclasses.replace(
        p, traffic=dataclasses.replace(
            p.traffic,
            interconnect_words=p.traffic.interconnect_words + 1.0))
    assert "RPC007" in _codes(rc.check_plan(bad))
    with pytest.raises(rc.CheckError):
        rc.verify(bad)


def test_rpc008_workload_malformed():
    wl = _conv_wl()
    object.__setattr__(wl, "k", 0)
    assert _codes(rc.check_workload(wl)) == {"RPC008"}


def test_rpc010_words_bytes_mix():
    p = plan.plan(_conv_wl())
    bad = dataclasses.replace(
        p, traffic=dataclasses.replace(p.traffic,
                                       bytes=p.traffic.bytes + 1.0))
    # words still match the model: only the unit-discipline check fires
    assert _codes(rc.check_plan(bad)) == {"RPC010"}

    g = plan.plan(MatmulWorkload(m=512, n=512, k=512))
    bad_g = dataclasses.replace(
        g, traffic=dataclasses.replace(g.traffic,
                                       bytes=g.traffic.bytes + 1.0))
    assert "RPC010" in _codes(rc.check_plan(bad_g))


def _small_netplan(**kw):
    layers = [ConvWorkload(name=f"l{i}", cin=c, cout=c2, k=3,
                           wi=16, hi=16, wo=16, ho=16)
              for i, (c, c2) in enumerate([(8, 16), (16, 16), (16, 8)])]
    return plan.plan_graph(layers, **kw)


def test_rpc011_edge_dtype_mismatch():
    netp = _small_netplan()
    g = netp.graph
    t = g.workload_nodes[0].ins[0]
    g.tensors[t] = dataclasses.replace(g.tensors[t], word_bytes=8)
    assert "RPC011" in _codes(rc.check_graph(g))


def test_rpc012_word_conservation():
    netp = _small_netplan()
    bad = dataclasses.replace(
        netp, traffic=dataclasses.replace(
            netp.traffic,
            interconnect_words=netp.traffic.interconnect_words + 64.0))
    assert "RPC012" in _codes(rc.check_netplan(bad))


def test_rpc013_graph_shape_mismatch():
    netp = _small_netplan()
    g = netp.graph
    t = g.workload_nodes[0].out
    g.tensors[t] = dataclasses.replace(g.tensors[t],
                                       channels=g.tensors[t].channels + 1)
    assert "RPC013" in _codes(rc.check_graph(g))


def test_rpc020_residency_overlap():
    netp = _small_netplan(residency_bytes=1 << 20)
    assert netp.resident_tensors             # something actually fused
    bad = dataclasses.replace(netp, residency_bytes=64)
    assert "RPC020" in _codes(rc.check_netplan(bad))


def test_rpc021_non_residable_resident():
    netp = _small_netplan()
    g = netp.graph
    inp = g.inputs[0]
    edges = tuple(dataclasses.replace(e, resident=True)
                  if e.tensor == inp else e for e in netp.edges)
    bad = dataclasses.replace(netp, edges=edges)
    assert "RPC021" in _codes(rc.check_netplan(bad))


def test_rpc022_peak_resident_mismatch_warns():
    netp = _small_netplan(residency_bytes=1 << 20)
    bad = dataclasses.replace(netp,
                              peak_resident_bytes=netp.peak_resident_bytes + 1)
    got = [d for d in rc.check_netplan(bad) if d.code == "RPC022"]
    assert got and got[0].severity is Severity.WARNING
    rc.verify(bad)      # warnings alone never raise


# --------------------------------------------------- kernel launch checks
def _one_operand_launch(grid, array_shape, block_shape):
    from repro.kernels.launch import LaunchPlan, OperandPlan
    return LaunchPlan(
        name="t", grid=grid, body=lambda *refs: None, inputs=(),
        outputs=(OperandPlan("x", array_shape, block_shape, lambda i: (i,)),))


def test_rpc030_blockspec_indivisible():
    launch = _one_operand_launch((2,), (100,), (32,))
    assert "RPC030" in _codes(rc.check_launch(launch))


def test_rpc031_index_map_out_of_range():
    launch = _one_operand_launch((4,), (64,), (32,))
    assert "RPC031" in _codes(rc.check_launch(launch))   # blocks 0..1, grid 0..3


def test_rpc032_kernel_vmem_exceeded():
    wl = ConvWorkload(name="t", cin=64, cout=64, k=3, wi=56, hi=56,
                      wo=56, ho=56)
    sched = Schedule(kind="conv", bm=64, bn=64)
    assert rc.check_conv_launch(wl, sched) == []         # fits the limit
    got = rc.check_conv_launch(wl, sched, vmem_budget=1 << 16)
    assert "RPC032" in _codes(got)


def test_kernel_launch_checks_match_real_kernels():
    # the checker reads the kernels' own launch plans; anything it admits
    # at defaults must actually execute
    import numpy as np
    from repro.kernels.conv2d_psum import conv2d_psum
    wl = ConvWorkload(name="t", cin=6, cout=10, k=3, wi=8, hi=8,
                      wo=8, ho=8)
    sched = Schedule(kind="conv", bm=4, bn=4)
    assert rc.check_conv_launch(wl, sched) == []
    x = np.random.default_rng(0).normal(size=(6, 10, 10)).astype("float32")
    w = np.random.default_rng(1).normal(size=(10, 6, 3, 3)).astype("float32")
    out = conv2d_psum(x, w, schedule=sched)
    assert out.shape == (10, 8, 8)

    assert rc.check_matmul_launch(
        256, 256, 256, Schedule(kind="matmul", bm=128, bn=128, bk=128)) == []


def test_preflight_gate_rejects_before_compile(monkeypatch):
    """The acceptance-criterion test: a malformed launch is rejected by the
    static gate before conv2d_psum (and hence pallas_call) is ever entered."""
    from repro.kernels import conv_network

    def _explode(*a, **k):   # pragma: no cover - must never run
        raise AssertionError("kernel compiled despite failed pre-flight")

    monkeypatch.setattr(conv_network, "conv2d_psum", _explode)

    layers = [ConvWorkload(name="l0", cin=4, cout=8, k=3, wi=8, hi=8,
                           wo=8, ho=8)]
    netp = plan.plan_graph(layers)
    g = netp.graph
    params = conv_network.init_network_params(g)

    # malformed: schedule kind is wrong for the conv launch
    bad = {n: Schedule(kind="matmul", bm=128, bn=128, bk=128)
           for n in netp.schedules}
    with pytest.raises(rc.CheckError) as exc:
        conv_network.run_network_kernels(g, bad, params)
    assert any(d.code == "RPC003" for d in exc.value.diagnostics)

    # missing weights: RPC033 before compile
    with pytest.raises(rc.CheckError) as exc:
        conv_network.run_network_kernels(g, netp, {})
    assert any(d.code == "RPC033" for d in exc.value.diagnostics)

    # and the good path still pre-flights clean (gate passes; the sentinel
    # proves the gate, not the kernel, raised above)
    assert rc.check_network_kernels(g, netp, params) == []


# ----------------------------------------------------------- checked=True
def test_checked_plan_raises_on_infeasible_budget():
    wl = _conv_wl(k=7)     # K^2 = 49 > budget: even bm=bn=1 violates eq (1)
    plan.plan(wl, budget=16)                     # unchecked: silent fallback
    with pytest.raises(rc.CheckError) as exc:
        plan.plan(wl, budget=16, checked=True)
    assert any(d.code == "RPC001" for d in exc.value.diagnostics)


def test_checked_simulate_runs_clean():
    from repro.sim import simulate
    wl = _conv_wl()
    rep = simulate(wl, plan.plan(wl).schedule, checked=True)
    assert rep.interconnect_words > 0


# -------------------------------------------------------------- lint layer
def _lint_src(source, rules=None, rel="src/repro/models/x.py"):
    return [d for rule in (rules or rlint.default_rules())
            for d in rule.run(ast.parse(source), rel)]


def test_rpl100_raw_byte_arith():
    got = _lint_src("total = words * word_bytes\n")
    assert _codes(got) == {"RPL100"} and got[0].line == 1
    # allowlisted module: same source, no finding
    assert _lint_src("total = words * word_bytes\n",
                     rel="src/repro/sim/engine.py") == []


def test_rpl101_magic_energy_constant():
    got = _lint_src("ENERGY_PJ_SRAM_BYTE = 0.5\n")
    assert _codes(got) == {"RPL101"}
    assert _lint_src("ENERGY_PJ_SRAM_BYTE = 0.5\n",
                     rel="src/repro/roofline/constants.py") == []


def test_rpl102_words_bytes_cross_assign():
    assert _codes(_lint_src("out_words = in_bytes\n")) == {"RPL102"}
    assert _codes(_lint_src("f(fetch_bytes=fetch_words)\n")) == {"RPL102"}
    # an explicit conversion expression is RPL100's business, not RPL102's
    assert _codes(_lint_src("out_words = in_bytes * 2\n")) == {"RPL100"}


def test_rpl110_deprecated_import():
    got = _lint_src("from repro.core import bwmodel\n")
    assert _codes(got) == {"RPL110"}
    assert got[0].severity is Severity.WARNING
    assert _codes(_lint_src("import repro.core.partitioner\n")) == {"RPL110"}
    assert _lint_src("from repro.core import cnn_zoo\n") == []


def test_repo_is_lint_clean():
    """Satellite 6's invariant: the shipped tree has zero lint findings."""
    assert rc.check_codebase() == []


def test_lint_rules_load_from_tools():
    rules = rlint.load_rules()
    assert {r.code for r in rules} == {"RPL100", "RPL101", "RPL102",
                                       "RPL103", "RPL104", "RPL105",
                                       "RPL110"}


def test_rpl104_adhoc_wall_timing():
    got = _lint_src("t0 = time.perf_counter()\n")
    assert _codes(got) == {"RPL104"} and got[0].line == 1
    assert _codes(_lint_src("dt = monotonic_ns() - t0\n")) == {"RPL104"}
    # the sanctioned homes: the tracer itself, benchmarks, planserve
    assert _lint_src("t0 = time.perf_counter()\n",
                     rel="src/repro/obs/trace.py") == []
    assert _lint_src("t0 = time.perf_counter()\n",
                     rel="benchmarks/run.py") == []
    assert _lint_src("t0 = time.perf_counter()\n",
                     rel="src/repro/launch/planserve.py") == []
    # reading the module attribute without calling is not timing
    assert _lint_src("f = time.perf_counter\n") == []


# ------------------------------------------------ latent-violation pin
def test_hbm_traffic_bytes_delegates_to_gemm_model():
    """RPL100 fix: kernels/psum_matmul must reuse the one GEMM byte model,
    not carry a private copy of it."""
    from repro.kernels.psum_matmul import hbm_traffic_bytes
    from repro.plan.gemm_model import MatmulBlocks, traffic_model_bytes
    for (m, n, k) in [(512, 512, 512), (300, 700, 900), (128, 4096, 64)]:
        for ctrl in ("active", "passive"):
            got = hbm_traffic_bytes(m, n, k, bm=128, bn=256, bk=128,
                                    controller=ctrl)
            want = traffic_model_bytes(m, n, k, MatmulBlocks(128, 256, 128),
                                       ctrl, acc_bytes=4)
            assert got == want


# ------------------------------------------------------------------- CLI
def test_cli_plans_and_codebase_clean(capsys):
    from repro.check.__main__ import main
    rcode = main(["--plans", "--nets", "alexnet", "--controllers", "passive"])
    out = capsys.readouterr().out
    assert rcode == 0
    assert "0 error(s)" in out


def test_cli_github_annotations(capsys, tmp_path, monkeypatch):
    from repro.check.__main__ import main
    # a corrupted rules target: lint a tree containing one violation
    bad = tmp_path / "src"
    bad.mkdir()
    (bad / "bad.py").write_text("x_words = y_bytes\n")
    (tmp_path / "pyproject.toml").write_text("")
    monkeypatch.setattr(rlint, "find_repo_root", lambda start=None: tmp_path)
    rcode = main(["--codebase", "--github"])
    out = capsys.readouterr().out
    assert rcode == 1
    assert "::error file=src/bad.py,line=1::RPL102" in out
