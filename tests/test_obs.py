"""`repro.obs`: span tracer semantics (nesting, no-op fast path, restore),
metric registry behavior (counters/gauges/histograms, Prometheus rendering,
quantile parity vs np.percentile), the StatsCounter / cache-counter
bit-compatibility contract, and the Perfetto exporters' exactness pins —
including a hypothesis property over random workloads x controllers that
per-track trace cycles and counter words reproduce ``SimReport`` totals
word-for-word."""

import contextlib
import gc
import json
import math
import pathlib
import threading

import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                   # pragma: no cover
    from _hypothesis_stub import given, settings, st

import numpy as np

from repro import obs, plan, sim
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.plan.schedule import Controller
from repro.plan.workload import ConvWorkload

CONTROLLERS = (Controller.PASSIVE, Controller.ACTIVE)


def _own(tr):
    """The spans a test opened: a collection inside it may add `GC_SPAN`
    spans of its own."""
    return [s for s in tr.spans if s.name != obs_trace.GC_SPAN]


# ------------------------------------------------------------------ tracer
def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    s1 = obs_trace.span("a", cat="x", k=1)
    s2 = obs_trace.span("b")
    assert s1 is s2 is obs_trace._NOOP
    with s1 as sp:
        sp.set("ignored", 1)          # no-op, no error
    assert obs.get_tracer() is None


def test_tracing_records_nested_spans_with_parents():
    with obs.tracing() as tr:
        with obs_trace.span("outer", cat="t", a=1):
            with obs_trace.span("inner", cat="t") as sp:
                sp.set("late", "v")
    assert not obs.enabled()          # restored on exit
    assert len(_own(tr)) == 2
    by_name = {s.name: s for s in _own(tr)}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer.parent_id is None
    assert inner.parent_id == outer.span_id
    assert dict(outer.attrs) == {"a": 1}
    assert dict(inner.attrs) == {"late": "v"}
    assert outer.dur_s >= inner.dur_s >= 0.0
    assert outer.cat == "t"


def test_tracing_restores_previous_tracer():
    base = obs.enable()
    try:
        with obs.tracing() as inner:
            with obs_trace.span("in-scope"):
                pass
        assert obs.get_tracer() is base
        assert len(_own(inner)) == 1 and len(_own(base)) == 0
    finally:
        obs.disable()


def test_span_records_error_attr():
    with obs.tracing() as tr:
        with pytest.raises(RuntimeError):
            with obs_trace.span("boom"):
                raise RuntimeError("x")
    (s,) = _own(tr)
    assert dict(s.attrs)["error"] == "RuntimeError"


def test_tracer_record_external_interval_and_clear():
    tr = obs_trace.Tracer()
    parent = tr.record("virtual", 10.0, 2.5, cat="serve")
    child = tr.record("child", 10.5, 1.0, parent_id=parent.span_id,
                      attrs=(("req", 3),))
    assert child.parent_id == parent.span_id
    assert child.span_id != parent.span_id
    assert tr.spans[0].t0_s == 10.0 and tr.spans[0].dur_s == 2.5
    tr.clear()
    assert len(tr) == 0


def test_spans_carry_thread_ids():
    with obs.tracing() as tr:
        with obs_trace.span("main-side"):
            pass
        t = threading.Thread(target=lambda: obs_trace.span("worker")
                             .__enter__().__exit__(None, None, None))
        t.start()
        t.join()
    tids = {s.name: s.thread_id for s in tr.spans}
    assert tids["main-side"] != tids["worker"]


def test_stopwatch_measures_and_spans_when_named():
    with obs.Stopwatch() as sw:
        pass
    assert sw.s >= 0.0
    assert sw.us == sw.s * 1e6 and sw.ms == sw.s * 1e3
    with obs.tracing() as tr:
        with obs.Stopwatch("timed.step", cat="c") as named:
            pass
        with obs.Stopwatch() as anon:
            pass
    assert anon.s >= 0.0
    (s,) = _own(tr)                   # only the named stopwatch spans
    assert s.name == "timed.step" and s.cat == "c"
    assert named.s >= 0.0


# ------------------------------------------------------- profiler bridge
def _profiled(log_dir):
    """A JAX profiler session around a block, host annotations only."""
    jax = pytest.importorskip("jax")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def _host_events(log_dir, prefix):
    """{name: [events]} of the host events whose names start with
    ``prefix``, in the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.setdefault(e.name, []).append(e)
    return out


@pytest.mark.parametrize("with_tracer", (False, True))
def test_spans_reach_the_profiler_trace_with_their_metadata(tmp_path,
                                                            with_tracer):
    """While the profiler records, nested spans land on the host plane
    under their own names, keyword and ``.set()`` attributes as event
    stats, with or without a tracer installed."""
    tracer = obs_trace.Tracer() if with_tracer else None
    with _profiled(tmp_path):
        with obs.tracing(tracer) if with_tracer else contextlib.nullcontext():
            with obs_trace.span("bridge.outer", cat="t", graph="g"):
                with obs_trace.span("bridge.inner", n=3) as sp:
                    sp.set("diagnostics", 2)
    ev = _host_events(tmp_path, "bridge.")
    (outer,), (inner,) = ev["bridge.outer"], ev["bridge.inner"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert dict(outer.stats) == {"graph": "g"}
    assert dict(inner.stats) == {"n": 3, "diagnostics": 2}
    if with_tracer:
        by_name = {r.name: r for r in _own(tracer)}
        assert dict(by_name["bridge.inner"].attrs) == {"n": 3,
                                                       "diagnostics": 2}
        assert by_name["bridge.inner"].parent_id == \
            by_name["bridge.outer"].span_id


def test_span_off_path_is_the_shared_noop_around_a_profiled_region(
        tmp_path):
    """With JAX imported, no profiler and no tracer, ``span()`` is still
    the shared no-op; only the profiled region gets live spans."""
    jax = pytest.importorskip("jax")
    assert obs_trace.span("a", k=1) is obs_trace._NOOP
    assert not obs_trace._NOOP.recording
    with _profiled(tmp_path):
        live = obs_trace.span("b", k=1)
    assert live is not obs_trace._NOOP and live.recording
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert not obs.enabled()
    assert obs_trace.span("c", k=1) is obs_trace._NOOP


@pytest.mark.parametrize("where", ("tracer", "profiler"))
def test_gc_pause_is_a_span(tmp_path, where):
    """A full collection under a tracer or under the profiler is one
    `GC_SPAN` span, attribute ``generation``."""
    if where == "tracer":
        with obs.tracing() as tr:
            gc.collect()
        pauses = [dict(s.attrs) for s in tr.spans
                  if s.name == obs_trace.GC_SPAN]
        assert {"generation": 2} in pauses
        assert all(s.cat == "obs" for s in tr.spans
                   if s.name == obs_trace.GC_SPAN)
    else:
        with _profiled(tmp_path):
            gc.collect()
        pauses = _host_events(tmp_path, obs_trace.GC_SPAN)[obs_trace.GC_SPAN]
        assert {"generation": 2} in [dict(e.stats) for e in pauses]
    assert obs_trace._GC_OPEN is obs_trace._NOOP      # closed at its stop


def test_network_runner_spans_each_layer_boundary():
    """One `run_network_kernels` call: a ``network.step`` holding the
    pre-flight, one ``network.conv`` per conv and one ``network.glue`` per
    eager op group (input, concat + pad per conv, add, pool)."""
    pytest.importorskip("jax")
    from repro.kernels.conv_network import (init_network_params,
                                            run_network_kernels)
    from repro.plan import netplan
    from repro.plan.graph import NetworkGraph, Node, Tensor

    def conv(name, cin, cout, k):
        return ConvWorkload(name=name, cin=cin, cout=cout, k=k, wi=8, hi=8,
                            wo=8, ho=8)

    tensors = {n: Tensor(n, c, 8, 8) for n, c in
               (("x", 4), ("a", 8), ("b", 8), ("s", 8), ("p", 8), ("y", 8))}
    g = NetworkGraph("tiny", (
        Node("in", "input", (), "x"),
        Node("c1", "conv", ("x",), "a", conv("c1", 4, 8, 3)),
        Node("c2", "conv", ("a",), "b", conv("c2", 8, 8, 3)),
        Node("add", "add", ("a", "b"), "s"),
        Node("pool", "pool", ("s",), "p"),
        Node("c3", "conv", ("p", "x"), "y", conv("c3", 12, 8, 1)),
    ), tensors)
    netp = netplan.plan_graph(g, 512, "exact_opt", "active")
    with obs.tracing() as tr:
        run_network_kernels(g, netp, init_network_params(g))
    spans = _own(tr)
    (step,) = [s for s in spans if s.name == "network.step"]
    assert step.parent_id is None and dict(step.attrs)["graph"] == "tiny"
    children = [s for s in spans if s is not step]
    assert all(s.parent_id == step.span_id for s in children)
    assert sorted(s.name for s in children) == sorted(
        ["kernel.preflight"] + ["network.conv"] * 3 + ["network.glue"] * 6)
    assert [dict(s.attrs)["node"] for s in children
            if s.name == "network.conv"] == ["c1", "c2", "c3"]
    assert [(dict(s.attrs)["node"], dict(s.attrs)["op"]) for s in children
            if s.name == "network.glue"] == [
        ("in", "input"), ("c1", "concat_pad"), ("c2", "concat_pad"),
        ("add", "add"), ("pool", "pool"), ("c3", "concat_pad")]


# ----------------------------------------------------------------- metrics
def test_counter_semantics():
    reg = obs_metrics.Registry()
    c = reg.counter("c", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    c.reset()
    assert c.value == 0.0
    assert reg.counter("c") is c      # get-or-create returns the same object


def test_gauge_and_callback_gauge():
    reg = obs_metrics.Registry()
    g = reg.gauge("g")
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value == 3.0
    box = {"v": 7.0}
    cb = reg.gauge("cb", fn=lambda: box["v"])
    assert cb.value == 7.0
    box["v"] = 9.0
    assert cb.value == 9.0            # sampled at collection time
    with pytest.raises(ValueError):
        cb.set(1.0)


def test_registry_kind_conflict_families_unregister():
    reg = obs_metrics.Registry()
    reg.counter("m", labels={"k": "a"})
    reg.counter("m", labels={"k": "b"})
    reg.gauge("other")
    with pytest.raises(ValueError):
        reg.histogram("m", labels={"k": "a"})
    assert len(reg.family("m")) == 2
    assert reg.families() == ["m", "other"]
    assert reg.get("m", {"k": "a"}) is not None
    assert reg.get("m", {"k": "zz"}) is None
    assert reg.unregister("m") == 2
    assert reg.families() == ["other"]


def test_registry_snapshot_and_prometheus_render():
    reg = obs_metrics.Registry()
    reg.counter("hits", "cache hits", labels={"cache": "plan"}).inc(5)
    h = reg.histogram("lat", "latency")
    for v in (0.0, 1.0, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["hits"]["type"] == "counter"
    assert snap["hits"]["values"] == [{"labels": {"cache": "plan"},
                                       "value": 5.0}]
    hsnap = snap["lat"]["values"][0]["value"]
    assert hsnap["count"] == 3 and hsnap["sum"] == 3.0
    assert hsnap["min"] == 0.0 and hsnap["max"] == 2.0
    text = reg.render_prometheus()
    assert "# HELP hits cache hits" in text
    assert "# TYPE hits counter" in text
    assert 'hits{cache="plan"} 5' in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="0.0"} 1' in text      # exact-zero bucket
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_sum 3" in text and "lat_count 3" in text
    assert json.dumps(snap)           # snapshot is JSON-able


def test_histogram_quantiles_track_numpy_percentile():
    rng = np.random.default_rng(7)
    samples = np.concatenate([rng.lognormal(0.0, 1.5, size=400),
                              rng.uniform(1e-4, 1e3, size=400)])
    h = obs_metrics.Histogram("h")
    for v in samples:
        h.observe(float(v))
    for p in (1, 10, 25, 50, 75, 90, 99):
        exact = float(np.percentile(samples, p))
        approx = h.percentile(p)
        assert approx == pytest.approx(exact, rel=0.01), p
    assert math.isnan(obs_metrics.Histogram("empty").quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_stats_counter_mirrors_positive_deltas():
    name = "test_stats_counter_mirror"
    obs.REGISTRY.unregister(name)
    sc = obs_metrics.StatsCounter(metric=name)
    sc["grid_hits"] += 3
    sc["grid_hits"] += 2
    sc["evals"] += 1
    sc["evals"] -= 1                  # decrements never reach the counter
    assert sc["grid_hits"] == 5 and sc["evals"] == 0
    mirrored = obs.REGISTRY.get(name, {"key": "grid_hits"})
    assert mirrored is not None and mirrored.value == 5.0
    assert obs.REGISTRY.get(name, {"key": "evals"}).value == 1.0
    # still a real collections.Counter
    assert sc.most_common(1) == [("grid_hits", 5)]
    obs.REGISTRY.unregister(name)


def test_plan_caches_read_through_registry_bit_compatibly():
    plan.clear_plan_graph_cache()
    info0 = plan.plan_graph_cache_info()
    assert info0.hits == 0 and info0.misses == 0
    plan.plan_graph("alexnet", 2048, "paper_opt", "passive")
    plan.plan_graph("alexnet", 2048, "paper_opt", "passive")
    info = plan.plan_graph_cache_info()
    assert isinstance(info.hits, int) and isinstance(info.misses, int)
    assert info.hits >= 1 and info.misses >= 1 and info.currsize >= 1
    ctx = plan.PlanContext()
    assert isinstance(ctx.stats, obs_metrics.StatsCounter)
    plan.clear_plan_graph_cache()
    info1 = plan.plan_graph_cache_info()
    assert info1.hits == 0 and info1.misses == 0 and info1.currsize == 0


# ------------------------------------------------------------------ export
def _assert_valid_trace_events(events):
    """Spec-level invariants every emitted trace must satisfy."""
    assert events, "empty trace"
    for ev in events:
        assert ev["ph"] in ("X", "C", "M")
        if ev["ph"] in ("X", "C"):
            assert ev["ts"] >= 0.0
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0


def test_spans_to_trace_structure():
    with obs.tracing() as tr:
        with obs_trace.span("outer", cat="t"):
            with obs_trace.span("inner", cat="t"):
                pass
    own = obs_trace.Tracer()
    own.spans.extend(_own(tr))
    events = obs_export.spans_to_trace(own, process_name="unit")
    _assert_valid_trace_events(events)
    metas = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "unit" for e in metas)
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    # ts rebased to the earliest span; X events sorted by start time
    assert xs["outer"]["ts"] == 0.0
    assert xs["inner"]["args"]["parent_id"] == xs["outer"]["args"]["span_id"]
    assert obs_export.spans_to_trace(obs_trace.Tracer())[0]["ph"] == "M"


def _check_sim_trace_pins(report):
    events = obs_export.simreport_to_trace(report)
    _assert_valid_trace_events(events)
    # X events are laid out sequentially in virtual time: monotonic starts,
    # each phase beginning where the previous one ended.
    xs = [e for e in events if e["ph"] == "X"]
    t = 0.0
    for ev in xs:
        assert ev["ts"] == t
        t += ev["dur"]
    assert t == report.cycles
    # the pins proper: per-track cycles and counter words, exactly
    pins = obs_export.verify_sim_trace(report, events)
    track_cycles = [v for k, v in pins.items() if k != "interconnect_words"]
    assert sum(track_cycles) == report.cycles
    assert pins["interconnect_words"] == report.interconnect_words
    counter_words = sum(e["args"]["words"] for e in events
                       if e["ph"] == "C"
                       and e["tid"] == obs_export._WORDS_TID)
    assert counter_words == report.interconnect_words
    return events


@pytest.mark.parametrize("controller", ("passive", "active"))
def test_sim_trace_pins_zoo_network(controller):
    report = plan.plan_graph("alexnet", 2048, "paper_opt",
                             controller).simulate()
    events = _check_sim_trace_pins(report)
    # every resource track + both counter tracks are declared
    thread_names = {e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(obs_export.RESOURCE_TRACKS) <= thread_names
    assert {"interconnect words", "interconnect GB/s"} <= thread_names
    # phases carry their node provenance into args
    nodes = {e["args"].get("node") for e in events if e["ph"] == "X"}
    assert nodes - {None}


def test_verify_sim_trace_rejects_tampering():
    report = plan.plan_graph("alexnet", 2048, "paper_opt",
                             "passive").simulate()
    events = obs_export.simreport_to_trace(report)
    broken = [dict(e) for e in events]
    for ev in broken:
        if ev["ph"] == "X":
            ev["dur"] = ev["dur"] + 1.0
            break
    with pytest.raises(ValueError):
        obs_export.verify_sim_trace(report, broken)
    broken2 = [e for e in events
               if not (e["ph"] == "C"
                       and e["tid"] == obs_export._WORDS_TID)]
    with pytest.raises(ValueError):
        obs_export.verify_sim_trace(report, broken2)


@settings(max_examples=20, deadline=None)
@given(cin=st.integers(1, 60), cout=st.integers(1, 60),
       k=st.sampled_from([1, 3, 5]), hw=st.integers(2, 16),
       budget=st.sampled_from([512, 2048]),
       controller=st.sampled_from(CONTROLLERS))
def test_property_sim_trace_word_for_word(cin, cout, k, hw, budget,
                                          controller):
    """Random conv workloads x controllers: the virtual-time trace is
    balanced and complete — monotonic non-negative timestamps, per-track
    cycles summing exactly to ``SimReport.cycles``, counter-track words
    summing exactly to ``interconnect_words``."""
    wl = ConvWorkload(name="prop", cin=cin, cout=cout, k=k,
                      wi=hw, hi=hw, wo=hw, ho=hw)
    p = plan.plan(wl, budget, "exact_opt", controller)
    report = sim.simulate(wl, p.schedule)
    _check_sim_trace_pins(report)


# -------------------------------------------------- merge provenance (sim)
def test_merge_reports_node_provenance():
    netp = plan.plan_graph("alexnet", 2048, "paper_opt", "active")
    merged = netp.simulate()
    assert all(p.node for p in merged.phases)
    assert all(p.name.startswith(f"{p.node}/") for p in merged.phases)
    breakdown = merged.node_breakdown()
    assert len(breakdown) > 1
    assert sum(c for c, _ in breakdown.values()) == merged.cycles
    assert sum(w for _, w in breakdown.values()) == \
        pytest.approx(merged.interconnect_words, rel=1e-12)
    text = merged.summary()
    for node in breakdown:
        assert node in text
    # single-layer reports keep unstamped phases
    wl = plan.conv_workloads("alexnet")[0]
    rep = sim.simulate(wl, plan.plan(wl, 2048).schedule)
    assert all(p.node == "" for p in rep.phases)
    assert list(rep.node_breakdown()) == [rep.name]


# ------------------------------------------------- planserve histogram p50
def test_run_load_histogram_percentiles_agree():
    from repro.launch.planserve import run_load
    report = run_load(requests=24, smoke=True)
    for k in ("p50_ms", "p99_ms", "p50_ms_hist", "p99_ms_hist"):
        assert k in report
    # run_load itself asserts 1% parity; re-check the contract here
    assert report["p50_ms_hist"] == pytest.approx(report["p50_ms"], rel=0.01)
    assert report["p99_ms_hist"] == pytest.approx(report["p99_ms"], rel=0.01)
    fam = obs.REGISTRY.get("planserve_latency_seconds")
    assert fam is not None and fam.count >= 24


# --------------------------------------------------------------------- CLI
def test_cli_export_writes_verified_trace(tmp_path, capsys):
    from repro.obs.__main__ import main
    out = tmp_path / "t.json"
    rc = main(["export", "--net", "alexnet", "--controller", "passive",
               "--strategy", "paper_opt", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    _assert_valid_trace_events(doc["traceEvents"])
    assert "wrote" in capsys.readouterr().out


def test_cli_metrics_dumps_json_and_prometheus(capsys):
    from repro.obs.__main__ import main
    assert main(["metrics", "--no-warm"]) == 0
    json.loads(capsys.readouterr().out)
    assert main(["metrics", "--no-warm", "--prometheus"]) == 0
    assert "# TYPE" in capsys.readouterr().out


def test_cli_trace_load_writes_span_trace(tmp_path, capsys):
    from repro.obs.__main__ import main
    out = tmp_path / "spans.json"
    rc = main(["trace-load", "--smoke", "--requests", "8",
               "--out", str(out)])
    assert rc == 0
    assert not obs.enabled()          # CLI scope-exits its tracer
    doc = json.loads(out.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in xs}
    assert any(n.startswith("queue ") for n in names)
    assert any(n.startswith("serve ") for n in names)
    assert "planserve.batch" in names
    assert "fleet.plan_graphs" in names   # planner spans nest underneath
    assert "wrote" in capsys.readouterr().out


def test_instrumented_plan_paths_emit_spans():
    # mobilenet + mnasnet share layer-shape grids at the same topological
    # steps, so the lockstep beam actually buckets (exact_opt: grid-scored).
    plan.clear_plan_graph_cache()
    with obs.tracing() as tr:
        plan.plan_graphs(["mobilenet", "mnasnet"], 2048, "exact_opt",
                         "active", context=plan.PlanContext())
    names = [s.name for s in tr.spans]
    assert "fleet.plan_graphs" in names
    assert "fleet.bucket_step" in names
    by_name = {s.name: s for s in tr.spans}
    # bucket steps nest under the fleet span
    fleet = by_name["fleet.plan_graphs"]
    assert by_name["fleet.bucket_step"].parent_id == fleet.span_id
    assert fleet.parent_id is None
    step = by_name["fleet.bucket_step"]
    attrs = dict(step.attrs)
    assert attrs["lanes"] >= 2 and attrs["states"] > 0
