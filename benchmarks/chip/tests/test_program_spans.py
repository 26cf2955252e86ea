"""The program's spans in a trace: self time, device idle inside a span, and the
metric readers built on them. Run with an explicit path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

On hand-built intervals, and on a trace that the test records on the CPU
(host spans only: the CPU has no device plane).
"""

from __future__ import annotations

import gc
import importlib.util
import pathlib
import sys
import types

import jax
import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))       # as when run.py is the script
import program_spans  # noqa: E402
import xplane  # noqa: E402

E = xplane.Event


def _reader(metric: str):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{metric}", HERE / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _spans(*threads):
    return program_spans.ProgramSpans(
        tuple(program_spans._sorted(list(t)) for t in threads))


# one image: network.step 0-100 holds preflight 0-40 (a GC pause 20-30 in
# it), glue 40-50, conv 50-70, glue 70-75; a second thread collects 60-90
STEP = (E("network.step", 0, 100), E("kernel.preflight", 0, 40),
        E("obs.gc", 20, 30), E("network.glue", 40, 50),
        E("network.conv", 50, 70), E("network.glue", 70, 75))
OTHER = (E("obs.gc", 60, 90),)


def test_self_time_leaves_out_nested_program_spans():
    s = _spans(STEP, OTHER)
    assert s.self_s(("kernel.preflight",)) == pytest.approx(30e-9)
    assert s.self_s(("network.conv", "network.glue")) == pytest.approx(35e-9)
    # the step's own time: 100 less its children's union (0-75); the other
    # thread's pause does not nest in it
    assert s.self_s(("network.step",)) == pytest.approx(25e-9)
    assert s.self_s(("obs.gc",)) == pytest.approx(40e-9)
    assert s.names() == {"network.step", "kernel.preflight", "obs.gc",
                         "network.glue", "network.conv"}


def test_spans_clipped_to_the_window():
    s = _spans(STEP, OTHER).clipped((25.0, 65.0))
    assert s.self_s(("kernel.preflight",)) == pytest.approx(10e-9)  # 30-40
    assert s.self_s(("obs.gc",)) == pytest.approx(10e-9)  # 25-30, 60-65
    assert s.self_s(("network.conv",)) == pytest.approx(15e-9)       # 50-65
    assert _spans(STEP).clipped((200.0, 300.0)).names() == set()


def test_idle_inside_a_span_over_two_devices():
    """Device 0 runs 10-35 and 45-80: idle 0-10, 35-45, 80-100; device 1
    runs 0-100 but for 38-62. Gaps straddle the spans' edges."""
    trace = xplane.TraceSummary(
        window=(0.0, 100.0),
        devices=((E("a", 10, 35), E("b", 45, 80)),
                 (E("c", 0, 38), E("d", 62, 100))),
        spans=())
    s = _spans(STEP, OTHER)
    # preflight 0-40: device 0 idles 0-10 and 35-40, device 1 38-40
    assert s.idle_inside_s("kernel.preflight", trace) == pytest.approx(17e-9)
    # pauses 20-30 and 60-90 (two threads): device 0 80-90, device 1 60-62
    assert s.idle_inside_s("obs.gc", trace) == pytest.approx(12e-9)
    assert s.idle_inside_s("network.step", trace) == pytest.approx(
        sum(e - st for d in range(2)
            for st, e in xplane.gaps(trace.busy(d), trace.window)) / 1e9)
    assert s.idle_inside_s("absent", trace) == 0.0


def test_readers_are_silent_without_a_trace():
    run = types.SimpleNamespace(trace=None)
    for metric in ("preflight_ms.net", "dispatch_ms.net",
                   "gc_idle_share.ffn"):
        assert _reader(metric)(run) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace recorded on the CPU around two runner-like steps, each with
    a full collection in its pre-flight, through ``repro.obs.span`` with no
    tracer installed. Automatic collections are held off, so that every
    pause nests in a step."""
    from repro.obs import trace as obs_trace

    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    was_enabled = gc.isenabled()
    gc.disable()
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with obs_trace.span("network.step", graph="g"):
                    with obs_trace.span("kernel.preflight"):
                        gc.collect()
                    for node in ("c1", "c2"):
                        with obs_trace.span("network.glue", node=node):
                            pass
                        with obs_trace.span("network.conv", node=node):
                            jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        if was_enabled:
            gc.enable()
    return xplane.find_xplane(out)


def test_program_spans_read_by_name_from_a_recorded_trace(recorded):
    s = program_spans.load(str(recorded), program_spans.span_names())
    assert sum(len(t) for t in s.threads) >= 2 + 2 + 4 + 4 + 2
    assert s.names() == set(program_spans.span_names())
    assert program_spans.gc_span() == "obs.gc"
    step = s.self_s(("network.step",))
    children = s.self_s(("kernel.preflight", "network.glue",
                         "network.conv", "obs.gc"))
    total = sum(e.duration_ns for t in s.threads for e in t
                if e.name == "network.step") / 1e9
    assert 0 < step < total
    assert step + children == pytest.approx(total, rel=1e-6)


def test_readers_on_a_recorded_trace(recorded, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR",
                        recorded.parents[3])
    from jax.profiler import ProfileData
    (window,) = [e for p in ProfileData.from_file(str(recorded)).planes
                 for line in p.lines for e in line.events
                 if e.name == "bench.window"]
    trace = xplane.TraceSummary(window=(window.start_ns, window.end_ns),
                                devices=((),), spans=())
    run = types.SimpleNamespace(trace=trace,
                                window=types.SimpleNamespace(steps=2))
    s = program_spans.load(str(recorded), program_spans.span_names())
    assert _reader("preflight_ms.net")(run) == pytest.approx(
        1e3 * s.self_s(("kernel.preflight",)) / 2)
    assert _reader("dispatch_ms.net")(run) == pytest.approx(
        1e3 * s.self_s(("network.conv", "network.glue")) / 2)
    # no device operations: every pause idles the device
    gc_share = _reader("gc_idle_share.ffn")(run)
    assert gc_share == pytest.approx(
        100.0 * s.self_s(("obs.gc",)) / trace.window_s)
    assert 0 < gc_share < 100


def test_gc_reader_is_silent_for_a_program_without_gc_spans(recorded,
                                                            monkeypatch):
    """A program whose ``repro.obs.trace`` declares no ``GC_SPAN`` (an
    older one) gets no GC metric, and its trace is read without the name."""
    monkeypatch.setattr(program_spans, "TRACE_DIR", recorded.parents[3])
    monkeypatch.delattr(sys.modules["repro.obs.trace"], "GC_SPAN")
    assert program_spans.gc_span() is None
    assert program_spans.span_names() == program_spans.RUNNER_SPANS
    trace = xplane.TraceSummary(window=(0.0, float(2 ** 62)),
                                devices=((),), spans=())
    run = types.SimpleNamespace(trace=trace,
                                window=types.SimpleNamespace(steps=2))
    assert _reader("gc_idle_share.ffn")(run) is None
    assert "obs.gc" not in program_spans.of_run(run).names()
    assert _reader("preflight_ms.net")(run) > 0
