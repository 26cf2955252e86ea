#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json`` (at the root of the
checkout): one configuration under one traffic mix. Everything a cell needs
is found by name, so a new configuration, mix or metric is a new file plus
new ``BENCHMARK.json`` entries, never an edit:

  configs/<config>.json   the configuration's sizes as run, and its limits
  configs/<config>.py     build (plan, weights, inputs), step, work count,
                          plain reference (see the end of this docstring)
  mixes/<traffic>.json    the loop and the schedule parameters
  metrics/<metric>.py     one reader per metric: ``read(run) -> float|None``
  peaks.json              the chip's peaks, keyed by ``device_kind``

A run, in order:

  1. refuse anything but a TPU with at least the cell's chips (exit 2,
     naming what was found), or a chip missing from ``peaks.json``;
  2. keep JAX's compilation cache in ``<checkout>/.jax_cache``;
  3. set-up: plan, make weights and a pool of inputs from ``--seed`` on the
     device, warm the cell's own shapes up;
  4. the measured window of ``--seconds``; with ``--trace 1`` the profiler
     records it, and each call into a layer sits in a ``bench.*`` span;
  5. read the chip's peak memory, free the program's state, and compare a
     sample of the window's answers (drawn from the seed) with the plain
     float32 reference at the configuration's limits;
  6. print one JSON line: ``correct``, ``attempted``, ``failed``,
     ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
     per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
     ``check``: each compared number beside its limit. The same numbers are
     the last lines of standard error.

``setup_s`` runs from the start of the process to the first timed step.
A configuration file (``configs/<config>.py``) defines:

  build(cfg, mix, seed) -> object with ``pool`` (list of inputs), ``step(x)``
                           (dispatch one step, unblocked) and ``describe()``
  answers(out) -> {name: array}     what a step produced, to be compared
  reference(cfg, mix, seed, index, control=False) -> {name: float32 array}
                           the plain reference for pool input ``index``;
                           ``control=True`` computes it one precision lower
  work(cfg) -> {"units_per_step", "flops_per_step",
                "kernels": {kernel: [[flops, bytes], ...] per step}}
"""

from __future__ import annotations

import time

_PC0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional, Tuple  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
WARMUP_STEPS = 3       # steps run in set-up, so that the window compiles nothing
AHEAD_S = 8.0          # device seconds a back-to-back loop keeps dispatched
                       # ahead of the step it waits for: a host stall shorter
                       # than that leaves the chip fed


def setup_clock_s() -> float:
    """Seconds since this process started, from ``/proc`` (10 ms steps), so
    that ``setup_s`` counts the interpreter's start and the imports; where
    ``/proc`` is missing, seconds since this module was imported."""
    try:
        stat = pathlib.Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        uptime = float(pathlib.Path("/proc/uptime").read_text().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _PC0


# seconds from process start to each step of set-up, for the result line
SETUP_PHASES: Dict[str, float] = {}


class NoChipError(RuntimeError):
    """The run found no accelerator it may measure on."""


# ------------------------------------------------------------ the chip
def require_tpu(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it, or `NoChipError` naming what was found
    when it is not a TPU or has fewer than ``chips`` chips."""
    import jax

    SETUP_PHASES["jax_imported"] = setup_clock_s()
    devs = jax.devices()
    SETUP_PHASES["runtime_up"] = setup_clock_s()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if found["platform"] != "tpu":
        raise NoChipError(
            f"needs a TPU, but JAX's first device is platform "
            f"{found['platform']!r} ({found['kind']}, {found['count']} "
            f"device(s))")
    if found["count"] < chips:
        raise NoChipError(f"the cell needs {chips} chips, JAX found "
                          f"{found['count']} ({found['kind']})")
    found["count"] = chips
    return found


def load_peaks(kind: str, path: pathlib.Path = HERE / "peaks.json"
               ) -> Dict[str, Any]:
    table = json.loads(path.read_text())
    if kind not in table:
        raise NoChipError(f"device kind {kind!r} is not in {path.name} "
                          f"(known: {sorted(table)})")
    return table[kind]


def use_compile_cache(path: pathlib.Path = CACHE_DIR) -> str:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout; every compile is kept, however short."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


# --------------------------------------------------------- the cell
def load_module(path: pathlib.Path, name: str) -> Any:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with everything its names point at."""

    workload: Dict[str, Any]
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    config: Any                         # the configs/<config>.py module
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def name(self) -> str:
        return self.workload["name"]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg_file = root / cfg_entry["file"]
    return Cell(
        workload=wl,
        cfg=json.loads(cfg_file.read_text()),
        mix=json.loads((HERE / "mixes" / f"{wl['traffic']}.json").read_text()),
        config=load_module(cfg_file.with_suffix(".py"),
                           f"bench_config_{wl['config']}"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


# ------------------------------------------------------ the window
class Reservoir:
    """A uniform sample of ``k`` steps' outputs, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(f"bench-sample-{seed}")
        self.items: List[Tuple[int, Any]] = []      # (step, output)

    def offer(self, step: int, out: Any) -> None:
        if len(self.items) < self.k:
            self.items.append((step, out))
            return
        j = self.rng.randrange(step + 1)
        if j < self.k:
            self.items[j] = (step, out)


@dataclasses.dataclass
class Window:
    steps: int
    seconds: float
    latencies_s: List[float]
    compile_events: int
    ahead: int = 0                      # steps dispatched ahead (back to back)


def _span_factory(traced: bool) -> Callable[[str], Any]:
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def closed_loop(built: Any, seconds: float, keep: Reservoir,
                span: Callable[[str], Any], ahead: int
                ) -> Tuple[int, float, List[float]]:
    """One step at a time: dispatch, wait for it, then the next (``ahead``
    is 0). A step starts only inside the window; the window ends when the
    last one completes."""
    import jax

    pool = built.pool
    lat: List[float] = []
    t0 = time.perf_counter()
    end, last, i = t0 + seconds, t0, 0
    while True:
        ts = time.perf_counter()
        if ts >= end:
            break
        with span("bench.runner"):
            out = built.step(pool[i % len(pool)])
        with span("bench.sync"):
            jax.block_until_ready(out)
        last = time.perf_counter()
        lat.append(last - ts)
        keep.offer(i, out)
        i += 1
    return i, last - t0, lat


def back_to_back(built: Any, seconds: float, keep: Reservoir,
                 span: Callable[[str], Any], ahead: int
                 ) -> Tuple[int, float, List[float]]:
    """Steps dispatched back to back, ``ahead`` of them outstanding beyond
    the one waited for; dispatch stops at the end of the window, which
    closes when every dispatched step has completed, so all of that work
    counts over all of that time."""
    import jax

    pool = built.pool
    pending: collections.deque = collections.deque()
    t0 = time.perf_counter()
    end, i = t0 + seconds, 0
    while time.perf_counter() < end:
        with span("bench.dispatch"):
            out = built.step(pool[i % len(pool)])
        pending.append(out)
        keep.offer(i, out)
        i += 1
        if len(pending) > ahead:
            with span("bench.wait"):
                jax.block_until_ready(pending.popleft())
    with span("bench.drain"):
        jax.block_until_ready(list(pending))
    return i, time.perf_counter() - t0, []


LOOPS = {"closed": closed_loop, "back_to_back": back_to_back}


def steps_ahead(step_s: float) -> int:
    """Steps of ``step_s`` device seconds each that make ``AHEAD_S``."""
    return max(1, math.ceil(AHEAD_S / max(step_s, 1e-6)))


def measure(built: Any, mix: Dict[str, Any], seconds: float,
            keep: Reservoir, traced: bool, ahead: int) -> Window:
    """The measured window, under the profiler when ``traced``. JAX's
    tracing and compile events inside it are counted: there should be none."""
    from jax import monitoring

    compile_events = [0]

    def on_event(event: str, duration: float, **kwargs: Any) -> None:
        if "/compile" in event:
            compile_events[0] += 1

    span = _span_factory(traced)
    loop = LOOPS[mix["loop"]]
    monitoring.register_event_duration_secs_listener(on_event)
    try:
        with span("bench.window"):
            steps, secs, lat = loop(built, seconds, keep, span, ahead)
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    return Window(steps, secs, lat, compile_events[0], ahead)


# ---------------------------------------------------- what a run read
@dataclasses.dataclass
class Run:
    """What the metric readers (``metrics/<name>.py``) read."""

    setup_s: float
    window: Window
    work: Dict[str, Any]
    peaks: Dict[str, Any]
    trace: Any = None                   # xplane.TraceSummary, when traced

    @property
    def units_per_s(self) -> float:
        return (self.window.steps * self.work["units_per_step"]
                / self.window.seconds)

    def latency_quantile_ms(self, q: int) -> Optional[float]:
        """The ``q``-th percentile of the per-step latencies over every
        step of the window (``statistics.quantiles``, inclusive)."""
        import statistics
        lat = self.window.latencies_s
        if len(lat) < 2:
            return None
        return statistics.quantiles(lat, n=100, method="inclusive")[q - 1] \
            * 1e3

    def step_mfu(self) -> float:
        """Logical FLOPs completed over the window, as % of the chip's
        peak."""
        flops = self.window.steps * self.work["flops_per_step"]
        return 100.0 * flops / self.window.seconds / self.peaks["flops_per_s"]

    def kernel_min_s(self, kernel: str) -> float:
        """The least time the chip could take for ``kernel``'s work in one
        step: each call bound by FLOPs or by compulsory bytes."""
        return sum(max(f / self.peaks["flops_per_s"],
                       b / self.peaks["hbm_bytes_per_s"])
                   for f, b in self.work["kernels"][kernel])

    def is_kernel(self, op: str) -> bool:
        from xplane import base_name
        return base_name(op) in self.work["kernels"]

    def kernel_roofline(self, kernel: str) -> Optional[float]:
        """% of its roofline that ``kernel`` reached: the least time for the
        window's steps over the device time of its launches."""
        if self.trace is None:
            return None
        from xplane import base_name
        device_s = self.trace.op_seconds(lambda op: base_name(op) == kernel)
        if device_s <= 0:
            return None
        return 100.0 * self.window.steps * self.kernel_min_s(kernel) \
            / device_s

    def glue_share(self) -> Optional[float]:
        """% of the device's busy time spent in operations that are not a
        Pallas kernel of the configuration."""
        if self.trace is None or self.trace.busy_s <= 0:
            return None
        glue = self.trace.op_seconds(lambda op: not self.is_kernel(op))
        return 100.0 * glue / len(self.trace.devices) / self.trace.busy_s

    def idle_share(self) -> Optional[float]:
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)


def read_metrics(specs: List[Dict[str, Any]], run: Run
                 ) -> Dict[str, Dict[str, Any]]:
    out: Dict[str, Dict[str, Any]] = {}
    for spec in specs:
        reader = load_module(HERE / "metrics" / f"{spec['name']}.py",
                             f"bench_metric_{spec['name']}")
        value = reader.read(run)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


# --------------------------------------------------------- the check
def compare(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The worst, over the reference's arrays, of the relative L2 error
    (``rel_l2``) and of the largest absolute error over the reference's
    largest magnitude (``max_err``). A missing array reads infinite."""
    import jax.numpy as jnp

    worst = {"rel_l2": 0.0, "max_err": 0.0}
    for name, ref in want.items():
        if name not in got or tuple(got[name].shape) != tuple(ref.shape):
            return {"rel_l2": math.inf, "max_err": math.inf}
        ref = ref.astype(jnp.float32)
        diff = got[name].astype(jnp.float32) - ref
        rel = float(jnp.linalg.norm(diff) / jnp.linalg.norm(ref))
        mx = float(jnp.max(jnp.abs(diff)) / jnp.max(jnp.abs(ref)))
        for key, v in (("rel_l2", rel), ("max_err", mx)):
            if not v <= worst[key]:          # NaN is worst
                worst[key] = v
    return worst


def check(cell: Cell, seed: int, samples: List[Tuple[int, Dict[str, Any]]]
          ) -> Tuple[Dict[str, Dict[str, float]], int]:
    """Compare each sampled step's answers with the reference of its input;
    ({number: {"value", "limit"}}, answers that missed a limit)."""
    limits = cell.cfg["limits"]
    pool = cell.mix["pool"]
    worst = {k: 0.0 for k in limits}
    failed = 0
    refs: Dict[int, Dict[str, Any]] = {}
    for step, got in samples:
        index = step % pool
        if index not in refs:
            refs[index] = cell.config.reference(cell.cfg, cell.mix, seed,
                                                index)
        nums = compare(got, refs[index])
        if not all(nums[k] <= limits[k] for k in limits):
            failed += 1
        for k in limits:
            if not nums[k] <= worst[k]:
                worst[k] = nums[k]
    if not samples:
        worst = {k: math.inf for k in limits}
    # JSON has no infinity or NaN: a number that is neither reads null
    return {k: {"value": worst[k] if math.isfinite(worst[k]) else None,
                "limit": limits[k]} for k in limits}, failed


# ------------------------------------------------------------ a run
def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             device: Dict[str, Any], peaks: Dict[str, Any],
             log: Callable[[str], None]) -> Dict[str, Any]:
    """Set-up, window, check; the result line as a dict."""
    import jax

    phases = dict(SETUP_PHASES)
    built = cell.config.build(cell.cfg, cell.mix, seed)
    phases["build"] = setup_clock_s()
    log(json.dumps({"cell": cell.name, "seed": seed, **built.describe()}))
    jax.block_until_ready(built.step(built.pool[0]))      # compiles
    t = time.perf_counter()
    for i in range(1, WARMUP_STEPS):
        out = built.step(built.pool[i % len(built.pool)])
    jax.block_until_ready(out)
    ahead = 0 if cell.mix["loop"] == "closed" else steps_ahead(
        (time.perf_counter() - t) / (WARMUP_STEPS - 1))
    keep = Reservoir(cell.mix["sample"], seed)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # Python calls: slow and not read
        opts.host_tracer_level = 1       # keeps the bench.* annotations
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = setup_clock_s()
    phases["warmup"] = setup_s
    log(json.dumps({"setup_phases_s": phases}))
    window = measure(built, cell.mix, seconds, keep, traced, ahead)
    summary = None
    if traced:
        jax.profiler.stop_trace()
        import xplane
        summary = xplane.load(xplane.find_xplane(TRACE_DIR))
    stats = jax.devices()[0].memory_stats() or {}
    samples = [(step, cell.config.answers(out)) for step, out in keep.items]
    keep.items = []
    del built
    gc.collect()
    numbers, failed = check(cell, seed, samples)
    run = Run(setup_s=setup_s, window=window,
              work=cell.config.work(cell.cfg), peaks=peaks, trace=summary)
    result: Dict[str, Any] = {
        "correct": failed == 0 and window.steps > 0 and all(
            v["value"] is not None and v["value"] <= v["limit"]
            for v in numbers.values()),
        "attempted": window.steps,
        "failed": failed,
        "metrics": read_metrics(cell.per_layer if traced else cell.end_to_end,
                                run),
        "device": dict(device,
                       memory_peak_bytes=stats.get("peak_bytes_in_use")),
        "window": {"seconds": window.seconds, "steps": window.steps,
                   "compile_events": window.compile_events,
                   "ahead": window.ahead,
                   "sampled": len(samples)},
        "setup_phases_s": phases,
    }
    if summary is not None:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["check"] = numbers
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    if not (ROOT / "src" / "repro").is_dir():
        log(f"run.py: the program (src/repro) is not in {ROOT}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(args.workload)
    try:
        device = require_tpu(cell.workload["chips"])
        peaks = load_peaks(device["kind"])
    except NoChipError as exc:
        log(f"run.py: {exc}")
        return 2
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, peaks, log)
    for name, num in result["check"].items():
        log(f"check {name} {num['value']!r} limit {num['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
