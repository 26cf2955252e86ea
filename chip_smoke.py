#!/usr/bin/env python3
"""Run the planner -> schedule -> Pallas kernel path once on one TPU chip.

    python chip_smoke.py [--seed N]

Everything runs in this one process, on ``jax.devices()[0]``, with every
kernel compiled by Mosaic (``interpret=False``):

  0. device         refuse anything but a TPU: exit 2, naming what was found
  1. conv_network   ResNet-18 at its published channel widths (64-512),
                    shrunk to 56x56 at stride 1 (the 224x224 graph needs
                    shape-changing pools the kernel runner cannot execute),
                    planned by ``plan_graph(g, 2048, "exact_opt", "active")``
                    and run by ``run_network_kernels``. Every conv output is
                    compared with a float32 HIGHEST-precision ``lax.conv``
                    fed the kernel's own input for that layer.
  2. psum_matmul    the Qwen2-1.5B FFN up-projection (M=2048, K=1536,
                    N=8960, bf16) under the active and the passive
                    controller with ``exhaustive_vmem`` planner blocks, and
                    passive once more at the active blocks, whose 12 k-steps
                    round-trip the fp32 partial sums through HBM; each
                    against ``ref.matmul_ref`` in float32.
  3. flash_attention  12 heads x 2048 x 128, causal, bf16, against
                    ``ref.attention_ref`` in float32.

Each phase prints JSON lines: its shapes, the schedules it used, its error
against the reference (max |got - ref| and that over max |ref|) and its
compile seconds (first call minus second call; informational, not a
benchmark). A phase that raises or misses its tolerance makes the exit code
1. Only when every phase passed is the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Weights and inputs come from ``--seed``; nothing is read from outside the
checkout. The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import traceback
from typing import Any, Callable, Dict, List, Tuple

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.device import (NoAcceleratorError, require_tpu,  # noqa: E402
                                 use_compile_cache)
from repro.obs.trace import Stopwatch  # noqa: E402

CONV_RTOL = 1e-2      # f32 convs, each against its HIGHEST-precision reference
BF16_RTOL = 2e-2      # bf16 GEMMs and attention against float32 references


def emit(**row: Any) -> None:
    print(json.dumps(row), flush=True)


def errors(got: jax.Array, want: jax.Array) -> Tuple[float, float]:
    """(max abs error, that over the reference's max abs value)."""
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    max_abs = float(jnp.max(jnp.abs(got - want)))
    return max_abs, max_abs / max(float(jnp.max(jnp.abs(want))), 1e-30)


def first_and_second(fn: Callable[[], Any]) -> Tuple[Any, float]:
    """Run ``fn`` twice to completion; (result, first minus second seconds),
    the compile time of everything ``fn`` compiles on its first call."""
    with Stopwatch() as first:
        out = jax.block_until_ready(fn())
    with Stopwatch() as second:
        jax.block_until_ready(fn())
    return out, first.s - second.s


def conv_network(seed: int) -> List[str]:
    from repro.kernels.conv_network import (init_network_params,
                                            run_network_kernels)
    from repro.plan import netplan
    from repro.plan.graph import NetworkGraph

    g = NetworkGraph.from_cnn("resnet18").shrink(56, 1)
    netp = netplan.plan_graph(g, 2048, "exact_opt", "active")
    params = init_network_params(g, rng_seed=seed)
    values, compile_s = first_and_second(lambda: run_network_kernels(
        g, netp, params, rng_seed=seed, interpret=False))
    failed, worst = [], 0.0
    for node in g.workload_nodes:
        wl = node.workload
        pad = wl.k // 2
        x = jnp.concatenate([values[t] for t in node.ins], axis=0)
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)))
        want = jax.lax.conv_general_dilated(
            x[None], params[node.name], (wl.stride, wl.stride), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=jax.lax.Precision.HIGHEST)[0]
        sched = netp.schedules[node.name]
        max_abs, rel = errors(values[node.out], want)
        worst = max(worst, rel)
        emit(phase="conv_network", layer=node.name, x=list(x.shape),
             w=list(params[node.name].shape), stride=wl.stride,
             schedule={"m": sched.m, "n": sched.n,
                       "controller": sched.controller.value},
             max_abs=max_abs, rel=rel, ok=rel <= CONV_RTOL)
        if not rel <= CONV_RTOL:
            failed.append(node.name)
    emit(phase="conv_network", graph="resnet18.shrink(56, 1)",
         convs=len(g.workload_nodes), worst_rel=worst, rtol=CONV_RTOL,
         compile_s=compile_s, ok=not failed)
    return failed


def psum_matmul_phase(seed: int) -> List[str]:
    from repro import plan
    from repro.kernels import ref
    from repro.kernels.psum_matmul import psum_matmul

    m, k, n = 2048, 1536, 8960
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = ref.matmul_ref(x, w, out_dtype=jnp.float32)
    wl = plan.MatmulWorkload(m=m, n=n, k=k)
    sched = {c: plan.plan(wl, strategy="exhaustive_vmem",
                          controller=c).schedule
             for c in ("active", "passive")}
    runs = [("active", sched["active"]), ("passive", sched["passive"]),
            ("passive@active_blocks", dataclasses.replace(
                sched["active"], controller=plan.Controller.PASSIVE))]
    failed = []
    for name, s in runs:
        got, compile_s = first_and_second(
            lambda s=s: psum_matmul(x, w, schedule=s, interpret=False))
        max_abs, rel = errors(got, want)
        emit(phase="psum_matmul", run=name, m=m, k=k, n=n, dtype="bfloat16",
             schedule={"bm": s.bm, "bn": s.bn, "bk": s.bk,
                       "controller": s.controller.value,
                       "k_steps": -(-k // s.bk)},
             max_abs=max_abs, rel=rel, rtol=BF16_RTOL, compile_s=compile_s,
             ok=rel <= BF16_RTOL)
        if not rel <= BF16_RTOL:
            failed.append(name)
    return failed


def flash_phase(seed: int) -> List[str]:
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention

    bh, s, d = 12, 2048, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(key, (bh, s, d), jnp.bfloat16)
               for key in (kq, kk, kv))
    with jax.default_matmul_precision("highest"):
        want = ref.attention_ref(*(t.astype(jnp.float32) for t in (q, k, v)),
                                 causal=True)
    got, compile_s = first_and_second(
        lambda: flash_attention(q, k, v, causal=True, interpret=False))
    max_abs, rel = errors(got, want)
    emit(phase="flash_attention", bh=bh, sq=s, skv=s, d=d, causal=True,
         dtype="bfloat16", schedule={"bq": 128, "bk": 128}, max_abs=max_abs,
         rel=rel, rtol=BF16_RTOL, compile_s=compile_s, ok=rel <= BF16_RTOL)
    return [] if rel <= BF16_RTOL else ["flash_attention"]


PHASES: Dict[str, Callable[[int], List[str]]] = {
    "conv_network": conv_network,
    "psum_matmul": psum_matmul_phase,
    "flash_attention": flash_phase,
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of every weight and input")
    args = ap.parse_args()
    try:
        device = require_tpu()
    except NoAcceleratorError as exc:
        print(f"chip_smoke: {exc}", file=sys.stderr)
        return 2
    cache = use_compile_cache(ROOT / ".jax_cache")
    emit(phase="device", compile_cache=cache, **device)
    failed: List[str] = []
    for name, phase in PHASES.items():
        try:
            failed += [f"{name}/{what}" for what in phase(args.seed)]
        except Exception:    # report and go on; the exit code still fails
            traceback.print_exc()
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
