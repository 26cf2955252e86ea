"""Kernel-level active-vs-passive HBM traffic (the paper's Table II story at
the VMEM level), from the analytical schedule model validated by the
instrumented AMC simulation, plus wall time of the interpret-mode kernels on
small shapes (correctness-scale only — this container is CPU)."""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro import plan
from repro.kernels.psum_matmul import psum_matmul
from repro.plan.gemm_model import matmul_terms

GEMMS = [
    ("ffn_up_8k", 8192, 28672, 8192),      # llama-90b FFN
    ("qkv_qwen2", 65536, 2048, 1536),      # token-major projection
    ("expert_ds", 16384, 1408, 2048),      # deepseek expert
    ("head_gemma", 16384, 256000, 2048),   # lm head
]


def traffic_rows() -> list[str]:
    rows = []
    for name, m, n, k in GEMMS:
        wl = plan.MatmulWorkload(name=name, m=m, n=n, k=k)
        s = plan.plan(wl, strategy="exhaustive_vmem",
                      controller="active").schedule
        act = float(matmul_terms(wl, s.bm, s.bn, s.bk, "active").bytes)
        pas = float(matmul_terms(wl, s.bm, s.bn, s.bk, "passive").bytes)
        saving = 100 * (1 - act / pas)
        rows.append(f"kernel_traffic/{name}/active_GB,0,{act/1e9:.3f}")
        rows.append(f"kernel_traffic/{name}/passive_GB,0,{pas/1e9:.3f}")
        rows.append(f"kernel_traffic/{name}/saving_pct,0,{saving:.1f}")
    return rows


def interpret_rows() -> list[str]:
    """Wall time of the two schedules in interpret mode (tiny shapes)."""
    rows = []
    m = n = k = 256
    x = jnp.asarray(np.random.default_rng(0).standard_normal((m, k)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((k, n)),
                    jnp.float32)
    for ctrl in ("active", "passive"):
        psum_matmul(x, w, bm=64, bn=64, bk=64, controller=ctrl)  # warm
        t0 = time.perf_counter()
        psum_matmul(x, w, bm=64, bn=64, bk=64, controller=ctrl).block_until_ready()
        us = (time.perf_counter() - t0) * 1e6
        rows.append(f"kernel_interpret/matmul256/{ctrl},{us:.0f},1")
    return rows
