"""Helpers that the harness and every configuration file share.

Nothing here imports the program under test (``repro``): the plain
references in ``configs/`` use these helpers too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0          # largest finite float8_e4m3fn


def key_of(seed: int, *salt: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed of any size (``PRNGKey``
    alone keeps only the low 64 bits, and as one word), folded with
    ``salt`` so that each array a seed makes has a key of its own."""
    seed %= 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    for s in salt:
        key = jax.random.fold_in(key, s)
    return key


def fake_quant_fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one per-tensor scale (the largest
    magnitude maps to 448) and returned in float32: what a path that
    stores operands in fp8 computes with."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale
