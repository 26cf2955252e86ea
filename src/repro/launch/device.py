"""The accelerator a run measures on, and where its compiled code is kept.

Shared by every entry point that must run on the chip (``chip_smoke.py``
today, the chip benchmark next):

  * `require_tpu` names the device JAX found and refuses anything but a TPU
    — a device run never falls back to the CPU or the Pallas interpreter.
  * `use_compile_cache` points JAX's persistent compilation cache at one
    fixed directory, unless ``JAX_COMPILATION_CACHE_DIR`` already chose one.
"""

from __future__ import annotations

import os
import pathlib
from typing import Any, Dict

import jax


class NoAcceleratorError(RuntimeError):
    """JAX's first device is not a TPU."""


def require_tpu() -> Dict[str, Any]:
    """The device as JAX reports it (platform, kind, count), or
    `NoAcceleratorError` naming what was found when it is not a TPU."""
    dev = jax.devices()[0]
    found = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    if found["platform"] != "tpu":
        raise NoAcceleratorError(
            f"needs a TPU, but JAX's first device is platform "
            f"{found['platform']!r} ({found['kind']}, {found['count']} "
            f"device(s))")
    return found


def use_compile_cache(default_dir: "str | os.PathLike[str]") -> str:
    """Enable JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins;
    otherwise the cache goes to ``default_dir``, which callers keep fixed
    (the path is part of what a later run must find again). Every compile
    is cached, however short: the Pallas kernels compile in about a second.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env if env else str(pathlib.Path(default_dir))
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
