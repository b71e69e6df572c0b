"""Start-up for entry points that run on an accelerator.

``init_device`` does the two things such an entry point needs before
its first compile:

- **Compile cache.** JAX's persistent compilation cache lives where
  ``JAX_COMPILATION_CACHE_DIR`` says when that is set (JAX reads the
  variable itself; nothing here overrides it). Otherwise it goes to
  ``<checkout>/.jax_cache``, one fixed path: the directory is part of
  the cache's key, so a path built from a temporary name, a process id
  or the time would never hit. ``.gitignore`` lists it.
- **Device check.** ``require="tpu"`` fails unless JAX's first device
  is a TPU, so a run that silently landed on the CPU backend cannot
  report itself as a chip run.

It returns the device stamp every chip result carries: platform,
``device_kind`` and device count, as JAX reports them.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: The checkout's fixed compile-cache directory (used only when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_device(require: Optional[str] = None) -> dict:
    """Configure the compile cache, then report (and check) the device.

    ``require``: platform name the first device must have (``"tpu"``);
    raises RuntimeError naming what JAX found instead.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if require is not None and stamp["platform"] != require:
        raise RuntimeError(
            f"no {require.upper()} found: JAX's first device is "
            f"{stamp['platform']!r} ({stamp['kind']}, {stamp['count']} "
            f"device(s)); this entry point does not fall back")
    return stamp
