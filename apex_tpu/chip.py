"""What a program settles before it says it ran on the chip.

Two questions, asked once at start-up by ``chip_smoke.py`` and by the
benchmark's runs (the third — were the kernels really compiled in — is
:func:`apex_tpu.ops.mosaic_call_count`, asked of each executable):

- :func:`require_tpu` — which device is this?  Everything in the
  library runs on the CPU too (the test suite depends on it), so a run
  that lands there by accident completes, slowly and silently.  A
  program that reports device facts refuses instead.
- :func:`compile_cache_dir` — where does JAX's persistent compilation
  cache live?  Every chip call starts on a fresh machine; the cache is
  what a second process of the same call (or a machine that comes with
  ``JAX_COMPILATION_CACHE_DIR`` set) does not compile again.

One process drives all the chips of a host: a process that has touched
JAX holds them, and a child that needs them then fails or hangs.  So a
parent either stays off JAX and leaves the chip to its children, or
does everything itself (``chip_smoke.py``, ``benchmark/run.py``).
"""
from __future__ import annotations

import os
from typing import Dict

import jax

__all__ = ["CACHE_DIRNAME", "compile_cache_dir", "require_tpu"]

#: the in-checkout cache directory (listed in ``.gitignore``)
CACHE_DIRNAME = ".jax_cache"


def require_tpu() -> Dict[str, object]:
    """The device as JAX reports it — ``{"platform", "kind", "count"}``
    from ``jax.devices()`` — or ``SystemExit`` (a nonzero exit, message
    on stderr) unless the platform is ``tpu``.  Sets no platform."""
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices() reports {device}; refusing to run a "
            "chip path on another backend"
        )
    return device


def compile_cache_dir(checkout: str) -> str:
    """Place the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it:
    nothing is done and no other directory is set in code.  Where it is
    not, the cache goes to the FIXED ``<checkout>/.jax_cache`` — the
    path is part of every entry's key, so a directory named after a
    process, a temporary file or the time would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
