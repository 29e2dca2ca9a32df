"""What this package asks of the jax installation it runs on.

The backend is whatever jax initialises: the accelerator where there is
one, the CPU where the operator set ``JAX_PLATFORMS=cpu`` (the tests do).
Nothing here or anywhere else in the package changes it, and a failure
to initialise it is the caller's exception, never a quiet host run.

Two helpers:

- ``configure_compile_cache`` places jax's persistent compilation cache.
  ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it itself);
  otherwise the cache goes to one fixed directory inside the checkout.
  The path is part of the cache key's surroundings — a directory that
  moves (a tmpdir, a pid, a timestamp) never hits.
- ``pick_twin`` is the one decision point of the host/device twins
  (``ops/ingest.py``, ``pxar/chunkindex.py``, ``pxar/similarityindex.py``,
  ``models/verify.py``): device when the backend is an accelerator, host
  on the CPU backend, counted per twin so a run can say which side did
  the work.
"""

from __future__ import annotations

import functools
import os
import sys

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# twin name → how many calls took each side (observability; a lost
# update under threads only shaves a count)
twin_counts: dict[str, dict[str, int]] = {}


def configure_compile_cache() -> str:
    """Point jax's persistent compilation cache at its place and return
    that place.  Call before the first compilation.  Child processes
    inherit the choice through the environment."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    if "jax" in sys.modules:        # imported already: it has read the env
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         CACHE_DIR)
    return CACHE_DIR


@functools.cache
def on_accelerator() -> bool:
    """True when jax's default backend is not the CPU.  Decided once
    (backends don't change mid-process); an exception from jax
    propagates and is not remembered."""
    import jax
    return jax.default_backend() != "cpu"


def pick_twin(name: str) -> bool:
    """True → the caller runs its device twin, False → its host twin."""
    device = on_accelerator()
    counts = twin_counts.setdefault(name, {"device": 0, "host": 0})
    counts["device" if device else "host"] += 1
    return device
