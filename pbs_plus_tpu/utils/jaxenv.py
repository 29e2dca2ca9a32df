"""What this package asks of the jax installation it runs on.

The backend is whatever jax initialises: the accelerator where there is
one, the CPU where the operator set ``JAX_PLATFORMS=cpu`` (the tests do).
Nothing here or anywhere else in the package changes it, and a failure
to initialise it is the caller's exception, never a quiet host run.

Three helpers:

- ``configure_compile_cache`` places jax's persistent compilation cache.
  ``JAX_COMPILATION_CACHE_DIR`` wins when set (jax reads it itself);
  otherwise the cache goes to one fixed directory inside the checkout.
  The path is part of the cache key's surroundings — a directory that
  moves (a tmpdir, a pid, a timestamp) never hits.
- ``pick_twin`` is the one decision point of the host/device twins
  (``pxar/chunkindex.py``, ``pxar/similarityindex.py``,
  ``models/verify.py``): device when the backend is an accelerator, host
  on the CPU backend, counted per twin so a run can say which side did
  the work.
- ``watch_compiles`` counts the programs jax builds or loads from its
  cache, process-wide (``compiles``, exported on ``/metrics``) and per
  thread (``thread_compiles``): a device dispatch that moved its own
  thread's count met a shape class for the first time
  (``utils/trace.py`` ``round_trip``).
"""

from __future__ import annotations

import functools
import os
import sys
import threading

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


# programs built, or loaded from the persistent cache, since
# ``watch_compiles``, and the seconds that took
compiles = {"count": 0, "seconds": 0.0}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles_here = threading.local()      # .count: those made on this thread


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        compiles["count"] += 1
        compiles["seconds"] += seconds
        _compiles_here.count = thread_compiles() + 1


@functools.cache
def watch_compiles() -> None:
    """Register, once, the ``jax.monitoring`` listener behind
    ``compiles``.  The device ops call it as they are imported, so it is
    in place before their first dispatch."""
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration)


def thread_compiles() -> int:
    """Programs compiled on the calling thread so far (jax compiles on
    the thread that makes the call)."""
    return getattr(_compiles_here, "count", 0)
