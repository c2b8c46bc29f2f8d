"""Process-wide count of the executables JAX builds.

One ``jax.monitoring`` listener, installed once (``install()``; the paged
scheduler calls it), counts ``/jax/core/compile/backend_compile_duration``.
JAX 0.9 fires that event around every backend build of an executable,
whether XLA compiles it or loads it from the persistent compilation cache
(a load also fires ``/jax/compilation_cache/cache_hits``, so counting both
would count a load twice).  A jit call that hits the in-memory cache fires
nothing.  ``count()`` is a plain integer read: the scheduler takes it at
its phase boundaries and adds each step's delta to ``stats["compiles"]``,
and ``Tracer.span`` tags a span that held a compile with ``compiles=n``.
"""
from __future__ import annotations

import threading

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()        # JAX may compile on more than one thread
_count = 0
_installed = False


def _on_duration(name: str, _secs: float, **_kw) -> None:
    global _count
    if name == BACKEND_COMPILE:
        with _lock:
            _count += 1


def install() -> None:
    """Register the listener (idempotent)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def count() -> int:
    """Executables built or loaded since ``install()``."""
    return _count
