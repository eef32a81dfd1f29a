"""Compile bookkeeping shared by the entry points, benchmarks and tests.

* :func:`compile_count` — backend compiles this process has run, read
  from JAX's own ``jax.monitoring`` compile events.  Every program that
  reaches the backend compiler fires one event (a persistent-cache load
  included), so a retrace gate reads "no compile inside the window"
  directly; the jitted function's private dispatch-cache size also
  grows on calls that compile nothing, and is not a compile count.
* :func:`use_compile_cache` — turn on JAX's persistent compilation
  cache for an entry point.  Never called on library import.
"""
from __future__ import annotations

import collections
import os
import threading
from typing import Callable, Union

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_counts: collections.Counter = collections.Counter()
_lock = threading.Lock()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        with _lock:
            _counts[kw.get("fun_name", "")] += 1


# registered once, when this module is first imported: counts start then
jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_count(fn: Union[None, str, Callable] = None) -> int:
    """Backend compiles since this module was imported.  ``fn`` (a jitted
    function, or the name it was defined under) restricts the count to
    that function's programs; functions sharing a name share a count, so
    gates compare two readings around a window."""
    with _lock:
        if fn is None:
            return sum(_counts.values())
        name = fn if isinstance(fn, str) else fn.__name__
        return _counts[f"jit({name})"]


def use_compile_cache() -> str:
    """Enable JAX's persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, and no
    other directory is configured here), else ``.jax_cache`` at the root
    of this checkout — a fixed path, since the path is part of the key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
