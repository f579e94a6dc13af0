"""Where XLA's persistent compile cache lives.

Every entry point that puts work on the device (``cli/trainer.py``,
``benchmark/run.py``, ``chip_smoke.py``, the training scripts under ``tools/``)
calls ``enable_compile_cache()`` before its first compile, so a second
process on the same machine loads the programs the first one built
instead of compiling them again.

The directory is chosen from outside: when ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and no code here sets another.  Otherwise the
cache goes to ``<checkout>/.jax_cache`` — one fixed path (listed in
``.gitignore``), never derived from ``tempfile``, a pid or a clock: the
path is part of the cache key, so a directory that moves never hits.

The same call installs the process's one compile listener
(``jax.monitoring``): every program XLA really compiles, as against one
read back from the persistent cache, is counted in
``trainer_xla_compiles_total`` and written, with its seconds, onto the
spans open on the compiling thread (``compiles``, ``compile_s``), so a
recompile names the dispatch, refresh or job phase it fell in.
"""

from __future__ import annotations

import os
import threading

from .metrics import default_registry

XLA_COMPILES = default_registry.counter(
    "trainer_xla_compiles_total",
    "Programs XLA compiled in this process (persistent-cache hits are loads, not compiles)",
)

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The directory compiled programs persist in (see module docstring)."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # Persist every program, not only the slow ones: with the default
    # one-second floor a warm process still recompiles the many small
    # programs, and a compile that lands on either side of the floor
    # makes "the second run adds nothing" a matter of luck.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _install_compile_listener()
    return path


# jax fires the duration event around every backend compile, and around a
# load from the persistent cache too; only the load fires the hit event,
# before it and on the same thread.  A duration with no hit before it is a
# compile: a cache miss, or a program compiled with the cache off.
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_listener = threading.local()
_listener_installed = False


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _listener.loaded = True


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event != _BACKEND_COMPILE:
        return
    if getattr(_listener, "loaded", False):
        _listener.loaded = False
        return
    from .tracing import default_tracer

    XLA_COMPILES.inc()
    # The innermost span and the phases around it: ``trainer/enqueue``
    # says which call compiled, its dispatch and ``trainer/run`` count it.
    for span in default_tracer.open_spans():
        span.set(
            compiles=span.attributes.get("compiles", 0) + 1,
            compile_s=span.attributes.get("compile_s", 0.0) + seconds,
        )


def _install_compile_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    import jax.monitoring as mon

    mon.register_event_listener(_on_event)
    mon.register_event_duration_secs_listener(_on_duration)
    _listener_installed = True
