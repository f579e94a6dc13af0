"""Where XLA's persistent compile cache lives.

Every entry point that puts work on the device (``cli/trainer.py``,
``bench.py``, ``chip_smoke.py``, the training scripts under ``tools/``)
calls ``enable_compile_cache()`` before its first compile, so a second
process on the same machine loads the programs the first one built
instead of compiling them again.

The directory is chosen from outside: when ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and no code here sets another.  Otherwise the
cache goes to ``<checkout>/.jax_cache`` — one fixed path (listed in
``.gitignore``), never derived from ``tempfile``, a pid or a clock: the
path is part of the cache key, so a directory that moves never hits.
"""

from __future__ import annotations

import os

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
    return path
