"""Persistent XLA compilation cache, placed once by the entry points.

Every process that reaches a chip through the run tool starts with no
compiled code, and the ingest path compiles many sub-second programs
(``dequant_q8``, ``widen_f32``, ``_csr_coords``, one ``_decode_span_jit``
per layout) that JAX's default thresholds never persist. Entry points
(``chip_smoke.py``, ``benchmarks/*``, ``examples/*``,
``__graft_entry__.py``) call :func:`enable_compile_cache` before their
first jit; library modules never do — importing ``dmlc_tpu`` touches no
JAX configuration.
"""

from __future__ import annotations

import os
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# one fixed path inside the checkout (git-ignored): the directory is part
# of what makes a cache hit, so it is never derived from tempfile, a pid
# or the clock
CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on and return its directory
    (``None`` when this run keeps none).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the directory is the
    caller's: JAX reads the variable itself and this function sets none in
    code. Otherwise the cache lives at :data:`CACHE_DIR` — except in a run
    the caller pinned to the CPU backend (``JAX_PLATFORMS=cpu``: tests,
    the multichip dry run), which keeps no cache:
    the compile time it could save is small, and XLA loads a CPU
    executable cached on another machine even when the instruction sets
    differ (it logs "could lead to ... SIGILL" and goes on), which is what
    a checkout copied to the chip machine would hand it. Wherever a cache
    is on, the minimum compile time and entry size are zeroed so the
    pipeline's small programs are kept too.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        if os.environ.get("JAX_PLATFORMS") == "cpu":
            return None
        cache_dir = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
