"""Persistent XLA compilation cache for the solver's jitted programs.

The solver compiles one program per (signature, E-bucket, P-bucket, N)
shape variant and each compile costs seconds. In-process jax caching
dedupes within one server lifetime; the on-disk cache lets restarts,
test runs and bench processes skip variants any prior process built.

The directory is placed from OUTSIDE: when ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and this module names no directory at all.
Otherwise the cache lives at ``<checkout>/.jax_cache`` -- a fixed path,
because the path is part of the cache key: a directory that moves with
a uid, a pid or the clock never hits.

``enable_compile_cache`` runs once, from the ``nomad_tpu.solver``
package import, which every program factory (solver/*, parallel/mesh)
sits behind -- so no program can compile before the cache is on.
"""
from __future__ import annotations

import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    # cache every program, however quick its compile was this time: a
    # time threshold would make what is cached depend on the clock
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
