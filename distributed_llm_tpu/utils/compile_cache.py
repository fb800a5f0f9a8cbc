"""Persistent XLA compilation cache for the entry points.

Every process that serves or measures recompiles the same programs: the
server warms each tier's program family at start, the tester builds a
fresh Router per config (reference semantics —
routing_chatbot_tester.py:368-376), and each chip run is a new process
on a new machine.  JAX's persistent cache keys serialized executables by
HLO hash on disk — fresh processes (and fresh jit closures inside one
process) deserialize instead of recompiling.

Where the cache lives is the deployment's choice, not the code's: when
``JAX_COMPILATION_CACHE_DIR`` is set, jax already uses that directory
and this module sets no other.  Unset, the cache goes to one fixed
directory inside the checkout (the path is part of the cache key's
surroundings — a directory that moves never hits).

The test suite wires the same rule in tests/conftest.py; this helper is
for the runtime entry points (serving/app.py, chip_smoke.py,
bench.tester, training.pretrain).  Call before the first
device computation.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the persistent cache belongs in: the externally set
    ``JAX_COMPILATION_CACHE_DIR`` when there is one, else the fixed
    in-checkout default."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_persistent_compile_cache() -> str:
    """Point jax at the persistent compilation cache; returns the dir.

    The thresholds make small/fast programs cacheable too (the serving
    program family is many second-scale compiles); jax reads its own
    ``JAX_PERSISTENT_CACHE_MIN_*`` variables, which win when set."""
    import jax
    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
