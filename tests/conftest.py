"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The reference has no device-free test story (SURVEY.md §4.6); we do better —
multi-chip sharding is validated on host CPU via
``--xla_force_host_platform_device_count`` so the whole suite runs without a
TPU.  Must be set before jax is imported anywhere.
"""

import gc
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Force (not setdefault): the suite runs on the host CPU with 8 virtual
# devices wherever it is started — it must never take an accelerator, and
# its device count must not depend on the machine.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    # Read by the CPU backend at first use, which hasn't happened yet.
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compile cache: the suite is compile-dominated and most
# programs recur across runs (same tiny shapes).  Same placement rule as
# the runtime entry points (utils/compile_cache.py, stdlib-only at import):
# an externally set JAX_COMPILATION_CACHE_DIR wins, otherwise the fixed
# in-checkout directory.  Exported via env so subprocess-based tests
# (test_reference_unchanged.py, which recompile full engines) inherit it.
from distributed_llm_tpu.utils.compile_cache import \
    compile_cache_dir  # noqa: E402

os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

# Dynamic twin of the lint's ownership rules (lint/checkers/ownership.py):
# every engine stop() in the suite asserts zero leaked pool blocks and
# zero live spill pins.  setdefault so a debugging run can disarm it
# (DLLM_KV_LEAK_CHECK=0 or empty).
os.environ.setdefault("DLLM_KV_LEAK_CHECK", "1")

import jax  # noqa: E402
import pytest  # noqa: E402

# Belt and braces for a jax some pytest plugin imported before this file
# ran (the env vars above are only read at import).
jax.config.update("jax_platforms", "cpu")

from distributed_llm_tpu.utils.compile_cache import \
    enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'` (ROADMAP.md): slow marks the soak/chaos
    # legs excluded from it; chaos marks scripted-fault harness scenarios
    # (run them alone with `-m chaos`).  Registered here because the repo
    # has no pytest.ini.
    config.addinivalue_line(
        "markers", "slow: long-running soak/chaos legs, excluded from "
                   "tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: scripted-fault chaos-soak scenarios "
                   "(utils/faults.py FaultSchedule)")


@pytest.fixture(autouse=True, scope="module")
def _release_a_files_programs():
    """After a file's tests, drop jax's in-memory program caches.  A
    loaded CPU executable keeps some 700 memory maps, an eager ``scan``
    read back from the persistent cache loads one a CALL, and the jit
    caches keep them all: a worker that serves five files passed Linux's
    65 530 maps a process (``vm.max_map_count``) in
    ``test_latent_moe.py``'s int8 test and died inside
    ``deserialize_executable`` in 2 of 4 whole runs (PR 61: a new file
    had shifted which files share a worker).  The persistent cache
    keeps the next file's reads cheap."""
    yield
    jax.clear_caches()
    gc.collect()
