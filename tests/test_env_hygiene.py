"""Environment hygiene pins (ISSUE 12 satellite, re-cut by PR 22).

Older containers lacked ``from jax import shard_map``, an orbax with
``PyTreeRestore(partial_restore=...)`` and ``hypothesis``; the suite
carried ``env:``-reasoned skip guards for each, and this file pinned
their count so a regression could not hide inside a growing skip pile.

There is one installation now (jax 0.9.0, orbax 0.11.32, hypothesis
6.x) and it has all three, so the guards — which had all come to
evaluate to "run" — are gone.  What is pinned instead: that no guard
comes back under any name, and that the installation still has what the
guards used to probe, so a container that loses one fails HERE, by name,
instead of skipping or dying at collection somewhere else.
"""

import glob
import inspect
import os
import re

import pytest

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def test_no_env_skip_guards_remain():
    """No test skips on a probe of the installed packages: tier-1 is
    green-or-real."""
    offenders = []
    pattern = re.compile(
        r"importorskip\(|ENV_SKIP_|env_require_|HAS_(SHARD_MAP|ORBAX|"
        r"HYPOTHESIS)|allow_module_level")
    for path in glob.glob(os.path.join(TESTS_DIR, "*.py")):
        if os.path.basename(path) == os.path.basename(__file__):
            continue
        if pattern.search(open(path, encoding="utf-8").read()):
            offenders.append(os.path.basename(path))
    assert offenders == []


def _shard_map():
    from jax import shard_map
    assert "check_vma" in inspect.signature(shard_map).parameters


def _orbax_partial_restore():
    import orbax.checkpoint as ocp
    assert "partial_restore" in inspect.signature(
        ocp.args.PyTreeRestore.__init__).parameters


def _hypothesis():
    import hypothesis  # noqa: F401


@pytest.mark.parametrize("probe", [_shard_map, _orbax_partial_restore,
                                   _hypothesis],
                         ids=["jax.shard_map(check_vma)",
                              "orbax-partial_restore", "hypothesis"])
def test_installation_has_what_the_old_guards_probed(probe):
    probe()


def test_jax_compat_shim_is_gone():
    """The package imports ``shard_map`` from jax itself (PR 22 deleted
    distributed_llm_tpu/compat, the pre-0.4.35 spelling shim)."""
    pkg = os.path.join(os.path.dirname(TESTS_DIR), "distributed_llm_tpu")
    assert not os.path.exists(os.path.join(pkg, "compat"))
    for path in glob.glob(os.path.join(pkg, "parallel", "*.py")):
        assert "jax.experimental.shard_map" not in open(
            path, encoding="utf-8").read(), path
