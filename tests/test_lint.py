"""dllm-lint: framework + checker tests, and the repo-clean tier-1 pin.

Each checker gets at least one known-bad fixture it MUST flag and one
near-miss it must NOT (precision is what makes the suite enforceable —
a noisy checker gets suppressed into meaninglessness).  The lock
checker's bad fixture reproduces the PR 2 lock-held-through-compile bug
shape, so a reintroduction of that class fails tier-1.  The final pin
runs the real suite over the real repo and requires ZERO unsuppressed
findings.

These are pure AST passes — no jax, no engines — so the whole file runs
in well under a second.
"""

from __future__ import annotations

import json
import os
import textwrap
import threading
import time

import pytest

from distributed_llm_tpu.config_registry import (ENV_VARS,
                                                 UnknownConfigError,
                                                 env_flag, env_int,
                                                 env_str,
                                                 render_markdown)
from distributed_llm_tpu.lint import (Module, Project, all_checkers,
                                      repo_root, run_checkers, run_lint)
from distributed_llm_tpu.lint.checkers.config_drift import \
    ConfigDriftChecker
from distributed_llm_tpu.lint.checkers.error_shape import ErrorShapeChecker
from distributed_llm_tpu.lint.checkers.jit_purity import JitPurityChecker
from distributed_llm_tpu.lint.checkers.locks import LockChecker
from distributed_llm_tpu.lint.checkers.metrics_discipline import \
    MetricsDisciplineChecker
from distributed_llm_tpu.lint.checkers.ownership import OwnershipChecker
from distributed_llm_tpu.lint.checkers.span_discipline import \
    SpanDisciplineChecker

SERVING = "distributed_llm_tpu/serving/fixture.py"
ENGINE = "distributed_llm_tpu/engine/fixture.py"


def _project(files, *, dedent=True, complete=True):
    """The one fixture loader: {relpath: source} -> Project.  Inline
    triple-quoted fixtures get dedented; ``dedent=False`` keeps
    whole-file sources byte-exact, ``complete=False`` marks a narrowed
    (partial) load for the checkers that care."""
    return Project(
        "/", {path: Module(path, textwrap.dedent(src) if dedent else src)
              for path, src in files.items()},
        complete=complete)


def _lint(checker, files, **kw):
    return run_checkers(_project(files, **kw), [checker])


def _rules(result):
    return [f.rule for f in result.findings]


# -- lock checker ------------------------------------------------------------

PR2_BUG_SHAPE = """
    import threading

    class Manager:
        def __init__(self):
            self._lock = threading.RLock()
            self._engine = None

        def _build(self):
            engine = object()
            engine.warmup()              # compiles for minutes on chip
            self._engine = engine

        def health(self):
            with self._lock:
                if self._engine is None:
                    self._build()        # transitively blocking
                return {"ok": self._engine is not None}
"""


def test_lock_checker_catches_pr2_lock_held_through_compile():
    """Acceptance: the exact PR 2 shape — a probe-path method holding a
    lock through an engine compile reached via a local call — is
    flagged on reintroduction (the blocking-ness propagates through the
    module-local call graph, not just the direct name set)."""
    result = _lint(LockChecker(), {ENGINE: PR2_BUG_SHAPE})
    blocking = [f for f in result.findings
                if f.rule == "lock-blocking-call"]
    assert len(blocking) == 1, result.findings
    assert "_build" in blocking[0].message
    assert "transitively" in blocking[0].message
    assert "warmup" in blocking[0].message


def test_lock_checker_near_miss_bounded_and_unlocked():
    src = """
        import threading

        class Manager:
            def __init__(self):
                self._lock = threading.Lock()
                self._thread = None

            def stop(self):
                with self._lock:
                    if self._thread is not None:
                        self._thread.join(timeout=5)   # bounded: fine

            def start(self):
                engine = object()
                engine.warmup()                # no lock held: fine
    """
    assert _lint(LockChecker(), {ENGINE: src}).findings == []


def test_lock_checker_unbounded_wait_under_lock():
    src = """
        import threading

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def drain(self, q):
                with self._lock:
                    return q.get()          # unbounded queue wait
    """
    result = _lint(LockChecker(), {SERVING: src})
    assert _rules(result) == ["lock-blocking-call"]


def test_lock_checker_drain_under_lifecycle_lock_flagged():
    """``drain`` is in the blocking-call name set (PR 5): it waits out
    in-flight work and then calls stop_server, so calling it under the
    lifecycle lock is a self-deadlock — flagged directly AND through a
    local call."""
    src = """
        import threading

        class Manager:
            def __init__(self):
                self._lock = threading.RLock()

            def drain(self, timeout_s=None):
                pass

            def shutdown(self):
                with self._lock:
                    self.drain()             # blocking under the lock
    """
    result = _lint(LockChecker(), {SERVING: src})
    blocking = [f for f in result.findings
                if f.rule == "lock-blocking-call"]
    assert len(blocking) == 1, result.findings
    assert "drain" in blocking[0].message


def test_lock_checker_drain_near_miss_outside_lock_clean():
    """The real shape (engine/manager.py): drain runs OUTSIDE the
    lifecycle lock and only stop_server re-takes it internally — clean."""
    src = """
        import threading

        class Manager:
            def __init__(self):
                self._lock = threading.RLock()

            def stop_server(self):
                with self._lock:
                    pass

            def drain(self, timeout_s=None):
                self.stop_server()           # no lock held here: fine

            def shutdown(self):
                self.drain()                 # nor here
    """
    assert _lint(LockChecker(), {SERVING: src}).findings == []


def test_lock_order_inversion_detected_and_consistent_order_clean():
    bad = """
        import threading

        class A:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def one(self):
                with self._a:
                    with self._b:
                        pass

            def two(self):
                with self._b:
                    with self._a:
                        pass
    """
    result = _lint(LockChecker(), {SERVING: bad})
    assert "lock-order-inversion" in _rules(result)

    good = bad.replace(
        "with self._b:\n                    with self._a:",
        "with self._a:\n                    with self._b:")
    assert _lint(LockChecker(), {SERVING: good}).findings == []


def test_lock_mixed_guard_flags_bare_read_of_worker_written_attr():
    """The serving/tiers.py bug this PR fixed: an attribute written from
    a worker thread under a lock, but read bare elsewhere."""
    bad = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def go(self):
                threading.Thread(target=self._work).start()

            def _work(self):
                with self._lock:
                    self._n += 1

            def read(self):
                return self._n
    """
    result = _lint(LockChecker(), {SERVING: bad})
    assert _rules(result) == ["lock-mixed-guard"]
    assert "_n" in result.findings[0].message

    good = bad.replace(
        "        def read(self):\n                return self._n",
        "        def read(self):\n                with self._lock:\n"
        "                    return self._n")
    assert "with self._lock:\n" in good        # the replace really hit
    assert _lint(LockChecker(), {SERVING: good}).findings == []


def test_lock_mixed_guard_ignores_never_guarded_scheduler_state():
    """Near-miss: attrs never guarded anywhere are presumed
    single-writer by design (batching scheduler state + GIL-safe
    snapshot reads) — no finding."""
    src = """
        import threading

        class Engine:
            def __init__(self):
                self._progress = 0.0

            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self._progress = 1.0

            def snapshot(self):
                return self._progress
    """
    assert _lint(LockChecker(), {ENGINE: src}).findings == []


def test_lock_mixed_guard_flags_refcount_mutation_outside_allocator_lock():
    """ISSUE 10 regression shape: the refcounted BlockAllocator's
    ``_refs`` table is written from scheduler-thread-reachable code
    under the allocator lock — a bare mutation site elsewhere (a torn
    incref racing a concurrent free) must flag."""
    bad = """
        import threading

        class Allocator:
            def __init__(self):
                self._lock = threading.Lock()
                self._refs = {}
                self._free = []

            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                with self._lock:
                    for b, r in list(self._refs.items()):
                        if r == 0:
                            del self._refs[b]

            def share(self, blocks):
                for b in blocks:
                    self._refs[b] = self._refs[b] + 1   # bare incref
    """
    result = _lint(LockChecker(), {ENGINE: bad})
    assert "lock-mixed-guard" in _rules(result), result.findings
    assert any("_refs" in f.message for f in result.findings)


def test_lock_mixed_guard_refcount_mutation_under_lock_clean():
    """Near-miss: every ``_refs`` touch under the allocator lock — the
    shipped BlockAllocator shape — stays silent."""
    src = """
        import threading

        class Allocator:
            def __init__(self):
                self._lock = threading.Lock()
                self._refs = {}
                self._free = []

            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                with self._lock:
                    for b, r in list(self._refs.items()):
                        if r == 0:
                            del self._refs[b]

            def share(self, blocks):
                with self._lock:
                    for b in blocks:
                        self._refs[b] = self._refs[b] + 1
    """
    assert _lint(LockChecker(), {ENGINE: src}).findings == []


def test_lock_mixed_guard_all_bare_worker_writes_presumed_single_writer():
    """DELIBERATE LIMIT (pinned so a future edit is a conscious choice):
    a worker whose writes to an attr are ALL bare is presumed
    single-writer even when some OTHER site touches the attr under a
    lock.  The shapes are statically indistinguishable: the batching
    scheduler owns `_slots` bare everywhere while stop() reads it under
    the (unrelated) lifecycle lock AFTER joining the thread — flagging
    that pattern would force suppressions on the engine's core design.
    The rule therefore keys on the worker itself locking at some write
    site ("a discipline exists but missed a site"); writer-always-bare
    races need the worker to adopt a lock before the checker can see
    the inconsistency."""
    src = """
        import threading

        class Engine:
            def __init__(self):
                self._lock = threading.Lock()
                self._slots = {}

            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self._slots[0] = object()    # bare: scheduler-owned

            def stop(self):
                with self._lock:             # lifecycle lock, post-join
                    return len(self._slots)
    """
    assert _lint(LockChecker(), {ENGINE: src}).findings == []


def test_lock_mixed_guard_flags_bare_tenant_counter_read():
    """ISSUE 17 shape: the tenant-quota registry's in-flight counters
    are debited from router worker threads under the registry lock — a
    bare read feeding an admission decision elsewhere is exactly the
    torn-count race the registry lock exists to prevent."""
    bad = """
        import threading

        class TenantQuotas:
            def __init__(self):
                self._lock = threading.Lock()
                self._inflight = {}

            def watch(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                with self._lock:
                    for t in list(self._inflight):
                        self._inflight[t] = max(0, self._inflight[t] - 1)

            def try_admit(self, tenant):
                return self._inflight[tenant] < 4   # bare read
    """
    result = _lint(LockChecker(), {SERVING: bad})
    assert "lock-mixed-guard" in _rules(result), result.findings
    assert any("_inflight" in f.message for f in result.findings)


def test_lock_mixed_guard_tenant_counter_under_lock_clean():
    """Near-miss: the shipped TenantQuotas shape — every counter touch
    under the registry lock — stays silent."""
    src = """
        import threading

        class TenantQuotas:
            def __init__(self):
                self._lock = threading.Lock()
                self._inflight = {}

            def watch(self):
                threading.Thread(target=self._drain).start()

            def _drain(self):
                with self._lock:
                    for t in list(self._inflight):
                        self._inflight[t] = max(0, self._inflight[t] - 1)

            def try_admit(self, tenant):
                with self._lock:
                    return self._inflight[tenant] < 4
    """
    assert _lint(LockChecker(), {SERVING: src}).findings == []


def test_lock_checker_manual_release_ends_held_region():
    """acquire/try/finally-release then blocking work must not flag:
    the held region ends at the release."""
    src = """
        import threading
        import time

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def step(self, engine):
                self._lock.acquire(timeout=5)
                try:
                    x = 1
                finally:
                    self._lock.release()
                engine.warmup()          # lock already released: fine
    """
    assert _lint(LockChecker(), {ENGINE: src}).findings == []

    held = src.replace("engine.warmup()          # lock already released"
                       ": fine", "")
    held = held.replace("x = 1", "engine.warmup()")
    result = _lint(LockChecker(), {ENGINE: held})
    assert _rules(result) == ["lock-blocking-call"]   # inside: still flags


def test_typo_d_lint_target_is_a_usage_error():
    """A target path matching no files must fail loudly, not lint
    nothing and report clean."""
    from distributed_llm_tpu.lint import load_project
    with pytest.raises(FileNotFoundError):
        load_project(repo_root(), ["distributed_llm_tpu/servingg"])


# -- jit purity --------------------------------------------------------------

def test_jit_purity_flags_host_impurity_and_concretization():
    src = """
        import time

        import jax


        def step(x):
            t0 = time.perf_counter()
            print("tracing")
            if bool(x):
                return x
            return x

        fn = jax.jit(step)
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    rules = _rules(result)
    assert rules.count("jit-host-impurity") == 2        # time + print
    assert "jit-traced-concretization" in rules


def test_jit_purity_flags_transitive_callee_and_host_rng():
    src = """
        import jax
        import numpy as np


        def noise(shape):
            return np.random.normal(size=shape)    # host RNG


        def step(x):
            return x + noise(x.shape)

        fn = jax.jit(step)
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    assert _rules(result) == ["jit-host-impurity"]
    assert "np.random" in result.findings[0].message


def test_jit_purity_near_miss_host_code_and_jax_random_clean():
    src = """
        import time

        import jax
        from jax import random


        def step(x, key):
            return x + random.normal(key, x.shape)

        fn = jax.jit(step)


        def host_benchmark(x):
            t0 = time.perf_counter()      # host code: fine
            print(fn(x))                  # host code: fine
            return time.perf_counter() - t0
    """
    assert _lint(JitPurityChecker(), {ENGINE: src}).findings == []


def test_jit_purity_lambda_root_params_are_traced():
    src = """
        import jax

        f = jax.jit(lambda x: 1 if bool(x) else 0)
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    assert _rules(result) == ["jit-traced-concretization"]


def test_jit_purity_decorator_and_shard_map_roots():
    src = """
        import time
        from functools import partial

        import jax
        from jax import shard_map


        @partial(jax.jit, donate_argnums=(0,))
        def decorated(x):
            time.sleep(1)
            return x


        def mapped(x):
            print(x)
            return x

        f = shard_map(mapped, mesh=None, in_specs=None, out_specs=None)
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    assert _rules(result).count("jit-host-impurity") == 2


def test_jit_purity_pallas_kernel_blocking_host_callback_flagged():
    """ISSUE 6: a Pallas KERNEL body is traced like any jit root (and a
    blocking host callback inside one would wedge the whole device
    program) — the checker must catch it, including through the repo
    idiom of assigning ``partial(_kernel, ...)`` to a variable before
    ``pl.pallas_call``."""
    src = """
        import functools
        import time

        from jax.experimental import pallas as pl


        def _ragged_kernel(pos_ref, q_ref, o_ref, *, bs):
            time.sleep(0.1)              # blocking host callback
            o_ref[0] = q_ref[0]


        def run(q, pos):
            kernel = functools.partial(_ragged_kernel, bs=16)
            return pl.pallas_call(kernel, grid=(4,))(pos, q)
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    assert _rules(result) == ["jit-host-impurity"], result.findings
    assert "time.sleep" in result.findings[0].message


def test_jit_purity_pallas_near_miss_host_timing_around_call_clean():
    """Host-side timing AROUND a pallas_call (the micro A/B's own shape)
    must not flag: only the kernel body is traced."""
    src = """
        import time

        from jax.experimental import pallas as pl


        def _kernel(q_ref, o_ref):
            o_ref[0] = q_ref[0]


        def bench(q):
            t0 = time.perf_counter()     # host code: fine
            out = pl.pallas_call(_kernel, grid=(1,))(q)
            return out, time.perf_counter() - t0
    """
    assert _lint(JitPurityChecker(), {ENGINE: src}).findings == []


def test_jit_purity_shard_map_wrapped_pallas_dispatcher_flagged():
    """ISSUE 16: the TP path wraps the ragged Pallas dispatchers in
    ``shard_map`` (parallel/tp_attention) — the shard_map BODY is a
    traced root even though it is also ordinary host code that builds
    a ``pallas_call``.  A blocking host callback inside that body runs
    once per shard per trace and wedges the sharded program; the
    checker must flag it through the composed idiom (shard_map body
    containing a pallas_call dispatch)."""
    src = """
        import time
        from functools import partial

        from jax.experimental import pallas as pl
        from jax import shard_map


        def _kernel(q_ref, o_ref, *, bs):
            o_ref[0] = q_ref[0]


        def _shard_body(q, pool):
            time.sleep(0.01)             # host callback inside the shard
            kernel = partial(_kernel, bs=16)
            return pl.pallas_call(kernel, grid=(4,))(q, pool)


        def tp_decode(mesh, specs):
            return shard_map(_shard_body, mesh=mesh, in_specs=specs,
                             out_specs=specs[0])
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    assert _rules(result) == ["jit-host-impurity"], result.findings
    assert "time.sleep" in result.findings[0].message


def test_jit_purity_wrapper_call_inside_lambda_body_still_roots():
    """A jit/pallas_call ISSUED inside a lambda body must keep rooting
    its function argument (lambdas are not scope entries, so the scoped
    walker has to descend into them — regression guard for the scoped
    rewrite)."""
    src = """
        import time

        import jax


        def step(x):
            time.sleep(1)
            return x


        run = lambda q: jax.jit(step)(q)
    """
    result = _lint(JitPurityChecker(), {ENGINE: src})
    assert _rules(result) == ["jit-host-impurity"], result.findings
    assert "time.sleep" in result.findings[0].message


def test_jit_purity_pallas_variable_resolution_is_scoped():
    """A host-only helper bound to the SAME variable name in a different
    function must not be rooted as a kernel (module-wide name resolution
    would produce a CI-blocking false impurity finding here)."""
    src = """
        import functools
        import time

        from jax.experimental import pallas as pl


        def _kernel(q_ref, o_ref):
            o_ref[0] = q_ref[0]


        def _poll_host():
            time.sleep(0.5)              # legitimate host code


        def run(q):
            fn = functools.partial(_kernel)
            return pl.pallas_call(fn, grid=(1,))(q)


        def wait_for_device():
            fn = _poll_host               # same variable name, host scope
            fn()
    """
    assert _lint(JitPurityChecker(), {ENGINE: src}).findings == []


def test_jit_purity_covers_shipped_rows_kernel_module():
    """The real ops/rows_attention.py kernel is in the checker's
    jit-root coverage: injecting a host impurity into a kernel body of
    the SHIPPED source must produce a finding (a module the checker
    cannot see would pass this by linting nothing)."""
    path = os.path.join(repo_root(),
                        "distributed_llm_tpu/ops/rows_attention.py")
    with open(path, encoding="utf-8") as f:
        src = f.read()
    marker = "live = live_blocks(b)"
    assert marker in src, "kernel body marker moved — update this test"
    bad = "import time\n" + src.replace(
        marker, "time.sleep(0.0)\n    " + marker, 1)
    rel = "distributed_llm_tpu/ops/rows_attention.py"
    result = _lint(JitPurityChecker(), {rel: bad}, dedent=False)
    assert "jit-host-impurity" in _rules(result), result.findings
    # And the pristine module lints clean (no false findings from the
    # broadened root set).
    clean = _lint(JitPurityChecker(), {rel: src}, dedent=False)
    assert clean.findings == []


# -- error shape -------------------------------------------------------------

def test_error_shape_flags_drift():
    src = """
        def bad_nested():
            return {"error": {"code": 500}}


        def bad_extra_key():
            return {"error": "Request failed: x", "status": 500}


        def bad_retry_typing():
            return {"error": "Request failed: x", "retry_after_s": "soon"}
    """
    result = _lint(ErrorShapeChecker(), {SERVING: src})
    assert _rules(result) == ["error-shape"] * 3


def test_error_shape_near_miss_conforming_and_unrelated():
    src = """
        def ok(exc, retry):
            return {"error": f"Request failed: {exc}",
                    "retry_after_s": round(retry, 2)}


        def unrelated():
            return {"response": "fine", "cache_hit": False}
    """
    assert _lint(ErrorShapeChecker(), {SERVING: src}).findings == []


# -- config drift ------------------------------------------------------------

def test_config_drift_flags_unregistered_env_read():
    src = """
        import os

        VAL = os.environ.get("DLLM_DEFINITELY_NOT_REGISTERED", "x")
    """
    result = _lint(ConfigDriftChecker(), {"scripts/probe.py": src})
    unregistered = [f for f in result.findings
                    if f.rule == "config-env-unregistered"]
    assert len(unregistered) == 1
    assert "DLLM_DEFINITELY_NOT_REGISTERED" in unregistered[0].message


def test_config_drift_near_miss_registered_read():
    src = """
        import os

        VAL = os.environ.get("DLLM_PROFILE_TICKS", "256")
    """
    result = _lint(ConfigDriftChecker(), {"scripts/probe.py": src})
    assert not [f for f in result.findings
                if f.rule == "config-env-unregistered"]


def test_registry_accessors_fail_loudly_on_typo():
    with pytest.raises(UnknownConfigError):
        env_int("DLLM_PROFILE_TICK", 3)         # typo'd name
    with pytest.raises(UnknownConfigError):
        env_str("DLLM_NOT_A_KNOB")
    assert env_int("DLLM_PROFILE_TICKS", 3) == 3  # unset -> default


def test_registry_accessors_read_environment(monkeypatch):
    monkeypatch.setenv("DLLM_PROFILE_TICKS", "7")
    assert env_int("DLLM_PROFILE_TICKS", 3) == 7
    monkeypatch.setenv("DLLM_PROFILE_TICKS", "garbage")
    assert env_int("DLLM_PROFILE_TICKS", 3) == 3  # never lose the run
    monkeypatch.setenv("DLLM_FLAGSHIP_KV_INT8", "1")
    assert env_flag("DLLM_FLAGSHIP_KV_INT8")
    monkeypatch.delenv("DLLM_FLAGSHIP_KV_INT8")
    assert not env_flag("DLLM_FLAGSHIP_KV_INT8")


def test_config_md_in_sync_with_registry():
    path = os.path.join(repo_root(), "CONFIG.md")
    with open(path, encoding="utf-8") as f:
        on_disk = f.read()
    assert on_disk == render_markdown(), (
        "CONFIG.md is stale — regenerate with "
        "`python -m distributed_llm_tpu.config_registry > CONFIG.md`")


def test_every_registered_env_var_documents_itself():
    for name, entry in ENV_VARS.items():
        assert entry.doc.strip(), name
        assert entry.consumer.strip(), name


def test_config_drift_no_stale_findings_on_narrowed_target_run():
    """A narrowed lint run (e.g. `lint distributed_llm_tpu/serving`)
    cannot prove a registered var has no reader — no-reader findings
    must only fire when the full default project was loaded."""
    src = "X = 1\n"
    result = _lint(ConfigDriftChecker(),
                   {"distributed_llm_tpu/serving/f.py": src},
                   complete=False)
    assert not [f for f in result.findings
                if f.rule == "config-env-stale"]

    from distributed_llm_tpu.lint import load_project
    narrowed = load_project(repo_root(), ["distributed_llm_tpu/serving"])
    assert narrowed.complete is False
    assert load_project(repo_root()).complete is True


def test_lock_mixed_guard_thread_target_scoped_to_spawning_class():
    """A Thread(target=self._work) in class A must not mark class B's
    same-named method worker-reachable (cross-class name collisions are
    common: _loop, _work, _run)."""
    src = """
        import threading

        class Spawner:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def go(self):
                threading.Thread(target=self._work).start()

            def _work(self):
                with self._lock:
                    self._n += 1

            def read(self):
                with self._lock:
                    return self._n

        class Bystander:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def _work(self):
                with self._lock:
                    self._n += 1

            def read(self):
                return self._n      # single-threaded class: no finding
    """
    assert _lint(LockChecker(), {SERVING: src}).findings == []


# -- span discipline (migrated checker) --------------------------------------

def test_span_discipline_flags_bare_and_manual_enter():
    src = """
        def f(tr):
            sp = tr.span('x')          # bare: no structural exit
            tr.start_span('y')         # manual enter: forbidden
            return sp
    """
    result = _lint(SpanDisciplineChecker(), {SERVING: src})
    assert sorted(_rules(result)) == ["span-manual-enter",
                                      "span-not-with"]


def test_span_discipline_near_miss_with_item():
    src = """
        def f(tr):
            with tr.span('x') as sp:
                sp.annotate(ok=True)
    """
    assert _lint(SpanDisciplineChecker(), {SERVING: src}).findings == []


# -- obs discipline (SLO feed has ONE site) ----------------------------------

def test_obs_discipline_flags_slo_feed_outside_finish():
    """A second SLOMonitor.record_request site in the instrumented
    layers double-counts requests and halves every goodput reading —
    flagged anywhere but _finish_request."""
    from distributed_llm_tpu.lint.checkers.obs_discipline import \
        ObsDisciplineChecker
    bad = """
        class Router:
            def _finish_request(self, trace, which, ok):
                self.slo.record_request("hybrid", which, ok)   # sanctioned

            def route_query(self, history):
                self.slo.record_request("hybrid", "nano", True)

        def helper(obs):
            obs.slo.record_request("perf", "orin", False)
    """
    result = _lint(ObsDisciplineChecker(), {SERVING: bad})
    assert _rules(result) == ["slo-feed-outside-finish"] * 2
    assert all("_finish_request" in f.message for f in result.findings)


def test_obs_discipline_near_miss_unrelated_record_request():
    """Precision: a non-SLO object's record_request method, and the
    sanctioned feed inside _finish_request (including via a callback
    defined there), must stay silent."""
    from distributed_llm_tpu.lint.checkers.obs_discipline import \
        ObsDisciplineChecker
    src = """
        class AccessLog:
            def flush(self):
                self.log.record_request("GET /chat")     # not an SLO feed

        class Router:
            def _finish_request(self, trace, which, ok):
                self.obs.slo.record_request("s", which, ok)
                retry = lambda: self.slo.record_request("s", which, ok)
                return retry
    """
    assert _lint(ObsDisciplineChecker(), {SERVING: src}).findings == []


def test_obs_discipline_flags_profiler_stamp_in_traced_code():
    """ISSUE 11: a profiler stamp inside a jit-traced function runs at
    TRACE time — it bakes one perf_counter constant into the compiled
    program and measures nothing after.  Flagged via the project-wide
    traced closure, whatever module it lands in."""
    from distributed_llm_tpu.lint.checkers.obs_discipline import \
        ProfilerDisciplineChecker
    bad = """
        import jax

        def build(profiler):
            def run(x):
                profiler.event("compile", stage="decode")
                return x
            return jax.jit(run)

        class Engine:
            def _decode_step(self):
                def step(params, pool):
                    with self.profiler.phase("decode"):
                        return params
                return jax.jit(step)
    """
    result = _lint(ProfilerDisciplineChecker(), {ENGINE: bad})
    assert _rules(result) == ["profiler-hook-in-traced-code"] * 2
    assert all("TRACE time" in f.message for f in result.findings)
    # Its whole_project widening must NOT ride on the per-file slo rule
    # (they are separate checkers precisely so --changed keeps
    # filtering slo-feed findings to changed files).
    from distributed_llm_tpu.lint.checkers.obs_discipline import \
        ObsDisciplineChecker
    assert ProfilerDisciplineChecker.whole_project is True
    assert ObsDisciplineChecker.whole_project is False


def test_obs_discipline_near_miss_profiler_on_host_side():
    """Precision: stamping AROUND a jitted call on the host side — the
    exact idiom the engine uses — and a profiler call in the (untraced)
    function that merely DEFINES a jit root must both stay silent."""
    from distributed_llm_tpu.lint.checkers.obs_discipline import \
        ProfilerDisciplineChecker
    src = """
        import jax

        def tick(profiler, fn, x):
            with profiler.phase("decode"):    # host side, around the call
                return jax.jit(fn)(x)

        class Engine:
            def _decode_step(self):
                def run(params):
                    return params
                self.profiler.event("compile", stage="decode")  # host
                return jax.jit(run)
    """
    assert _lint(ProfilerDisciplineChecker(), {ENGINE: src}).findings == []


# -- suppression machinery ---------------------------------------------------

def test_suppression_with_justification_silences_finding():
    src = """
        def f(tr):
            sp = tr.span('x')  # dllm-lint: disable=span-not-with -- fixture: exit handled by the harness
            return sp
    """
    result = _lint(SpanDisciplineChecker(), {SERVING: src})
    assert result.findings == []
    assert len(result.suppressed) == 1


def test_suppression_without_justification_is_itself_a_finding():
    src = """
        def f(tr):
            sp = tr.span('x')  # dllm-lint: disable=span-not-with
            return sp
    """
    result = _lint(SpanDisciplineChecker(), {SERVING: src})
    rules = _rules(result)
    # The original finding survives AND the naked suppression is flagged.
    assert "span-not-with" in rules
    assert "suppression-missing-justification" in rules


def test_suppression_standalone_comment_covers_next_line():
    src = """
        def f(tr):
            # dllm-lint: disable=span-not-with -- fixture: next-line scope
            sp = tr.span('x')
            return sp
    """
    result = _lint(SpanDisciplineChecker(), {SERVING: src})
    assert result.findings == []
    assert len(result.suppressed) == 1


def test_suppression_file_scope():
    src = """
        # dllm-lint: disable-file=span-not-with -- fixture: whole-file opt-out
        def f(tr):
            a = tr.span('x')
            b = tr.span('y')
            return a, b
    """
    result = _lint(SpanDisciplineChecker(), {SERVING: src})
    assert result.findings == []
    assert len(result.suppressed) == 2


def test_suppression_wrong_rule_does_not_silence():
    src = """
        def f(tr):
            sp = tr.span('x')  # dllm-lint: disable=lock-blocking-call -- fixture: wrong rule id
            return sp
    """
    result = _lint(SpanDisciplineChecker(), {SERVING: src})
    assert _rules(result) == ["span-not-with"]


# -- whole-project call graph (ISSUE 8 tentpole) -----------------------------

def _psyms(files):
    from distributed_llm_tpu.lint.symbols import project_symbols
    return project_symbols(_project(files))


UTIL = "distributed_llm_tpu/engine/util.py"
CALLER = "distributed_llm_tpu/serving/caller.py"


def test_callgraph_resolves_from_import():
    ps = _psyms({
        UTIL: """
            def helper():
                pass
        """,
        CALLER: """
            from ..engine.util import helper

            def go():
                helper()
        """,
    })
    edges = ps.calls.get(f"{CALLER}:go", [])
    assert (f"{UTIL}:helper", "helper") in [(g, b) for g, b, _ in edges]


def test_callgraph_resolves_import_alias_and_dotted():
    ps = _psyms({
        UTIL: """
            def helper():
                pass
        """,
        CALLER: """
            import distributed_llm_tpu.engine.util as u
            import distributed_llm_tpu.engine.util

            def via_alias():
                u.helper()

            def via_dotted():
                distributed_llm_tpu.engine.util.helper()
        """,
    })
    for fn in ("via_alias", "via_dotted"):
        gids = [g for g, _, _ in ps.calls.get(f"{CALLER}:{fn}", [])]
        assert f"{UTIL}:helper" in gids, (fn, gids)


def test_callgraph_resolves_self_method_and_locals():
    ps = _psyms({
        CALLER: """
            class C:
                def outer(self):
                    def worker():
                        pass
                    self.inner()
                    worker()

                def inner(self):
                    pass
        """,
    })
    gids = [g for g, _, _ in ps.calls.get(f"{CALLER}:C.outer", [])]
    assert f"{CALLER}:C.inner" in gids
    assert f"{CALLER}:C.outer.<locals>.worker" in gids


def test_callgraph_follows_reexport_chain():
    """``from pkg import fn`` where pkg/__init__ re-exports fn from an
    impl module — the repo's models/__init__ idiom."""
    ps = _psyms({
        "distributed_llm_tpu/pkgx/__init__.py": """
            from .impl import fn
        """,
        "distributed_llm_tpu/pkgx/impl.py": """
            def fn():
                pass
        """,
        CALLER: """
            from ..pkgx import fn

            def go():
                fn()
        """,
    })
    gids = [g for g, _, _ in ps.calls.get(f"{CALLER}:go", [])]
    assert "distributed_llm_tpu/pkgx/impl.py:fn" in gids


def test_callgraph_name_collision_never_edges():
    """Two modules defining the same bare name must NOT edge without an
    import proving it — the PR 4 graph's documented blind spot was
    name-matching; the fix must not overcorrect into name-matching
    across files."""
    ps = _psyms({
        UTIL: """
            def build():
                pass
        """,
        CALLER: """
            def build():
                pass

            def go(obj):
                obj.build()      # a METHOD on some object: unknowable
        """,
    })
    gids = [g for g, b, _ in ps.calls.get(f"{CALLER}:go", [])
            if b == "build"]
    assert gids == [None]


def test_callgraph_conflicting_from_imports_poison_the_name():
    """Two from-imports binding the SAME local name to DIFFERENT
    targets (top-level + a lazy function-local import) must resolve to
    NEITHER: module-wide last-writer-wins would silently mis-edge every
    call site of the other import."""
    ps = _psyms({
        UTIL: """
            def load():
                pass
        """,
        "distributed_llm_tpu/engine/other.py": """
            def load():
                pass
        """,
        CALLER: """
            from ..engine.util import load

            def go():
                load()

            def lazy():
                from ..engine.other import load
                load()
        """,
    })
    for qual in ("go", "lazy"):
        gids = [g for g, b, _ in ps.calls.get(f"{CALLER}:{qual}", [])
                if b == "load"]
        assert gids == [None], (qual, gids)


def test_callgraph_resolves_thread_target_cross_module():
    ps = _psyms({
        UTIL: """
            def loop():
                pass
        """,
        CALLER: """
            import threading
            from ..engine.util import loop

            def spawn():
                threading.Thread(target=loop, daemon=True).start()
        """,
    })
    targets = ps.thread_target_gids()
    assert f"{UTIL}:loop" in targets
    assert targets[f"{UTIL}:loop"][0][0] == CALLER


def test_callgraph_resolves_callee_defined_later_in_file():
    """Regression: the PR 4 walker resolved calls DURING the AST walk,
    so a self-method call to a method defined later in the class (the
    _admit -> _admit_replay shape) silently never edged."""
    ps = _psyms({
        CALLER: """
            class C:
                def first(self):
                    self.second()

                def second(self):
                    pass
        """,
    })
    gids = [g for g, _, _ in ps.calls.get(f"{CALLER}:C.first", [])]
    assert f"{CALLER}:C.second" in gids


# -- cross-module lock regression (the PR 2 shape, split across files) -------

XMOD_MANAGER = """
    import threading
    from .builder import build_engine

    class Manager:
        def __init__(self):
            self._lock = threading.RLock()
            self._engine = None

        def health(self):
            with self._lock:
                if self._engine is None:
                    self._engine = build_engine()
                return {"ok": True}
"""

XMOD_BUILDER = """
    def build_engine():
        engine = object()
        engine.warmup()              # compiles for minutes on chip
        return engine
"""


def test_lock_checker_catches_pr2_shape_across_modules():
    """ISSUE 8 acceptance: the lock-held-through-compile shape with the
    blocking callee in ANOTHER FILE is now caught."""
    result = _lint(LockChecker(), {
        "distributed_llm_tpu/engine/xmanager.py":
            textwrap.dedent(XMOD_MANAGER),
        "distributed_llm_tpu/engine/builder.py":
            textwrap.dedent(XMOD_BUILDER)})
    blocking = [f for f in result.findings
                if f.rule == "lock-blocking-call"]
    assert len(blocking) == 1, result.findings
    assert "transitively" in blocking[0].message
    assert "warmup" in blocking[0].message
    assert "builder.build_engine" in blocking[0].message


def test_lock_checker_old_module_local_graph_was_a_miss():
    """The same fixture with ONLY the manager module loaded produces no
    finding: module-local resolution cannot see the callee — which is
    exactly what the PR 4 (module-local) graph did even with both files
    loaded.  This pins that the cross-module catch comes from the
    import-resolved edge, not from bare-name matching."""
    result = _lint(LockChecker(), {
        "distributed_llm_tpu/engine/xmanager.py":
            textwrap.dedent(XMOD_MANAGER)})
    assert result.findings == []


# -- retrace checker ---------------------------------------------------------

def test_retrace_wrap_in_loop_flagged_and_warm_call_clean():
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        import jax

        def serve(batches):
            for b in batches:
                fn = jax.jit(lambda x: x + 1)    # fresh trace per batch
                fn(b)
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert "retrace-wrap-in-loop" in _rules(result)

    good = """
        import jax

        fn = jax.jit(lambda x: x + 1)

        def serve(batches):
            for b in batches:
                fn(b)                 # calling the wrapped fn: warm path
    """
    assert _lint(RetraceChecker(), {ENGINE: good}).findings == []


def test_retrace_per_call_wrap_on_hot_path_flagged():
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        from functools import partial

        import jax

        def step(x, k):
            return x + k

        def handle(q):    # dllm-lint: hot-path
            return jax.jit(partial(step, k=2))(q)   # re-traced per request
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert _rules(result) == ["retrace-per-call-wrap"], result.findings


def test_retrace_per_call_wrap_inside_traced_code_clean():
    """pallas_call/jit rebuilt INSIDE traced code traces once per outer
    compile — the ops-module idiom must stay silent even when the
    function is also hot-path-reachable (project-wide traced closure
    wins)."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    src = """
        from functools import partial

        import jax
        from jax.experimental import pallas as pl

        def _k(q_ref, o_ref, *, bs):
            o_ref[0] = q_ref[0]

        def op(x):
            return pl.pallas_call(partial(_k, bs=4), grid=(1,))(x)

        def run(x):
            return op(x)

        f = jax.jit(run)

        def handle(q):    # dllm-lint: hot-path
            return run(q)
    """
    assert _lint(RetraceChecker(), {ENGINE: src}).findings == []


def test_retrace_dynamic_shape_upload_flagged_and_full_clean():
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        import jax.numpy as jnp

        def tick(self, wb):
            return jnp.asarray(self._tables[:, :wb])   # shape varies
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert _rules(result) == ["retrace-dynamic-shape"]

    good = """
        import jax.numpy as jnp

        def tick(self):
            full = jnp.asarray(self._tables)        # shape-stable
            head = jnp.asarray(self._tables[:, :8])  # constant bound
            return full, head
    """
    assert _lint(RetraceChecker(), {ENGINE: good}).findings == []


def test_retrace_shape_derived_scalar_without_static_argnums():
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        import jax

        def _run(x, width):
            return x

        fn = jax.jit(_run)

        def serve(x, tokens):
            return fn(x, len(tokens))
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert _rules(result) == ["retrace-dynamic-shape"], result.findings
    assert "static_argnums" in result.findings[0].message

    good = bad.replace("fn = jax.jit(_run)",
                       "fn = jax.jit(_run, static_argnums=(1,))")
    assert _lint(RetraceChecker(), {ENGINE: good}).findings == []


def test_retrace_shape_cache_key_flagged_and_slice_clean():
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        _cache = {}

        def get(x):
            return _cache[f"prog-{x.shape}"]
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert _rules(result) == ["retrace-shape-cache-key"]

    good = """
        def get(x, q):
            window = x[:, : q.shape[1]]      # slicing TO a bound: fine
            msg = f"shapes {x.shape}"        # logging: fine
            return window, msg
    """
    assert _lint(RetraceChecker(), {ENGINE: good}).findings == []


def test_retrace_tp_program_family_bounded_key_clean():
    """ISSUE 16's per-shard program family — compiled fns cached under
    the bounded ``(γ_bucket, pool span, tp)`` tuple and filled once per
    key outside any loop — is the sanctioned keyed-cache shape: every
    component is a bucketed/config int, not an array ``.shape``, so the
    retrace checker must stay silent even with a hot-path caller (the
    ``.shape``-keyed BAD twin is covered above)."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    src = """
        import jax

        _FAMILY = {}

        def _bucket(n, ladder=(4, 8)):
            return min(g for g in ladder if g >= n)

        def _verify_fn(gb, span, tp):
            key = (gb, span, tp)       # bounded bucket tuple, not .shape
            if key not in _FAMILY:
                def step(q, pool):
                    return q + pool
                _FAMILY[key] = jax.jit(step)
            return _FAMILY[key]

        def handle(q, pool, gamma, span, tp):    # dllm-lint: hot-path
            gb = _bucket(gamma)
            return _verify_fn(gb, span, tp)(q, pool)
    """
    assert _lint(RetraceChecker(), {ENGINE: src}).findings == []


def test_retrace_shape_scalar_index_is_not_a_cache_key():
    """``tables[q.shape[0]]`` is ordinary array indexing — a shape
    INDEXED down to a scalar must not read as a mapping key (mappings
    and arrays are statically indistinguishable; only the
    unambiguously-mapping-shaped keys fire)."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    good = """
        def gather(tables, q, buf):
            row = tables[q.shape[0]]
            last = buf[q.shape[1] - 1]
            return row, last
    """
    assert _lint(RetraceChecker(), {ENGINE: good}).findings == []

    # But the shape used AS a value in a tuple key still fires.
    bad = """
        def get(cache, x):
            return cache[(x.shape, x.dtype)]
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert _rules(result) == ["retrace-shape-cache-key"], result.findings


def test_retrace_warmup_exempt():
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    src = """
        import jax.numpy as jnp

        def warmup(self):
            for wb in self._buckets:
                arr = jnp.asarray(self._tables[:, :wb])   # warmup's JOB
    """
    assert _lint(RetraceChecker(), {ENGINE: src}).findings == []


def test_retrace_chunk_program_family_bounded_keys_clean():
    """The chunked-prefill idiom (ISSUE 9): a program cache keyed by
    bounded (bucket/chunk, window) INTS, a fixed-chunk staging buffer
    padded to the chunk size, and a loop calling the already-built
    wrapped function — the engine's `_chunk_prefill_fn` /
    `_advance_prefill` shape must stay silent, or the checker would be
    flagging the design it exists to protect."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    src = """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def chunk_fn(self, chunk, window):
            key = ("chunk", chunk, window)     # bounded rung key, not a shape
            if key not in self._fns:
                self._fns[key] = jax.jit(self._run)
            return self._fns[key]

        def advance(self, pf):    # dllm-lint: hot-path
            c = self.chunk_tokens
            while pf.consumed < pf.total:
                k = min(pf.consumed + c, pf.total) - pf.consumed
                tokens = np.full((1, c), self.pad_id, np.int32)  # padded
                tokens[0, :k] = pf.seq[pf.consumed:pf.consumed + k]
                fn = self.chunk_fn(c, self.window)
                fn(self.params, jnp.asarray(tokens))   # warm wrapped call
                pf.consumed += k
    """
    assert _lint(RetraceChecker(), {ENGINE: src}).findings == []


def test_retrace_chunk_per_prompt_length_shapes_flagged():
    """The naive chunked prefill this PR must NOT ship: uploading each
    chunk at the prompt's own residual length mints one compiled program
    per distinct prompt length — unbounded churn on the admit path."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        import jax.numpy as jnp

        def advance(self, pf):
            while pf.consumed < pf.total:
                end = min(pf.consumed + self.chunk_tokens, pf.total)
                tokens = jnp.asarray(pf.seq[pf.consumed:end])  # per-length
                self._fn(self.params, tokens)
                pf.consumed = end
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert "retrace-dynamic-shape" in _rules(result), result.findings

    keyed = """
        def chunk_fn(self, tokens, window):
            return self._fns[(tokens.shape, window)]   # one program/shape
    """
    result = _lint(RetraceChecker(), {ENGINE: keyed})
    assert _rules(result) == ["retrace-shape-cache-key"], result.findings


def test_retrace_spec_verify_family_bounded_keys_clean():
    """The batched-speculation idiom (ISSUE 15): draft/verify program
    caches keyed by the bounded (γ_bucket, pool-span) INTS — per-slot γ
    and acceptance lengths are runtime operands — plus the scheduler
    loop calling the already-built wrapped functions.  The shipped
    ``_spec_draft_fn``/``_spec_verify_fn``/``_spec_plan`` shape must
    stay silent."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    src = """
        import jax
        import jax.numpy as jnp

        def verify_fn(self, gb):
            key = ("spec_verify", gb)      # bounded γ-bucket key
            if key not in self._fns:
                self._fns[key] = jax.jit(self._run_verify)
            return self._fns[key]

        def spec_round(self, active, gb):    # dllm-lint: hot-path
            while active:
                out, n_acc, self.pool = self.verify_fn(gb)(
                    self.params, self.pool, self.tables,
                    jnp.asarray(self._pos), jnp.asarray(self._cur),
                    self.drafted, jnp.asarray(self.gammas),
                    jnp.asarray(self._temps), self.rng)
                active = self.emit(out, n_acc)
    """
    assert _lint(RetraceChecker(), {ENGINE: src}).findings == []


def test_retrace_spec_per_acceptance_length_wrap_flagged():
    """The naive speculative tick this PR must NOT ship: wrapping (or
    keying) the verify per observed acceptance length re-traces on the
    hot path once per distinct n_acc — acceptance is data, not a
    program key."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        from functools import partial

        import jax

        def _verify(params, pool, chunk, *, n_acc):
            return params, pool

        def spec_round(self, n_acc):    # dllm-lint: hot-path
            # fresh trace per acceptance length — unbounded churn
            return jax.jit(partial(_verify, n_acc=n_acc))(
                self.params, self.pool, self.chunk)
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert "retrace-per-call-wrap" in _rules(result), result.findings

    keyed = """
        def verify_fn(self, drafted):
            return self._fns[drafted.shape]   # one program per γ observed
    """
    result = _lint(RetraceChecker(), {ENGINE: keyed})
    assert _rules(result) == ["retrace-shape-cache-key"], result.findings


def test_retrace_cow_copy_per_admission_wrap_flagged():
    """The COW boundary copy this PR must NOT ship (ISSUE 10): wrapping
    the one-block copy per admission re-traces on the admit path — the
    copy must ride the cached block-write program family (block ids are
    traced scalars, ONE program for every (src, dst) pair)."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    bad = """
        from functools import partial

        import jax

        def _copy(pool, *, src, dst):
            return pool["k"].at[:, :, dst].set(pool["k"][:, :, src])

        def admit(self, pool, src, dst):    # dllm-lint: hot-path
            # fresh trace per (src, dst) pair — unbounded program churn
            return jax.jit(partial(_copy, src=src, dst=dst))(pool)
    """
    result = _lint(RetraceChecker(), {ENGINE: bad})
    assert "retrace-per-call-wrap" in _rules(result), result.findings


def test_retrace_cow_copy_cached_block_write_family_clean():
    """Near-miss: the shipped idiom — copy_block jitted ONCE into a
    cached program (src/dst are traced scalar ARGS, not closure
    constants), reused by every shared-hit admission — must stay
    silent like the prefill writers it rides next to."""
    from distributed_llm_tpu.lint.checkers.retrace import RetraceChecker
    src = """
        import jax
        import jax.numpy as jnp

        def copy_block(pool, src, dst):
            return pool["k"].at[:, :, dst].set(pool["k"][:, :, src])

        def cow_fn(self):
            if self._cow_fn is None:
                self._cow_fn = jax.jit(copy_block)   # minted once
            return self._cow_fn

        def admit(self, pool, src, dst):    # dllm-lint: hot-path
            return self.cow_fn()(pool, jnp.asarray(src, jnp.int32),
                                 jnp.asarray(dst, jnp.int32))
    """
    assert _lint(RetraceChecker(), {ENGINE: src}).findings == []


# -- transfer checker --------------------------------------------------------

def test_transfer_sync_in_cross_module_hot_callee_flagged():
    """The headline shape: the hot-path root is in one module, the sync
    hides in a helper in ANOTHER — only the project-wide closure sees
    it."""
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    files = {
        ENGINE: """
            from ..serving.helper import pull

            def tick(self):    # dllm-lint: hot-path
                while True:
                    pull(self.buf)
        """,
        "distributed_llm_tpu/serving/helper.py": """
            import jax

            def pull(buf):
                return jax.block_until_ready(buf)
        """,
    }
    result = _lint(TransferChecker(), files)
    assert _rules(result) == ["transfer-host-sync"], result.findings
    assert result.findings[0].path == "distributed_llm_tpu/serving/helper.py"


def test_transfer_sync_outside_hot_path_and_warmup_clean():
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    src = """
        import jax

        def generate(self, q):          # not hot-path-annotated
            out = self._fn(q)
            return jax.block_until_ready(out)

        def tick(self):    # dllm-lint: hot-path
            self.warmup_programs()

        def warmup_programs(self):      # warmup-named: exempt
            jax.block_until_ready(self._fn(0))
    """
    assert _lint(TransferChecker(), {ENGINE: src}).findings == []


def test_transfer_item_and_round_trip_flagged():
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    src = """
        import jax.numpy as jnp
        import numpy as np

        def tick(self):    # dllm-lint: hot-path
            x = self.state.item()                # device pull per call
            y = np.asarray(jnp.dot(self.a, self.b))   # implicit pull
            z = int(toks[0])                     # host indexing: fine
            return x, y, z
    """
    result = _lint(TransferChecker(), {ENGINE: src})
    assert sorted(_rules(result)) == ["transfer-host-round-trip",
                                      "transfer-host-sync"]


def test_transfer_sync_inside_lambda_on_hot_path_flagged():
    """A lambda is not a call-graph entry and cannot carry its own
    hot-path annotation, so its body scans as part of the enclosing hot
    function — a per-tick sync must not hide in one."""
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    src = """
        import jax

        def tick(self):    # dllm-lint: hot-path
            pull = lambda v: int(jax.device_get(v))
            return pull(self.state)
    """
    result = _lint(TransferChecker(), {ENGINE: src})
    assert "transfer-host-sync" in _rules(result), result.findings


def test_transfer_sync_spill_pool_pull_on_scheduler_loop_flagged():
    """The ISSUE 14 rule: a synchronous host copy of POOL data reachable
    from the scheduler `_loop` hot path — the spill copier worker is the
    only sanctioned device→host crossing for pool blocks.  Both the
    explicit-sync and the np-pull shapes classify as the SPECIFIC rule
    (never the generic transfer-host-sync), so the finding names the
    sanctioned alternative."""
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    src = """
        import jax
        import numpy as np

        class Engine:
            def _loop(self):    # dllm-lint: hot-path
                while True:
                    self._demote()

            def _demote(self):
                host = jax.device_get(self.pool["k"][:, :, self.victim])
                spare = np.asarray(self.pool["v"][:, :, self.victim])
                self.store.append((host, spare))
    """
    result = _lint(TransferChecker(), {ENGINE: src})
    assert _rules(result) == ["transfer-sync-spill",
                              "transfer-sync-spill"], result.findings
    assert "copier" in result.findings[0].message


def test_transfer_sync_spill_near_miss_copier_worker_clean():
    """Near-miss: the SANCTIONED shape — the scheduler issues the async
    gather snapshot (no sync) and the device→host pull lives on the
    copier worker, a thread target outside the hot-path closure.  Must
    stay silent, and so must the existing sanctioned non-pool syncs
    (first-token block_until_ready under its justification)."""
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    src = """
        import jax
        import jax.numpy as jnp

        def _loop(self):    # dllm-lint: hot-path
            while True:
                tiles = self._gather(self.pool, self.victim)  # async snap
                self.jobs.put(tiles)

        def _copier_loop(self):
            while True:
                tiles = self.jobs.get()
                self.store.append(jax.device_get(tiles))
    """
    assert _lint(TransferChecker(), {ENGINE: src}).findings == []


def test_transfer_undonated_buffer_flagged_and_donated_clean():
    from distributed_llm_tpu.lint.checkers.transfer import TransferChecker
    bad = """
        import jax

        def _step(params, pool, tok):
            pool = pool + 1
            return tok, pool

        fn = jax.jit(_step)
    """
    result = _lint(TransferChecker(), {ENGINE: bad})
    assert _rules(result) == ["transfer-undonated-buffer"], result.findings
    assert "pool" in result.findings[0].message

    good = bad.replace("fn = jax.jit(_step)",
                       "fn = jax.jit(_step, donate_argnums=(1,))")
    assert _lint(TransferChecker(), {ENGINE: good}).findings == []


# -- thread_lifecycle checker ------------------------------------------------

def test_thread_no_reclaim_flagged_daemon_and_joined_clean():
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    bad = """
        import threading

        def spawn():
            t = threading.Thread(target=work)
            t.start()                       # never joined, not daemon

        def work():
            pass
    """
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"]

    daemon = bad.replace("threading.Thread(target=work)",
                         "threading.Thread(target=work, daemon=True)")
    assert _lint(ThreadLifecycleChecker(), {SERVING: daemon}).findings == []

    joined = bad.replace("t.start()                       "
                         "# never joined, not daemon",
                         "t.start()\n            t.join()")
    assert _lint(ThreadLifecycleChecker(), {SERVING: joined}).findings == []


def test_thread_string_join_does_not_reclaim():
    """``", ".join(names)`` is the formatting idiom, not a thread join —
    it must not silence thread-no-reclaim for an unrelated Thread in the
    same function (only thread-shaped joins count: no args, or a
    timeout)."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    bad = """
        import threading

        def spawn(names):
            label = ", ".join(names)
            t = threading.Thread(target=work, name=label)
            t.start()

        def work():
            pass
    """
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"], result.findings

    joined = bad.replace("t.start()", "t.start()\n            t.join(2.0)")
    assert _lint(ThreadLifecycleChecker(), {SERVING: joined}).findings == []


def test_thread_join_must_name_its_thread():
    """Joining worker A must not silence a never-joined worker B in the
    same function — the join is matched to the thread's own binding."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    bad = """
        import threading

        def spawn():
            a = threading.Thread(target=work)
            b = threading.Thread(target=work)
            a.start()
            b.start()
            a.join()                    # b is never joined

        def work():
            pass
    """
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"], result.findings

    both = bad.replace("a.join()                    # b is never joined",
                       "a.join()\n            b.join()")
    assert _lint(ThreadLifecycleChecker(), {SERVING: both}).findings == []


def test_thread_loop_variable_join_reclaims_fanout():
    """The bench fan-out idiom: threads collected in a list, joined
    through a loop variable — an alias no spawn is bound to counts as
    reclamation (the binding is untraceable, edge-only-when-proven cuts
    the other way for reclaim credit)."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    good = """
        import threading

        def fan_out(n):
            workers = []
            for _ in range(n):
                t = threading.Thread(target=work)
                t.start()
                workers.append(t)
            for th in workers:
                th.join(5.0)

        def work():
            pass
    """
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []


def test_thread_reclaim_requires_stop_reachable_join():
    """A join parked in a method NO stop/drain path calls does not
    reclaim the thread — nothing runs it at shutdown."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    good = """
        import threading

        class W:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass

            def stop(self):
                self._t.join(timeout=2)
    """
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []

    bad = good.replace("def stop(self):", "def refresh(self):")
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"]


def test_thread_worker_pool_join_loop_reclaims(  # ISSUE 12 satellite
):
    """Per-replica worker POOLS: threads appended to a ``self.X`` list
    and joined through a ``for t in self.X: t.join()`` loop in a
    stop/drain-family method are reclaimed — and a leaked pool (drain
    joins a DIFFERENT pool, or no stop path joins it at all) is
    caught."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    good = """
        import threading

        class ReplicaSet:
            def __init__(self):
                self._workers = []

            def start(self, n):
                for _ in range(n):
                    t = threading.Thread(target=self._run)
                    t.start()
                    self._workers.append(t)

            def _run(self):
                pass

            def drain(self):
                for t in self._workers:
                    t.join(timeout=5)
    """
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []

    # The list()-wrapper form of the drain loop reclaims too.
    wrapped = good.replace("for t in self._workers:",
                           "for t in list(self._workers):")
    assert _lint(ThreadLifecycleChecker(),
                 {SERVING: wrapped}).findings == []

    # Drain joins a DIFFERENT pool: the replica workers leak.
    bad = good.replace("for t in self._workers:\n                    "
                       "t.join(timeout=5)",
                       "for t in self._others:\n                    "
                       "t.join(timeout=5)")
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"], result.findings

    # The join loop exists but in a method no stop path reaches.
    unreached = good.replace("def drain(self):", "def rebalance(self):")
    result = _lint(ThreadLifecycleChecker(), {SERVING: unreached})
    assert _rules(result) == ["thread-no-reclaim"], result.findings


def test_thread_worker_pool_direct_append_reclaims():
    """``self.X.append(threading.Thread(...))`` with no binding still
    resolves to the pool for stop-family reclamation."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    good = """
        import threading

        class Pool:
            def __init__(self):
                self._threads = []

            def start(self):
                self._threads.append(threading.Thread(target=self._run))
                self._threads[-1].start()

            def _run(self):
                pass

            def stop(self):
                for t in self._threads:
                    t.join()
    """
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []

    bad = good.replace("            def stop(self):\n"
                       "                for t in self._threads:\n"
                       "                    t.join()",
                       "")
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"], result.findings


def test_thread_autoscaler_controller_reclaim_and_leak():
    """ISSUE 18 fixture pair: the elastic-capacity controller shape
    (serving/autoscaler.py) — a periodic control-loop thread spawned in
    start().  The shipped lifecycle (stop() sets the event and joins
    bounded) must stay clean; the near-miss where the join is parked in
    a non-stop-family method (``rebalance``) leaks the controller on
    router drain and must be flagged."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    good = """
        import threading

        class ReplicaAutoscaler:
            def __init__(self):
                self._stop = threading.Event()

            def start(self):
                self._thread = threading.Thread(target=self._loop)
                self._thread.start()

            def _loop(self):
                while not self._stop.wait(0.5):
                    pass

            def stop(self):
                self._stop.set()
                self._thread.join(timeout=5)
    """
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []

    # Near-miss: the SAME join exists, but only reachable through a
    # method outside the stop family — drain never runs it.
    bad = good.replace("def stop(self):", "def rebalance(self):")
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-no-reclaim"], result.findings


def test_thread_acquire_leak_flagged_and_finally_clean():
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    bad = """
        import threading

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def step(self):
                self._lock.acquire()
                do_work()                # raises -> lock held forever
                self._lock.release()
    """
    result = _lint(ThreadLifecycleChecker(), {ENGINE: bad})
    assert _rules(result) == ["thread-acquire-leak"]

    good = """
        import threading

        class M:
            def __init__(self):
                self._lock = threading.Lock()

            def step(self):
                self._lock.acquire()
                try:
                    do_work()
                finally:
                    self._lock.release()
    """
    assert _lint(ThreadLifecycleChecker(), {ENGINE: good}).findings == []


def test_thread_ring_no_stop_flagged_and_drained_clean():
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    bad = """
        import threading

        class Recorder:
            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                pass

        RECORDER = Recorder()       # module-scope, no stop hook at all
    """
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-ring-no-stop"]
    assert "no stop/close/shutdown hook" in result.findings[0].message

    good = """
        import threading

        class Recorder:
            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                pass

            def stop(self):
                pass

        RECORDER = Recorder()

        def drain_all():
            RECORDER.stop()
    """
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []

    orphan = good.replace("def drain_all():", "def refresh_all():")
    result = _lint(ThreadLifecycleChecker(), {SERVING: orphan})
    assert _rules(result) == ["thread-ring-no-stop"]
    assert "never called" in result.findings[0].message


def test_thread_ring_hook_match_requires_instance_receiver():
    """An unrelated ``fh.close()`` in a drain path must not mark a
    never-stopped recorder reclaimed — the hook call's receiver has to
    name the module-scope instance."""
    from distributed_llm_tpu.lint.checkers.thread_lifecycle import \
        ThreadLifecycleChecker
    bad = """
        import threading

        class Recorder:
            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                pass

            def close(self):
                pass

        RECORDER = Recorder()

        def drain_all(fh):
            fh.close()                  # a file handle, not the ring
    """
    result = _lint(ThreadLifecycleChecker(), {SERVING: bad})
    assert _rules(result) == ["thread-ring-no-stop"], result.findings

    good = bad.replace("fh.close()                  # a file handle, "
                       "not the ring",
                       "RECORDER.close()")
    assert _lint(ThreadLifecycleChecker(), {SERVING: good}).findings == []


# -- --changed reporting filter ----------------------------------------------

def test_filter_changed_keeps_whole_project_findings():
    from distributed_llm_tpu.lint.core import Finding, LintResult, \
        filter_changed

    class _Narrow:
        whole_project = False
        rules = ("span-not-with",)

    class _Wide:
        whole_project = True
        rules = ("lock-blocking-call",)

    result = LintResult(findings=[
        Finding("span-not-with", "a.py", 1, "in changed file"),
        Finding("span-not-with", "b.py", 1, "in unchanged file"),
        Finding("lock-blocking-call", "b.py", 2, "whole-project rule"),
    ], suppressed=[])
    out = filter_changed(result, ["a.py"], [_Narrow(), _Wide()])
    got = [(f.rule, f.path) for f in out.findings]
    assert got == [("span-not-with", "a.py"),
                   ("lock-blocking-call", "b.py")]


def test_filter_changed_never_drops_parse_or_suppression_findings():
    """A syntax error (or naked suppression) in an UNCHANGED file blinds
    every whole-project analysis to that module — --changed must surface
    it, not report a green the graph checkers cannot back."""
    from distributed_llm_tpu.lint.core import (Finding, JUSTIFICATION_RULE,
                                               LintResult, PARSE_RULE,
                                               filter_changed)
    result = LintResult(findings=[
        Finding(PARSE_RULE, "unchanged.py", 1, "syntax error"),
        Finding(JUSTIFICATION_RULE, "unchanged.py", 2, "naked suppression"),
    ], suppressed=[])
    out = filter_changed(result, ["a.py"], [])
    assert [(f.rule, f.path) for f in out.findings] == [
        (PARSE_RULE, "unchanged.py"),
        (JUSTIFICATION_RULE, "unchanged.py")]


def test_config_drift_widens_under_changed_mode():
    """config-env-stale lands in the UNCHANGED registry file when an
    edit elsewhere deletes a knob's last reader — config_drift must be
    whole_project so --changed cannot drop it."""
    from distributed_llm_tpu.lint.checkers.config_drift import \
        ConfigDriftChecker
    assert ConfigDriftChecker.whole_project is True


def test_changed_mode_survives_unusable_git(monkeypatch):
    """No git binary / hung git falls back to a full-project run (None),
    not a traceback."""
    import subprocess as sp
    from distributed_llm_tpu.lint.__main__ import _git_changed_files

    def boom(*a, **k):
        raise FileNotFoundError("git")
    monkeypatch.setattr(sp, "run", boom)
    assert _git_changed_files("/", "HEAD") is None


def test_hot_path_annotation_parsed_on_def_and_line_above():
    src = textwrap.dedent("""
        def a():    # dllm-lint: hot-path
            pass

        # dllm-lint: hot-path
        def b():
            pass
    """)
    from distributed_llm_tpu.lint.symbols import (hot_path_roots,
                                                  project_symbols)
    project = _project({ENGINE: src}, dedent=False)
    roots = hot_path_roots(project_symbols(project))
    assert roots == {f"{ENGINE}:a", f"{ENGINE}:b"}


# -- perf: one parse, one graph, bounded wall clock --------------------------

def test_full_repo_lint_wall_clock_under_15s():
    """CI ergonomics pin (ISSUE 8, bound raised for ISSUE 19): all
    twelve checkers over the whole repo — shared ASTs, one
    ProjectSymbols build, per-function CFGs for the ownership dataflow
    — stay well inside the tier-1 budget."""
    t0 = time.perf_counter()
    run_lint()
    elapsed = time.perf_counter() - t0
    assert elapsed < 15.0, f"full-repo lint took {elapsed:.1f}s"


def test_project_symbols_built_once_per_project():
    from distributed_llm_tpu.lint import load_project
    from distributed_llm_tpu.lint.symbols import project_symbols
    project = load_project(repo_root())
    ps1 = project_symbols(project)
    ps2 = project_symbols(project)
    assert ps1 is ps2


# -- the tier-1 pin: the repo lints clean ------------------------------------

def test_repo_lints_clean():
    """Acceptance: `python -m distributed_llm_tpu.lint` exits 0 — zero
    unsuppressed findings over the whole project, with every suppression
    carrying a justification (naked ones surface as findings here)."""
    result = run_lint()
    assert result.findings == [], "\n".join(
        f.render() for f in result.findings)


def test_repo_suppressions_all_reference_real_rules():
    """Every suppression in the repo names a rule some checker owns —
    a typo'd rule id would silently suppress nothing."""
    known = {r for c in all_checkers() for r in c.rules}
    from distributed_llm_tpu.lint import load_project
    project = load_project(repo_root())
    for rel, mod in project.modules.items():
        for rules in mod.suppressions.by_line.values():
            assert rules <= known, (rel, rules)
        assert mod.suppressions.file_level <= known, rel


@pytest.fixture(scope="module")
def default_modules():
    from distributed_llm_tpu.lint import load_project
    return load_project(repo_root()).modules


@pytest.mark.parametrize("checker", all_checkers(),
                         ids=lambda c: c.name)
def test_every_checker_scope_names_something_the_run_parses(
        checker, default_modules):
    """A scope entry is a path prefix; one that matches no module of the
    default project (a file renamed or deleted since) examines nothing
    and reports nothing, forever."""
    modules = default_modules
    for prefix in checker.scope:
        assert any(rel == prefix or rel.startswith(prefix.rstrip("/") + "/")
                   for rel in modules), (checker.name, prefix)


def test_every_registered_env_var_names_a_consumer_that_exists():
    """The ``consumer`` column of CONFIG.md points at files of the
    package (or of the repo's root): a row whose reader was deleted must
    go with it, not keep naming it."""
    root = repo_root()
    for name, entry in ENV_VARS.items():
        for consumer in entry.consumer.split(","):
            consumer = consumer.strip()
            assert any(os.path.isfile(os.path.join(root, base, consumer))
                       for base in ("distributed_llm_tpu", "")), (
                name, consumer)


# -- ownership & lifecycle dataflow (ISSUE 19 tentpole) ----------------------
#
# Each own-* rule gets a known-bad fixture it MUST flag and a near-miss
# twin it must NOT — the near-miss is always the bad shape plus exactly
# the unwind handler the rule is asking for, so a precision regression
# (flagging correctly-guarded code) fails here before it floods the
# repo pin with suppressions.


def _own(files):
    return _lint(OwnershipChecker(), files)


OWN_LEAK_BAD = """
    class Engine:
        def admit(self, n):
            blocks = self.allocator.alloc(n)
            if blocks is None:
                return None
            self.wake_scheduler()        # can raise: blocks leak
            self.table = blocks
"""

OWN_LEAK_GUARDED = """
    class Engine:
        def admit(self, n):
            blocks = self.allocator.alloc(n)
            if blocks is None:
                return None
            try:
                self.wake_scheduler()
            except BaseException:
                self.allocator.free(blocks)
                raise
            self.table = blocks
"""


def test_ownership_flags_leak_on_exception_path():
    result = _own({ENGINE: OWN_LEAK_BAD})
    assert _rules(result) == ["own-leak-on-path"], result.findings


def test_ownership_silent_when_unwind_handler_frees():
    assert _own({ENGINE: OWN_LEAK_GUARDED}).findings == []


OWN_DOUBLE_BAD = """
    class Engine:
        def churn(self, n):
            blocks = self.allocator.alloc(n)
            if blocks is None:
                return
            self.allocator.free(blocks)
            self.allocator.free(blocks)
"""

OWN_DOUBLE_DIAMOND = """
    class Engine:
        def churn(self, n, fast):
            blocks = self.allocator.alloc(n)
            if blocks is None:
                return
            if fast:
                self.allocator.free(blocks)
            else:
                self.allocator.free(blocks)
"""


def test_ownership_flags_double_release():
    result = _own({ENGINE: OWN_DOUBLE_BAD})
    assert _rules(result) == ["own-double-release"], result.findings


def test_ownership_silent_on_disjoint_branch_releases():
    """May-set gating: one free per path through a diamond is NOT a
    double release — the two frees can never both execute."""
    assert _own({ENGINE: OWN_DOUBLE_DIAMOND}).findings == []


OWN_UAT_BAD = """
    class Engine:
        def park(self, ids, n):
            blocks = self.allocator.alloc(n)
            if blocks is None:
                return
            self.prefix_cache.put(ids, blocks)
            self.allocator.free(blocks)
"""

OWN_UAT_NEAR = """
    class Engine:
        def park(self, ids, n):
            blocks = self.allocator.alloc(n)
            if blocks is None:
                return
            self.prefix_cache.put(ids, blocks)
            used = len(blocks)
"""


def test_ownership_flags_release_after_transfer():
    """put() hands the refcount to the prefix cache — a free after the
    transfer drops a reference the function no longer owns."""
    result = _own({ENGINE: OWN_UAT_BAD})
    assert _rules(result) == ["own-use-after-transfer"], result.findings


def test_ownership_silent_on_non_retaining_read_after_transfer():
    assert _own({ENGINE: OWN_UAT_NEAR}).findings == []


OWN_PIN_BAD = """
    class Engine:
        def lookup(self, ids):
            entry = self.prefix_cache.take(ids)
            if entry is None:
                return None
            self.touch()                 # can raise: pin leaks
            self.prefix_cache.untake(entry, 1)
"""

OWN_PIN_GUARDED = """
    class Engine:
        def lookup(self, ids):
            entry = self.prefix_cache.take(ids)
            if entry is None:
                return None
            try:
                self.touch()
            except BaseException:
                self.prefix_cache.untake(entry, 1)
                raise
            self.prefix_cache.untake(entry, 1)
"""


def test_ownership_flags_pin_without_unpin_on_exception():
    result = _own({ENGINE: OWN_PIN_BAD})
    assert _rules(result) == ["own-pin-no-unpin"], result.findings


def test_ownership_silent_when_unwind_handler_unpins():
    assert _own({ENGINE: OWN_PIN_GUARDED}).findings == []


# The seeded acceptance fixtures: the exact replicas.py scale-up shape
# this PR fixed (standby handle popped, a raise before the membership
# append leaks a live server), and its guarded twin.

REPLICA_LEAK_BAD = """
    class Tier:
        def scale_up_one(self, summary):
            r = self._standby.pop(0)
            self.breaker.ensure(r.name)
            self._members.append(r)
            summary["added"].append(r.name)
"""

REPLICA_LEAK_GUARDED = """
    class Tier:
        def scale_up_one(self, summary):
            r = self._standby.pop(0)
            try:
                self.breaker.ensure(r.name)
            except BaseException:
                r.mgr.stop_server()
                raise
            self._members.append(r)
            summary["added"].append(r.name)
"""


def test_ownership_flags_standby_pop_leak_before_membership_append():
    result = _own({SERVING: REPLICA_LEAK_BAD})
    assert _rules(result) == ["own-leak-on-path"], result.findings


def test_ownership_silent_when_standby_unwind_stops_server():
    assert _own({SERVING: REPLICA_LEAK_GUARDED}).findings == []


# ISSUE 20 rescue-capture protocol: a capture_requests() result is the
# victim's in-flight work (callers blocked on done.wait()) and must
# reach exactly one home — adopted by a sibling (transfer) or failed
# with the engine-stopped shape (release).

RESCUE_UAT_BAD = """
    class Tier:
        def rescue(self, victim, sibling):
            captured = victim.capture_requests()
            sibling.adopt_requests(captured)
            fail_captured(captured, self.name)
"""

RESCUE_UAT_NEAR = """
    class Tier:
        def rescue(self, victim, sibling):
            captured = victim.capture_requests()
            sibling.adopt_requests(captured)
            rescued = len(captured)
"""

RESCUE_LEAK_BAD = """
    class Tier:
        def rescue(self, victim, sibling):
            captured = victim.capture_requests()
            victim.mgr.start_server()    # can raise: captures strand
            sibling.adopt_requests(captured)
"""

RESCUE_LEAK_GUARDED = """
    class Tier:
        def rescue(self, victim, sibling):
            captured = victim.capture_requests()
            try:
                victim.mgr.start_server()
            except BaseException:
                fail_captured(captured, self.name)
                raise
            sibling.adopt_requests(captured)
"""


def test_ownership_flags_release_after_rescue_adoption():
    """adopt_requests() hands the captured requests to the sibling's
    queue — failing them afterwards would complete streams another
    engine is actively decoding."""
    result = _own({SERVING: RESCUE_UAT_BAD})
    assert _rules(result) == ["own-use-after-transfer"], result.findings


def test_ownership_silent_on_rescue_count_after_adoption():
    assert _own({SERVING: RESCUE_UAT_NEAR}).findings == []


def test_ownership_flags_captured_requests_leak_on_restart_raise():
    """A raise between capture and adoption strands every captured
    request — callers block on done.wait() forever (the dynamic twin
    is the stalled-stream symptom, invisible until a client hangs)."""
    result = _own({SERVING: RESCUE_LEAK_BAD})
    assert _rules(result) == ["own-leak-on-path"], result.findings


def test_ownership_silent_when_rescue_unwind_fails_captured():
    assert _own({SERVING: RESCUE_LEAK_GUARDED}).findings == []


def test_ownership_flags_rebind_while_owned():
    """Overwriting the only binding of live blocks leaks them on every
    path — reported at the acquire sites, not the dataflow frontier."""
    src = """
        class Engine:
            def grow(self):
                blocks = self.allocator.alloc(2)
                if blocks is None:
                    return
                blocks = self.allocator.alloc(4)
                if blocks is None:
                    return
                self.allocator.free(blocks)
    """
    result = _own({ENGINE: src})
    assert set(_rules(result)) == {"own-leak-on-path"}, result.findings
    assert any("overwritten" in f.message for f in result.findings)


def test_ownership_release_in_finally_covers_both_edges():
    """CFG contract: the finally body is cloned per completion class,
    so one free there satisfies the normal AND the exception exit."""
    src = """
        class Engine:
            def scan(self, n):
                blocks = self.allocator.alloc(n)
                if blocks is None:
                    return
                try:
                    self.kick()
                finally:
                    self.allocator.free(blocks)
    """
    assert _own({ENGINE: src}).findings == []


def test_ownership_interprocedural_summary_vs_unresolved_escape():
    """Summaries: a resolved module-local callee that frees its
    parameter counts as the release (so a second free IS a double
    release), while an unresolved call conservatively escapes its
    argument and stays silent (the v2 no-false-edge invariant)."""
    src = """
        class Engine:
            def _drop(self, blks):
                self.allocator.free(blks)

            def good(self, n):
                blocks = self.allocator.alloc(n)
                if blocks is None:
                    return
                self._drop(blocks)

            def bad(self, n):
                blocks = self.allocator.alloc(n)
                if blocks is None:
                    return
                self._drop(blocks)
                self.allocator.free(blocks)

            def unresolved(self, n):
                blocks = self.allocator.alloc(n)
                if blocks is None:
                    return
                self.mystery(blocks)
    """
    result = _own({ENGINE: src})
    assert _rules(result) == ["own-double-release"], result.findings


# -- metrics discipline (ISSUE 19 satellite) ---------------------------------

METRICS_REG = """
    METRIC_REGISTRY = (
        ("requests", "counter", "dllm_requests_total",
         ("tier",), "Requests admitted."),
    )
    BOUNDED_LABELS = {
        "tier": "closed set: cluster tier names",
    }
"""


def _metrics(emission_src):
    return _lint(MetricsDisciplineChecker(),
                 {ENGINE: METRICS_REG, SERVING: emission_src})


def test_metrics_flags_unregistered_emission():
    result = _metrics("""
        def serve(registry):
            registry.counter("dllm_surprise_total", "x", ("tier",))
    """)
    assert _rules(result) == ["metrics-unregistered"], result.findings


def test_metrics_silent_on_matching_registered_emission():
    assert _metrics("""
        def serve(registry):
            registry.counter("dllm_requests_total", "x", ("tier",))
    """).findings == []


def test_metrics_flags_kind_and_label_drift():
    result = _metrics("""
        def wrong_kind(registry):
            registry.gauge("dllm_requests_total", "x", ("tier",))

        def wrong_labels(registry):
            registry.counter("dllm_requests_total", "x", ("tier", "who"))
    """)
    assert _rules(result) == ["metrics-unregistered"] * 2, result.findings


def test_metrics_get_checks_name_only():
    assert _metrics("""
        def peek(m):
            return m.get("dllm_requests_total")
    """).findings == []
    result = _metrics("""
        def peek(m):
            return m.get("dllm_gone_total")
    """)
    assert _rules(result) == ["metrics-unregistered"], result.findings


def test_metrics_flags_unbounded_label_once_at_minting_row():
    src = """
        METRIC_REGISTRY = (
            ("a", "counter", "dllm_a_total", ("session_id",), "A."),
            ("b", "counter", "dllm_b_total", ("session_id",), "B."),
        )
        BOUNDED_LABELS = {}
    """
    result = _lint(MetricsDisciplineChecker(), {ENGINE: src})
    assert _rules(result) == ["metrics-label-cardinality"], result.findings


def test_metrics_md_in_sync_with_registry():
    from distributed_llm_tpu.obs.metrics import \
        render_markdown as render_metrics_md
    path = os.path.join(repo_root(), "METRICS.md")
    with open(path, encoding="utf-8") as f:
        on_disk = f.read()
    assert on_disk == render_metrics_md(), (
        "METRICS.md is stale — regenerate with "
        "`python -m distributed_llm_tpu.obs.metrics > METRICS.md`")


def test_metric_registry_materializes_every_row():
    """ServingMetrics is a straight fold over METRIC_REGISTRY — every
    row becomes an attribute whose family matches the declared kind,
    name, and label set, and every row documents itself."""
    from distributed_llm_tpu.obs.metrics import (METRIC_REGISTRY,
                                                 MetricsRegistry,
                                                 ServingMetrics)
    m = ServingMetrics(MetricsRegistry())
    for attr, kind, name, labels, help_ in METRIC_REGISTRY:
        fam = getattr(m, attr)
        assert fam.name == name and fam.kind == kind, attr
        assert tuple(fam.label_names) == tuple(labels), attr
        assert help_.strip(), attr


# -- machine-readable output (--json) ----------------------------------------

def test_lint_json_output_round_trips(capsys):
    """`lint --json` emits one JSON object with the stable schema CI
    diffs across rounds — suppressed findings included (flagged), exit
    code unchanged from the text path."""
    from distributed_llm_tpu.lint.__main__ import main
    rc = main(["--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0 and payload["ok"] is True
    assert payload["counts"]["findings"] == 0
    assert payload["counts"]["suppressed"] >= 1
    entries = payload["findings"]
    assert len(entries) == payload["counts"]["suppressed"]
    for e in entries:
        assert set(e) == {"rule", "path", "line", "message", "suppressed"}
        assert e["suppressed"] is True and isinstance(e["line"], int)


def test_v3_rules_registered():
    rules = {r for c in all_checkers() for r in c.rules}
    assert {"own-leak-on-path", "own-double-release",
            "own-use-after-transfer", "own-pin-no-unpin",
            "metrics-unregistered",
            "metrics-label-cardinality"} <= rules


# -- regression: the PR 4 lock fixes behave (runtime twin of the lint) -------

class _SlowWarmupEngine:
    """Stub engine whose warmup blocks until released — simulates the
    multi-minute on-chip compile inside start_server."""

    started = None
    release = None

    def __init__(self, *a, **k):
        pass

    def warmup(self, beat=None):
        type(self).started.set()
        assert type(self).release.wait(10)


def test_health_probe_never_blocks_on_lifecycle_lock(monkeypatch):
    """Runtime regression for the manager fix: while start_server holds
    the lifecycle lock through a (stubbed) long warmup, health() and
    is_server_running() must answer immediately — the PR 2 failure mode
    was exactly these readers queueing behind the compile."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.engine import manager as manager_mod

    _SlowWarmupEngine.started = threading.Event()
    _SlowWarmupEngine.release = threading.Event()
    monkeypatch.setattr(manager_mod, "InferenceEngine", _SlowWarmupEngine)

    tier = TierConfig(name="nano", model_preset="nano_test",
                      decode_batch=1)
    mgr = manager_mod.EngineManager(tier, warmup_on_start=True)
    starter = threading.Thread(target=mgr.start_server, daemon=True)
    starter.start()
    try:
        assert _SlowWarmupEngine.started.wait(10)
        t0 = time.perf_counter()
        running = mgr.is_server_running()
        health = mgr.health()
        elapsed = time.perf_counter() - t0
        # Mid-compile: no engine yet, and the probe did not block on the
        # lifecycle lock (generous bound — the read is lock-free).
        assert elapsed < 1.0, f"probe blocked {elapsed:.1f}s on lifecycle"
        assert running is False
        assert health["ok"] is False
        assert health["uptime_s"] == 0.0
    finally:
        _SlowWarmupEngine.release.set()
        starter.join(10)
    assert mgr.is_server_running() is True
    assert mgr.health()["ok"] is True
