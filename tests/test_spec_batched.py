"""Batched speculative decoding on the ragged paged kernel (ISSUE 15).

The contracts under test:

- ``ragged_verify`` op parity: the Pallas q_len=γ+1 verify kernels
  (bf16 + int8) against the XLA gather fallback — the byte-level parity
  reference — at skewed per-slot positions, including the γ=1
  degeneration to decode semantics.
- ``verify_step_paged`` reproduces sequential greedy decode exactly:
  row g's argmax equals the g-th sequential ``decode_step_paged``
  greedy token (the speculative guarantee's mechanical core).
- Engine byte-identity: spec-on output token ids equal spec-off for a
  concurrent greedy batch, with a self-draft (acceptance ≈ 1), a
  disagreeing draft (rejections + rollback every round), a chunked long
  prompt (spec-ineligible slot), and per-request sampled co-slots.
- Per-slot adaptive γ: the EWMA→γ mapping is pinned; a low-acceptance
  slot degrades to γ=0 (plain ragged decode — stops drafting entirely)
  while co-slots keep speculating; an all-degraded engine falls back to
  the plain T-step tick.
- Program family bound: compiled draft/verify programs == the
  (γ_bucket) family, fully warmed — serving mints nothing new.
- Observability: spec_stats/slot_stats surfaces, dllm_spec_* counters,
  the sampler's spec_accept_ratio field, draft/verify profiler phases.

All fast and deterministic (greedy decode, fixed seeds).  The
rollback × sharing matrix lives in tests/test_shared_prefix.py next to
the refcount machinery it exercises.
"""

import dataclasses
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_tpu.config import MODEL_PRESETS, tiny_batched_cluster
from distributed_llm_tpu.engine.batching import (SPEC_EWMA_FLOOR,
                                                 ContinuousBatchingEngine)


def _tier(**kw):
    base = dict(max_new_tokens=8)
    base.update(kw)
    return dataclasses.replace(tiny_batched_cluster().nano, **base)


def _spec_tier(draft="nano_test", **kw):
    return _tier(spec_decode=True, draft_preset=draft, **kw)


def _drain(eng, prompts, **gen_kw):
    reqs = [eng.submit(p, **gen_kw) for p in prompts]
    for r in reqs:
        assert r.done.wait(timeout=120), "request hung"
    for r in reqs:
        if r.error is not None:
            raise r.error
    return [tuple(r.result.token_ids) for r in reqs]


# -- op-level parity ----------------------------------------------------------

def _verify_inputs(q8=False, g=5):
    from distributed_llm_tpu.ops.quant import quantize_kv_rows
    key = jax.random.PRNGKey(0)
    nkv, nq, d, bs = 2, 4, 16, 8
    b, mb = 3, 6
    nb = b * mb + 1
    kp = jax.random.normal(key, (nkv, nb, bs, d), jnp.float32)
    vp = jax.random.normal(jax.random.PRNGKey(1), (nkv, nb, bs, d),
                           jnp.float32)
    tables = jnp.asarray(
        np.arange(b * mb, dtype=np.int32).reshape(b, mb) + 1)
    pos = jnp.asarray([3, 17, 40], jnp.int32)        # skewed frontiers
    q = jax.random.normal(jax.random.PRNGKey(2), (b, g, nq, d), jnp.float32)
    if not q8:
        return q, kp, vp, None, None, tables, pos
    kq, ksc = quantize_kv_rows(kp)
    vq, vsc = quantize_kv_rows(vp)
    return q, kq, vq, ksc, vsc, tables, pos


@pytest.mark.parametrize("q8", [False, True], ids=["bf16", "int8"])
def test_ragged_verify_g1_degenerates_to_decode(q8):
    from distributed_llm_tpu.ops import attention as A
    q, kp, vp, ks, vs, tables, pos = _verify_inputs(g=1, q8=q8)
    dec = A.paged_decode(q[:, 0], kp, vp, tables, pos, k_scale=ks,
                         v_scale=vs)
    ver = A.ragged_verify(q, kp, vp, tables, pos, k_scale=ks,
                          v_scale=vs)[:, 0]
    np.testing.assert_allclose(np.asarray(ver), np.asarray(dec),
                               rtol=2e-5, atol=2e-5)


def test_verify_step_reproduces_sequential_greedy_decode():
    """Row g's argmax == the g-th sequential greedy token: the verify
    forward IS greedy decode unrolled over the chunk, so the acceptance
    rule's byte-identity guarantee reduces to this pin."""
    from distributed_llm_tpu import models
    from distributed_llm_tpu.engine.paged_kv import (
        PagedConfig, TRASH_BLOCK, decode_step_paged, init_pool,
        verify_step_paged)
    cfg = MODEL_PRESETS["nano_test"]
    params = jax.jit(lambda: models.init_params(cfg, seed=3))()
    pcfg = PagedConfig(block_size=16, max_slots=2,
                       max_seq_len=cfg.max_seq_len)
    pool = init_pool(cfg, pcfg, "none")
    tables = np.full((2, pcfg.blocks_per_slot), TRASH_BLOCK, np.int32)
    tables[0, :4] = [1, 2, 3, 4]
    tables[1, :4] = [5, 6, 7, 8]
    tables = jnp.asarray(tables)
    pos = jnp.asarray([5, 9], jnp.int32)
    cur = jnp.asarray([7, 11], jnp.int32)

    pool_a, p, c = pool, pos, cur
    seq = []
    for _ in range(3):
        logits, pool_a = decode_step_paged(cfg, params, c, p, pool_a,
                                           tables)
        c = jnp.argmax(logits, -1).astype(jnp.int32)
        p = p + 1
        seq.append(np.asarray(c))

    chunk = jnp.stack([cur, jnp.asarray(seq[0]), jnp.asarray(seq[1])],
                      axis=1)
    logits_v, _ = verify_step_paged(cfg, params, chunk, pos, pool, tables)
    picks = np.asarray(jnp.argmax(logits_v, -1))
    for g in range(3):
        assert picks[:, g].tolist() == seq[g].tolist(), g


def test_verify_step_overflow_rows_write_trash_not_live_kv():
    """Chunk rows past max_seq_len scatter into the trash block — a
    clamped write would corrupt live KV the per-query mask exposes."""
    from distributed_llm_tpu import models
    from distributed_llm_tpu.engine.paged_kv import (
        PagedConfig, TRASH_BLOCK, init_pool, verify_step_paged)
    cfg = MODEL_PRESETS["nano_test"]
    params = jax.jit(lambda: models.init_params(cfg, seed=3))()
    pcfg = PagedConfig(block_size=16, max_slots=1,
                       max_seq_len=cfg.max_seq_len)
    pool = init_pool(cfg, pcfg, "none")
    nb = pcfg.blocks_per_slot
    tables = jnp.asarray(np.arange(1, nb + 1, dtype=np.int32)[None])
    last_block = nb                          # holds positions max_seq-16..
    before = np.asarray(pool["k"][:, last_block])
    # First chunk position = max_seq-1: rows 1..3 overflow the context.
    chunk = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    pos = jnp.asarray([cfg.max_seq_len - 1], jnp.int32)
    _, new_pool = verify_step_paged(cfg, params, chunk, pos, pool, tables)
    after = np.asarray(new_pool["k"][:, last_block])
    # Row 0 (position max_seq-1) legitimately wrote ONE row of the last
    # block; the three overflow rows must have gone to trash, leaving
    # every other row of the last block untouched.
    changed_rows = {int(r) for r in
                    np.argwhere(np.any(before != after, axis=(0, 2)))
                    .ravel()}
    assert changed_rows <= {(cfg.max_seq_len - 1) % pcfg.block_size}


# -- engine byte-identity -----------------------------------------------------

def _outputs(tier, prompts, seed=7, **gen_kw):
    eng = ContinuousBatchingEngine(tier, seed=seed)
    try:
        ids = _drain(eng, prompts, **gen_kw)
        stats = eng.spec_stats()
    finally:
        eng.stop()
    return ids, stats


PROMPTS = [f"question about rivers number {i}" for i in range(6)]


def test_spec_outputs_byte_identical_self_draft():
    off, _ = _outputs(_tier(), PROMPTS)
    on, st = _outputs(_spec_tier(), PROMPTS)
    assert on == off
    assert st["enabled"] and st["drafted_total"] > 0
    # Self-draft: identical weights and mirrored draft KV make the
    # draft's greedy continuation the target's — acceptance pins at 1.
    assert st["accept_ratio"] == 1.0


def test_spec_outputs_byte_identical_disagreeing_draft():
    off, _ = _outputs(_tier(), PROMPTS)
    on, st = _outputs(_spec_tier(draft="draft_test"), PROMPTS)
    assert on == off
    assert st["drafted_total"] > 0


def test_spec_chunked_long_prompt_stays_byte_identical():
    """A chunk-gated admission (long prompt) skips the draft seeding —
    its slot decodes plain (spec-ineligible) and the output still
    matches spec-off exactly, co-resident with speculating slots."""
    long_q = "long question: " + "rivers lakes mountains oceans " * 20
    prompts = [long_q] + PROMPTS[:3]
    kw = dict(prefill_chunk_tokens=32, prefill_buckets=(16, 32, 64, 128))
    off, _ = _outputs(_tier(**kw), prompts)
    on, _ = _outputs(_spec_tier(**kw), prompts)
    assert on == off


def test_spec_sampled_request_rides_gamma_zero():
    """A per-request temperature>0 slot never speculates (γ=0) but
    still samples its one token per round from the verify's first-row
    logits; greedy co-slots stay byte-identical to spec-off."""
    tier = _spec_tier()
    eng = ContinuousBatchingEngine(tier, seed=7)
    try:
        sampled = eng.submit("sampled request about rivers",
                             temperature=0.9)
        greedy = [eng.submit(p) for p in PROMPTS[:3]]
        assert sampled.done.wait(timeout=120)
        for r in greedy:
            assert r.done.wait(timeout=120)
        for r in [sampled] + greedy:
            if r.error is not None:
                raise r.error
        greedy_ids = [tuple(r.result.token_ids) for r in greedy]
    finally:
        eng.stop()
    off, _ = _outputs(_tier(), PROMPTS[:3])
    assert greedy_ids == off


def test_spec_preemption_replay_byte_identical():
    """Preempt → replay under a tight pool with spec ON: the replay
    re-seeds the draft prefix and the final outputs match spec-off on
    the same pool (the PR 5 byte-identity contract survives both the
    draft pool and the frontier rewind)."""
    kw = dict(decode_batch=2, kv_pool_blocks=10, max_new_tokens=24,
              enable_prefix_cache=False)
    prompts = [f"pressure question {i} about rivers" for i in range(4)]
    off, _ = _outputs(_tier(**kw), prompts)
    on, _ = _outputs(_spec_tier(**kw), prompts)
    assert on == off


# -- adaptive gamma -----------------------------------------------------------

def test_adapt_gamma_mapping_pinned():
    eng = ContinuousBatchingEngine(_spec_tier(spec_gamma_max=4), seed=7)
    try:
        assert eng._adapt_gamma(1.0) == 4
        assert eng._adapt_gamma(0.5) == 2
        assert eng._adapt_gamma(0.26) == 1
        assert eng._adapt_gamma(SPEC_EWMA_FLOOR) == 1    # floor inclusive
        assert eng._adapt_gamma(SPEC_EWMA_FLOOR - 1e-6) == 0
        assert eng._adapt_gamma(0.0) == 0
        assert eng._gamma_buckets == (1, 2, 4)
        assert eng._gamma_bucket(3) == 4
    finally:
        eng.stop()


def test_low_acceptance_slot_degrades_while_coslot_speculates():
    """The ISSUE 15 acceptance pin, fully deterministic: slot 0's
    drafts are bit-flipped at the draft-fn seam (a draft that can NEVER
    match the target's pick — structural acceptance 0), so its EWMA
    decays below the floor and the slot degrades to γ=0 (stops drafting
    entirely, sticky) while the self-draft co-slot keeps speculating at
    acceptance 1.  The degraded slot's output must STILL be
    byte-identical to plain decode — rejection always emits the
    target's own pick."""
    tier = _spec_tier(decode_batch=2, max_new_tokens=32)
    eng = ContinuousBatchingEngine(tier, seed=7)
    victim_ix = 0                    # first admission takes slot 0
    try:
        eng.warmup()

        def corrupt(orig):
            def f(params_d, pool_d, tables, pos, cur):
                drafted, pool_d = orig(params_d, pool_d, tables, pos, cur)
                bad = jnp.bitwise_xor(drafted[victim_ix], 1)
                return drafted.at[victim_ix].set(bad), pool_d
            return f

        for gb in eng._gamma_buckets:
            eng._spec_fns[("spec_draft", gb)] = corrupt(
                eng._spec_draft_fn(gb))
        eng._spec_slot_acc.clear()       # drop warmup's own round
        on_ids = _drain(eng, PROMPTS[:2])
        st = eng.spec_stats()["per_slot"]
        v = st[str(victim_ix)]
        o = st["1"]
        # Structural rejection: zero accepted; EWMA decay reaches the
        # floor within ceil(log(floor)/log(1-α)) ≈ 6 rounds at γ≤4
        # drafts each, after which γ=0 drafts nothing — the count is
        # BOUNDED, not merely smaller.
        assert v["accepted"] == 0
        assert v["drafted"] <= 8 * tier.spec_gamma_max
        # The co-slot keeps speculating: high acceptance (self-draft;
        # not exactly 1.0 — near-tie argmaxes can flip between the
        # draft's decode kernel and the verify's chunk kernel) and a
        # draft count far past the victim's degradation bound.
        assert o["ratio"] >= 0.5
        assert o["drafted"] >= 5 * tier.spec_gamma_max
        assert o["drafted"] > v["drafted"]
    finally:
        eng.stop()
    off, _ = _outputs(_tier(decode_batch=2, max_new_tokens=32),
                      PROMPTS[:2])
    assert on_ids == off


def test_all_degraded_engine_falls_back_to_plain_tick():
    """With every slot at γ=0 the scheduler runs the plain T-step tick
    (zero speculative overhead), observable as _spec_plan returning
    None."""
    eng = ContinuousBatchingEngine(_spec_tier(decode_batch=2), seed=7)
    try:
        reqs = [eng.submit(p, token_queue=queue.Queue())
                for p in PROMPTS[:2]]
        deadline = time.time() + 60
        while time.time() < deadline:
            live = [ix for ix, s in enumerate(eng._slots)
                    if s is not None]
            if len(live) == 2:
                break
            time.sleep(0.005)
        for ix in live:
            eng._slots[ix].gamma = 0
        assert eng._spec_plan(live) is None
        for r in reqs:
            assert r.done.wait(timeout=120)
    finally:
        eng.stop()


# -- program family + surfaces ------------------------------------------------

def test_verify_program_family_bounded_and_fully_warmed():
    """Warmup compiles the whole (γ_bucket) draft/verify family; a
    served batch mints NOTHING new — per-slot γ and acceptance lengths
    are runtime operands (the bench leg re-checks this live and the
    retrace-lint fixture pins the static half)."""
    eng = ContinuousBatchingEngine(_spec_tier(spec_gamma_max=4), seed=7)
    try:
        eng.warmup()
        family = len(eng._gamma_buckets)
        assert len(eng._compiled.get("verify", ())) == family
        warm_draft = set(eng._compiled.get("draft", ()))
        _drain(eng, PROMPTS)
        assert len(eng._compiled.get("verify", ())) == family
        assert set(eng._compiled.get("draft", ())) == warm_draft
    finally:
        eng.stop()


def test_spec_requires_ragged_and_draft():
    """spec_decode without its prerequisites disarms with a warning
    instead of building a broken engine."""
    eng = ContinuousBatchingEngine(
        _tier(spec_decode=True, attention_ragged=False,
              draft_preset="nano_test"), seed=7)
    try:
        assert not eng.spec
    finally:
        eng.stop()
    eng = ContinuousBatchingEngine(_tier(spec_decode=True), seed=7)
    try:
        assert not eng.spec            # no draft_preset
    finally:
        eng.stop()


def test_spec_stats_and_slot_stats_surfaces():
    eng = ContinuousBatchingEngine(_spec_tier(), seed=7)
    try:
        st = eng.slot_stats()
        assert "spec_gammas" in st and st["spec_gammas"] == {}
        _drain(eng, PROMPTS[:2])
        sp = eng.spec_stats()
        assert sp["enabled"] and sp["gamma_max"] == 4
        assert sp["drafted_total"] >= sp["accepted_total"] > 0
        assert sp["accept_ratio"] == pytest.approx(
            sp["accepted_total"] / sp["drafted_total"], abs=1e-3)
        assert sp["per_slot"], "per-slot accumulators must populate"
        for rec in sp["per_slot"].values():
            assert rec["drafted"] >= rec["accepted"]
    finally:
        eng.stop()


def test_spec_counters_and_sampler_field():
    """dllm_spec_* counters move and the router's engine-state collector
    exposes spec_accept_ratio for the sampler gauge."""
    from distributed_llm_tpu.obs import get_observability
    from distributed_llm_tpu.serving.router import Router
    eng = ContinuousBatchingEngine(_spec_tier(), seed=7)
    try:
        m = get_observability().m
        drafted0 = m.spec_drafted.labels(eng.tier.name).value
        accepted0 = m.spec_accepted.labels(eng.tier.name).value
        _drain(eng, PROMPTS[:2])
        st = eng.spec_stats()
        assert (m.spec_drafted.labels(eng.tier.name).value - drafted0
                == st["drafted_total"])
        assert (m.spec_accepted.labels(eng.tier.name).value - accepted0
                == st["accepted_total"])
        collected = Router._collect_engine_state(eng)
        assert collected.get("spec_accept_ratio") == st["accept_ratio"]
    finally:
        eng.stop()


def test_profiler_records_draft_and_verify_phases():
    eng = ContinuousBatchingEngine(_spec_tier(), seed=7)
    try:
        if not eng.profiler.enabled:
            pytest.skip("profiler disabled (DLLM_PROFILE=0)")
        _drain(eng, PROMPTS[:2])
        phases = eng.profiler.phase_stats()["phases"]
        assert phases.get("draft", {}).get("n", 0) > 0
        assert phases.get("verify", {}).get("n", 0) > 0
    finally:
        eng.stop()


def test_spec_decode_false_is_an_operator_kill_switch():
    """The tri-state knob's off state: an explicit spec_decode=False on
    a batched draft tier must NOT be re-armed by the manager's AUTO
    path — the tier keeps its draft config but serves plain batched
    decode (the operator's incident lever)."""
    from distributed_llm_tpu.engine.manager import EngineManager
    mgr = EngineManager(_tier(draft_preset="draft_test",
                              spec_decode=False),
                        warmup_on_start=False)
    try:
        eng = mgr.engine()
        assert isinstance(eng, ContinuousBatchingEngine)
        assert not eng.spec
    finally:
        mgr.stop_server()


def test_manager_routes_batched_draft_and_arms_spec():
    """The PR 1 bypass is retired: draft_preset + decode_batch>1 builds
    the batched engine with speculation armed; decode_batch=1 keeps the
    sequential SpeculativeEngine (tests/test_admission.py pins the
    admission-slots side)."""
    from distributed_llm_tpu.engine.manager import EngineManager
    mgr = EngineManager(_tier(draft_preset="draft_test"),
                        warmup_on_start=False)
    try:
        eng = mgr.engine()
        assert isinstance(eng, ContinuousBatchingEngine)
        assert eng.spec and eng.cfg_d is not None
        ids = _drain(eng, PROMPTS[:2])
    finally:
        mgr.stop_server()
    off, _ = _outputs(_tier(), PROMPTS[:2])
    assert ids == off
