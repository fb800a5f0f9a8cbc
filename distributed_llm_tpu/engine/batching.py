"""Continuous batching: many concurrent requests share one decode loop.

The reference serves one blocking request per device at a time (Flask →
Ollama with ``stream: false``, src/devices/nano_api.py:64-76); concurrency
is only across the two Jetsons.  Here a tier runs a scheduler in front of
the paged KV pool (engine/paged_kv.py):

- requests **admit** into one of ``max_slots`` batch slots as soon as a
  slot and enough KV blocks are free.  A prompt that fits one prefill
  chunk (``TierConfig.prefill_chunk_tokens``) prefills immediately —
  TTFT is one compiled prefill call, same as the sequential engine; a
  LONGER prompt becomes the tick's single **in-flight chunked prefill**:
  fixed-size chunks (``chunk_prefill_paged`` writing straight into the
  slot's pool blocks) interleave with decode ticks under a per-tick
  token budget, so admitting a 4k-token prompt stalls active streams by
  one CHUNK per tick, never one whole prompt;
- every scheduler tick runs ONE batched ``decode_step_paged`` for all
  active slots — a new request joins mid-flight without waiting for its
  neighbors to finish, and a finished one frees its blocks the same
  tick — then spends up to ``prefill_chunk_budget`` tokens advancing
  the in-flight prefill;
- the public surface stays the synchronous per-request ``generate()``
  (the /query contract): callers block on a per-request event while their
  tokens stream out of the shared loop.

Shapes are static in (max_slots, blocks_per_slot): one compiled decode
step serves every occupancy, so the scheduler never recompiles.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import logging
import queue
import threading
import time
from collections import deque
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..config import TierConfig
from .. import models
from ..models import transformer
from ..obs import metrics as obs_metrics
from ..obs import spans as obs_spans
from ..obs.profiler import make_profiler
from ..serving.errors import error_dict
from .inference import (GenerationResult, prepare_prompt, trim_at_eos,
                        upgrade_attention_impl)
from .paged_kv import (BlockAllocator, PagedConfig, TRASH_BLOCK,
                       chunk_prefill_paged, decode_step_paged, init_pool,
                       pool_formats, verify_step_paged,
                       write_prefill_blocks)
from .tokenizer import get_tokenizer

History = Union[str, Sequence[Dict[str, Any]]]

logger = logging.getLogger(__name__)


class EngineStoppedError(RuntimeError):
    """A request was failed by ``stop()`` (shutdown or drain deadline)
    while in flight or queued.  Carries the reference error-dict shape in
    ``.shape`` so serving layers (serving/tiers.py) forward the exact
    schema-validated dict to clients instead of re-stringifying a bare
    RuntimeError."""

    def __init__(self, shape: Dict[str, Any]):
        super().__init__(str(shape.get("error", "engine stopped")))
        self.shape = dict(shape)


def _sample_batched(logits: jax.Array, rng: jax.Array,
                    temps: jax.Array) -> jax.Array:
    """Per-slot runtime temperature: greedy where temp<=0, else sampled."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1)
        scaled = (logits.astype(jnp.float32)
                  / jnp.maximum(temps, 1e-6)[:, None])
        sampled = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(temps > 0.0, sampled, greedy)


def _fetch_tick(x):
    """THE tick boundary's one sanctioned device sync: pull a tick's
    device results to host in one blocking call — shared by the plain
    decode tick ([T, B] tokens) and the speculative round's verify
    outputs ((out, n_acc)), so the hot path has exactly ONE sync site
    and every other host round-trip must justify itself against it.
    ``tree_map`` makes the numpy pull cover either pytree shape."""
    # dllm-lint: disable=transfer-host-sync -- THE one sanctioned sync per tick: the tick boundary, where all of a tick's tokens become observable in one pull (plain [T,B] or speculative (out, n_acc)) — every other hot-path sync must justify itself against this one
    return jax.tree_util.tree_map(np.asarray, jax.block_until_ready(x))


def _window_ladder(span: int, block_size: int, doubling: bool) -> List[int]:
    """Attention-window rungs of a chunk program over a table of
    ``span`` positions: 256 and 1024, with ``doubling`` also 2048,
    4096, ... below the span, block-aligned, then the span itself."""
    rungs = [256, 1024]
    while doubling and rungs[-1] * 2 < span:
        rungs.append(rungs[-1] * 2)
    return sorted({-(-c // block_size) * block_size
                   for c in rungs if c < span} | {span})


# Per-slot acceptance-rate-adaptive γ (ISSUE 15): EWMA weight of a
# round's observed acceptance, and the floor under which a slot stops
# speculating entirely (γ=0 — it rides the verify's first row only, i.e.
# plain ragged decode, burning zero draft/verify width).  γ=0 is sticky
# for the slot's lifetime: with no drafts there is no new acceptance
# evidence, and a fresh request starts optimistic again.
SPEC_EWMA_ALPHA = 0.3
SPEC_EWMA_FLOOR = 0.125


@dataclasses.dataclass
class _Request:
    history: History
    max_new_tokens: Optional[int]
    temperature: Optional[float]
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    result: Optional[GenerationResult] = None
    error: Optional[BaseException] = None
    t_submit: float = dataclasses.field(default_factory=time.perf_counter)
    # Streaming: when set, every accepted token id is pushed here as it is
    # produced; None terminates the stream (see generate_stream).
    token_queue: Optional["queue.Queue"] = None
    # The submitting request's span tree (obs/spans.py), captured at
    # submit() because the scheduler thread has no request context of
    # its own.  None (direct engine use, tests) disables tracing.
    trace: Optional[Any] = None
    # Mid-decode preemption state: on preemption the slot's generated
    # tokens (already emitted to any stream) park here and the request
    # re-queues at the scheduler head; re-admission replays prompt +
    # prefix through prefill so greedy output is byte-identical
    # (_admit_replay).  The original TTFT survives the round trip.
    replay_tokens: Optional[List[int]] = None
    replay_ttft_ms: Optional[float] = None
    preempt_count: int = 0
    # First-admission order (monotonic): the preemption victim policy
    # picks the YOUNGEST slot, and a replayed request keeps its original
    # age so it is not immediately re-victimized.
    admit_seq: int = -1
    # Set when an admission attempt deferred because the single chunked-
    # prefill lane was busy: the scheduler skips re-popping (and
    # re-tokenizing) the head request every tick until the lane frees.
    needs_chunk: bool = False
    # Time spent at the scheduler head held by the busy prefill lane
    # (``head_blocked``): stamped when an admission first defers on it,
    # closed by the next admission attempt, annotated ``lane_wait_ms``
    # (0 for a request the lane never held up).
    t_lane_blocked: Optional[float] = None
    lane_wait_ms: float = 0.0
    # Billing identity (ISSUE 17): which tenant's quota this request
    # draws down.  None (direct engine use, quotas off) bills to the
    # shared default tenant where tenant state exists at all.
    tenant: Optional[str] = None


@dataclasses.dataclass
class _Slot:
    request: _Request
    blocks: List[int]
    prompt_len: int
    budget: int
    temperature: float
    ttft_ms: float
    tokens: List[int] = dataclasses.field(default_factory=list)
    # Prompt token ids, kept so the slot's prompt blocks can be parked for
    # prefix reuse when it finishes (engine/prefix_cache.py).
    prompt_ids: tuple = ()
    # Growth cap in pool blocks (prompt bucket + decode budget): blocks
    # are materialized lazily as the sequence grows, never past this.
    max_blocks: int = 0
    # Shared-prefix hit (ISSUE 10): the PrefixEntry this slot pinned —
    # its leading table rows map the entry's blocks READ-ONLY (incref'd;
    # the boundary block was COW-copied).  Unpinned on release; the
    # block references themselves drop through the allocator's uniform
    # refcounted free().
    pinned_entry: Optional[Any] = None
    # Batched speculative decoding (ISSUE 15): whether this slot's draft
    # KV was seeded (monolithic cold prefill / prefix-hit suffix chunk /
    # replay — chunked and host-promoted admissions skip the draft pass,
    # so their drafts would attend garbage), its current adaptive γ
    # (0 = degraded to plain ragged decode, sticky), the acceptance
    # EWMA driving γ, and lifetime draft/accept counts for spec_stats.
    spec: bool = False
    gamma: int = 0
    accept_ewma: float = 1.0
    spec_drafted: int = 0
    spec_accepted: int = 0


@dataclasses.dataclass
class _Prefill:
    """The tick's single in-flight chunked prefill: an admitted request
    whose prompt is being written into its reserved slot's pool blocks
    one fixed-size chunk per budget grant, interleaved with decode
    ticks.  A first-class scheduler citizen: its blocks count against
    the pool (KV-aware admission sees the remainder via ``kv_stats``),
    starvation cancels-and-requeues it before any DECODING slot is
    preempted, drain waits it out, and ``stop()`` fails it with the
    engine-stopped shape like any queued request."""

    request: _Request
    slot_ix: int                  # reserved slot (no _Slot until done)
    seq: List[int]                # tokens to prefill (prompt, or
                                  # prompt + generated[:-1] for a replay)
    prompt_len: int               # prompt tokens only (slot accounting)
    prompt_ids: tuple
    total: int                    # len(seq)
    budget: int                   # decode cap carried to the slot
    temperature: float
    rng: Any                      # split ONCE at start; sampled at the
                                  # final chunk exactly like monolithic
    max_blocks: int
    blocks: List[int] = dataclasses.field(default_factory=list)
    consumed: int = 0             # prefilled positions so far
    chunks_done: int = 0
    # Replayed generation (preempted request): the final chunk's sample
    # is discarded and decode resumes from replay[-1] (see
    # _admit_replay for the byte-identity contract).
    replay: Optional[List[int]] = None
    # Hierarchical-KV promotion (ISSUE 14, engine/kv_spill.py): when
    # set, the leading ``promote_nb`` blocks (covering the first
    # ``promote_tokens`` positions) are satisfied by host→device copies
    # of the claimed HostEntry instead of chunk compute —
    # _advance_promotion grants them per tick under the shared chunk
    # budget, then ``consumed`` jumps to ``promote_tokens`` and the
    # suffix chunk-prefills as usual.  A promotion that loses the race
    # clears these fields and the prefill restarts cold from 0
    # (byte-identical greedy output either way).
    promote_entry: Optional[Any] = None
    promote_tokens: int = 0
    promote_nb: int = 0
    promote_done: int = 0
    promote_waits: int = 0
    t_start: float = dataclasses.field(default_factory=time.perf_counter)
    # The ONE chunk whose outputs the host has not waited for yet (the
    # chunk program's first output as dispatched: the sampled token,
    # with a routed-expert model's assignment counts) and when it was
    # dispatched.  ``_settle_chunk`` resolves it: before the next chunk
    # is dispatched, or when the prefill ends either way.
    pending: Optional[Any] = None
    pending_t: float = 0.0


@dataclasses.dataclass
class _Tick:
    """A plain decode tick between its dispatch and its fetch: what the
    fetch, ``account`` and the emit need of it.  At most two exist at a
    time, the one being fetched and the one dispatched AHEAD of that
    fetch (``_may_go_ahead``)."""

    active: List[int]             # the slots it was dispatched for
    toks: Any                     # [T, B] as dispatched (routed experts:
                                  # with the steps' assignment counts)
    wb: int                       # its table rung, in blocks
    key_in: Any                   # the key it was given (a failed tick's
                                  # successor starts from its split)
    t0: float                     # perf_counter at its launch
    ahead: bool                   # dispatched before the fetch before it
    ride_s: float = 0.0           # a riding chunk's host section


class _ExpertLoad:
    """Assignments of tokens to routed experts, summed over an engine's
    life by stage (``decode`` steps, ``prefill`` chunks): a count a
    (layer, expert), and how many (step, layer, expert) cells had at
    least one token — the experts a step had to read.  Every row the
    program computed counts, an idle slot's and a chunk's padding too:
    the device read their experts all the same.  The experts are the
    ones this program HOLDS; a program that holds a share of a layer's
    experts (models/hybrid_ssm.py) returns one column more, the
    assignments that went to absent experts, summed as ``absent``.
    Scheduler-thread writes only; ``stats`` reads whole arrays under the
    GIL."""

    STAGES = ("decode", "prefill")

    def __init__(self, tier_name: str, n_layers: int, n_experts: int):
        self.tier_name = tier_name
        self.tokens = {s: np.zeros((n_layers, n_experts), np.int64)
                       for s in self.STAGES}
        self.steps = dict.fromkeys(self.STAGES, 0)
        self.touched = dict.fromkeys(self.STAGES, 0)
        self.absent = dict.fromkeys(self.STAGES, 0)

    def note(self, stage: str, counts) -> None:
        """``counts`` [steps, layers, experts (+ 1: absent)] as fetched."""
        counts = np.asarray(counts)
        held = self.tokens[stage].shape[1]
        absent = int(counts[..., held:].sum())
        counts = counts[..., :held]
        self.absent[stage] += absent
        touched = int(np.count_nonzero(counts))
        per_expert = counts.sum(axis=0)
        self.tokens[stage] += per_expert
        self.steps[stage] += counts.shape[0]
        self.touched[stage] += touched
        try:
            from ..obs import get_observability
            m = get_observability().m
            m.moe_assignments.labels(self.tier_name, stage).inc(
                int(per_expert.sum()))
            m.moe_experts_touched.labels(self.tier_name, stage).inc(touched)
            if absent:
                m.moe_absent_assignments.labels(self.tier_name,
                                                stage).inc(absent)
        except Exception:
            pass

    def stats(self) -> Dict[str, Any]:
        return {"expert_tokens": {s: self.tokens[s].tolist()
                                  for s in self.STAGES},
                "steps": dict(self.steps),
                "experts_touched": dict(self.touched),
                "absent_assignments": dict(self.absent)}


class ContinuousBatchingEngine:
    """Drop-in for InferenceEngine (same generate()/warmup() surface) with
    a shared batched decode loop behind it.  Built by EngineManager when
    ``tier.decode_batch > 1``.

    With a ``mesh`` the engine runs tensor-parallel over the tier submesh:
    params follow parallel/sharding.py's Megatron rules and the paged pool
    shards its kv-head axis (kv_pool_specs), so many concurrent requests
    share one batched decode loop across the tier's chips."""

    # generate() is designed for concurrent callers (the scheduler owns
    # slot admission); TierClient reads this to skip its serialization
    # lock — sequential engines without it assume serialized callers.
    concurrent_safe = True

    def __init__(self, tier: TierConfig, seed: int = 0,
                 params: Optional[Dict[str, Any]] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        self.tier = tier
        self.mesh = mesh
        # Under a mesh, "auto" stays on the GSPMD-partitionable XLA path
        # (upgrade_attention_impl only opts unsharded engines into Pallas).
        self.cfg = upgrade_attention_impl(tier.model(), mesh)
        self._refuse_unsupported(tier, mesh)
        bad = [b for b in tier.prefill_buckets if b % tier.kv_block_size]
        if bad:
            raise ValueError(
                f"prefill buckets {bad} not multiples of kv_block_size="
                f"{tier.kv_block_size}: prefilled K/V must page evenly")
        self.tokenizer = get_tokenizer(self.cfg)
        self.devices = list(devices) if devices else None
        self._rng = jax.random.PRNGKey(seed ^ 0xBA7C4)

        self.paged = PagedConfig(block_size=tier.kv_block_size,
                                 max_slots=tier.decode_batch,
                                 max_seq_len=self.cfg.max_seq_len,
                                 pool_blocks=tier.kv_pool_blocks)
        self.steps_per_tick = max(1, tier.decode_steps_per_tick)
        # The fused tick: every slot's FULL table row goes to ONE
        # attention.paged_decode call instead of a table sliced to a
        # bucketed window rung (``_resolve_ragged`` has the rule).
        self.ragged = self._resolve_ragged()
        # Full-table device upload cache: under ragged decode the tables
        # arg is shape-stable, so it is re-uploaded only when a table row
        # actually changes (admission/growth/finish/preempt).  The dense
        # rung path gets the same treatment per window width (ISSUE 8's
        # transfer lint flagged its every-tick host slice+upload):
        # _tables_dev_w caches one device copy per rung, invalidated
        # together with _tables_dev on any row change.
        self._tables_dev = None
        self._tables_dev_w: Dict[int, object] = {}
        # Recent decode-tick device times in ms (ring; bench skew leg and
        # tests read it — the obs histogram is the scrapeable twin).
        self.tick_ms: "deque[float]" = deque(maxlen=512)
        # Tick-phase profiler (ISSUE 11, obs/profiler.py): per-pass phase
        # breakdown ring + per-request decode-time/KV-residency
        # attribution.  DLLM_PROFILE=0 swaps in the shared zero-cost
        # null object; every stamp below and the attribution branch in
        # the tick gate on it.
        self.profiler = make_profiler(tier.name)
        # Per-slot KV-residency weight cache (Σ 1/refcount over the
        # slot's blocks): a refcount relevant to a LIVE slot can only
        # change through an event that also rewrites a table row
        # (admission/share, growth, finish/park, preempt), so the cache
        # is invalidated with the device-table caches in _set_table_row
        # and the attribution loop pays one dict lookup per slot per
        # tick instead of an allocator-locked refcount scan.
        self._kv_weights: Dict[int, float] = {}
        # Distinct compiled programs minted per stage (prefill buckets,
        # chunk (bucket, window) pairs, writers, decode widths) — the
        # compile-churn surface ISSUE 6 bounds: logged on growth and
        # mirrored to the dllm_compiled_programs gauge.
        self._compiled: Dict[str, set] = {}
        # The attention form each compiled rung of the decode tick was
        # traced with, by its table window in tokens (``tick_stats``).
        self._attention_forms: Dict[str, str] = {}
        # The chunk's twin: what each compiled chunk program's latent
        # attention was traced with, by (chunk tokens, window tokens)
        # (``prefill_stats``); empty for a family without a latent row.
        self._chunk_attention_forms: Dict[Tuple[int, int], str] = {}
        # ``step_programs``' entries by (stage, key): empty until GET
        # /debug/programs asks, and nothing but that route fills it.
        self._program_maps: Dict[Tuple[str, Any], Dict[str, Any]] = {}
        # Routed-expert load (the latent and hybrid families): what the
        # tick and the chunk program return beside their tokens, summed on
        # the host, over the family's expert layers and the experts held.
        expert_layers = 0
        if self.cfg.hybrid:
            expert_layers = self.cfg.layers_of("E")
        elif self.cfg.latent and self.cfg.num_experts > 1:
            expert_layers = self.cfg.num_layers - self.cfg.dense_lead_layers
        self._moe = (_ExpertLoad(tier.name, expert_layers,
                                 self.cfg.experts_held)
                     if expert_layers else None)
        # The hybrid family's recurrent rows: whose each is
        # (``_sync_state_owner``) and how many sequences started one.
        self._state_owner = (np.zeros(tier.decode_batch, np.int32)
                             if self.cfg.hybrid else None)
        self.state_resets_total = 0
        if tier.kv_pool_blocks is not None:
            # A constrained pool must still fit ONE largest-bucket prefill
            # plus a decode tick, or no request could ever admit.
            min_blocks = (max(b for b in tier.prefill_buckets
                              if b <= self.cfg.max_seq_len)
                          // tier.kv_block_size + 1)
            if tier.kv_pool_blocks < min_blocks:
                raise ValueError(
                    f"kv_pool_blocks={tier.kv_pool_blocks} cannot fit one "
                    f"largest-bucket prefill plus a decode tick (needs "
                    f">= {min_blocks} blocks of {tier.kv_block_size})")
        if params is None and tier.checkpoint_path:
            # Published tier weights win over random init (mirrors
            # InferenceEngine; EngineManager also pre-loads for its tiers).
            from ..utils.checkpoint import load_params_for_tier
            params = load_params_for_tier(tier.checkpoint_path, self.cfg,
                                          mesh=mesh, devices=self.devices)
        # An unsharded engine lives on the ONE device its tier was carved
        # (``devices[0]``), committed there: every program it runs takes
        # the weights or the pool, so its computation follows them.  Left
        # to the process default, every tier of a multi-chip host would
        # stack up on chip 0.
        own = (jax.sharding.SingleDeviceSharding(self.devices[0])
               if mesh is None and self.devices else None)
        if params is None and (self.cfg.latent or self.cfg.hybrid):
            # The seed is an ARGUMENT of the jitted maker: one compiled
            # program for every seed, and nothing folded at compile time.
            params = jax.jit(partial(models.init_params, self.cfg),
                             out_shardings=own)(jnp.int32(seed))
        if params is None:
            if mesh is not None:
                from ..parallel.sharding import param_shardings
                init = jax.jit(partial(models.init_params, self.cfg),
                               static_argnames=("seed",),
                               out_shardings=param_shardings(self.cfg, mesh))
            else:
                init = jax.jit(partial(models.init_params, self.cfg),
                               static_argnames=("seed",), out_shardings=own)
            params = init(seed=seed)
        from ..ops.quant import maybe_quantize
        self.params = maybe_quantize(params, tier, self.cfg, mesh=mesh)
        # Where this engine's pools rest — decided once here, applied by
        # _pool_program to every program that takes or returns a pool.  A
        # tensor-parallel tier shards the pool on its kv-head axis, so
        # every scatter/gather in decode_step_paged stays shard-local and
        # GSPMD's only collectives are the two per-layer matmul
        # all-reduces (same as the contiguous TP engine); pool-valued jit
        # outputs are pinned to that sharding — left unconstrained, XLA
        # may replicate the output pool, which silently multiplies KV
        # memory by the mesh size.  An unsharded pool lives with the
        # weights, and off the CPU a pool is donated to its programs.
        self._replicated = None
        home = own
        if mesh is not None:
            from ..parallel.sharding import kv_pool_shardings, replicated
            home = kv_pool_shardings(
                mesh, quantized=(tier.kv_quantize == "int8"))
            self._replicated = replicated(mesh)
            platform = mesh.devices.flat[0].platform
        else:
            first = jax.tree.leaves(self.params)[0]
            if home is None and isinstance(first, jax.Array):
                home = first.sharding
            platform = (next(iter(home.device_set)).platform
                        if home is not None else jax.default_backend())
        self._pool_donated = platform != "cpu"
        self._pool_home = home
        self.pool = self._new_pool(self.cfg, home)
        self._pool_formats = pool_formats(self.pool)
        self.allocator = BlockAllocator(self.paged.num_blocks)

        b, mb = self.paged.max_slots, self.paged.blocks_per_slot
        self._tables = np.full((b, mb), TRASH_BLOCK, np.int32)
        self._pos = np.zeros(b, np.int32)
        self._cur = np.zeros(b, np.int32)
        self._temps = np.zeros(b, np.float32)
        self._slots: List[Optional[_Slot]] = [None] * b
        # The tick's small inputs as the device holds them, by name
        # (``pos``, ``cur``, ``temps``): what the last tick returned or
        # ``prepare`` uploaded, absent once a writer other than the
        # plain emit has touched the mirror above (``_drop_carry``).
        # The mirrors stay the authority; ``prepare`` uploads what is
        # absent and nothing else.  Uploads and the key are COMMITTED
        # where the tick's outputs come out, so a tick fed from the host
        # and one fed from the last tick are one compiled program.
        self._carry: Dict[str, Any] = {}
        self._carry_home = (self._replicated if mesh is not None else
                            home if isinstance(home, jax.sharding.Sharding)
                            else None)
        if self._carry_home is not None:
            self._rng = jax.device_put(self._rng, self._carry_home)
        # Decode ticks launched, how many of them with no upload in
        # ``prepare`` (tick_stats' ``resident_share``), and the uploads
        # made there by what (dllm_tick_prepare_uploads_total).
        self.ticks_launched_total = 0
        self.ticks_resident_total = 0
        # Of the launched, those dispatched AHEAD of the fetch of the
        # tick before them (tick_stats' ``ahead_share``,
        # dllm_decode_ticks_ahead_total), and the steps such a tick
        # computed for a slot that an EOS/PAD in the tick before it had
        # ended: the whole waste of the ahead order.
        self.ticks_ahead_total = 0
        self.ahead_dead_slot_steps_total = 0
        self._ahead_sink: Any = None
        self.prepare_uploads_total: Dict[str, int] = {}
        self._prepare_upload_sinks: Dict[str, Any] = {}

        self._prefill_fns: Dict[Any, Any] = {}
        self._writer_fns: Dict[int, Any] = {}
        self._decode_fn = None
        self._buckets = sorted(set(
            b for b in tier.prefill_buckets if b <= self.cfg.max_seq_len))
        # Two ladders of attention windows for the chunk programs, one a
        # path, both block-aligned and both ending in the span (the
        # slot's whole table, which a chunk slid back against the
        # table's end takes):
        # - ``_reuse_windows``, the prefix-reuse SUFFIX chunk's (_admit),
        #   is COARSE, {256, 1024, span}: that chunk runs once per
        #   admission, so a wider gather costs one extra decode-tick's
        #   worth of reads, while a finer ladder multiplies the (reuse
        #   bucket, window) programs warm-up must cover in full — each
        #   miss is a mid-chat XLA trace on the admit path.
        # - ``_chunk_windows``, the chunked-prefill LANE's
        #   (_dispatch_chunk), DOUBLES from 1024 to the span: the lane
        #   runs a chunk every ``chunk_tokens`` of every long prompt, so
        #   the window is real reads and products there, and past 1024 a
        #   chunk attends less than twice what is written.  One more
        #   (chunk_tokens, window) program a doubling, all warmed.
        # The decode tick keeps the FINE bucket ladder (its gather runs
        # every tick).
        span = self.paged.blocks_per_slot * self.paged.block_size
        self._reuse_windows = _window_ladder(
            span, self.paged.block_size, doubling=False)
        self._chunk_windows = _window_ladder(
            span, self.paged.block_size, doubling=True)
        # Suffix buckets an admit will REUSE a prefix for: the first
        # three rungs cover typical chat turns; a longer new turn goes
        # through the (warmed) cold-prefill path instead of minting ever
        # more (sb, window) chunk programs.  Together with the coarse
        # window rungs this makes the warm set exhaustive — a prefix-hit
        # admission can never trace mid-chat.
        self._reuse_buckets = self._buckets[:3]

        # Disaggregated chunked prefill (ISSUE 9): a cold admission whose
        # prompt bucket exceeds one chunk no longer prefills in a single
        # monolithic call on the scheduler thread — it becomes the
        # in-flight _Prefill, advanced chunk-by-chunk between decode
        # ticks so TBT for active streams is bounded by one chunk.
        # Chunk size must page evenly (multiple of kv_block_size): the
        # compiled chunk-program family is keyed only by
        # (chunk, window-rung), the SAME bounded (bucket, window) keys
        # the prefix-reuse suffix chunks already mint, all funneled
        # through _note_compile's "chunk_prefill" stage.
        self.chunk_tokens = int(tier.prefill_chunk_tokens or 0)
        if self.chunk_tokens < 0 or (self.chunk_tokens
                                     and self.chunk_tokens
                                     % tier.kv_block_size):
            raise ValueError(
                f"prefill_chunk_tokens={tier.prefill_chunk_tokens} must be"
                f" a positive multiple of kv_block_size="
                f"{tier.kv_block_size} (chunks page evenly), or 0/None "
                f"to disable chunking")
        self.chunk_budget = max(self.chunk_tokens,
                                int(tier.prefill_chunk_budget or 0))
        self._prefill: Optional[_Prefill] = None
        # Cancel-and-requeue count over the engine's life (the prefill
        # twin of preempted_total; prefill_stats exposes it).
        self.prefill_cancelled_total = 0
        # Chunks dispatched over the engine's life, and how many of them
        # behind a tick whose tokens the host had not fetched yet
        # (``_ride_chunk``): how often the overlap engages
        # (prefill_stats, dllm_prefill_chunks_total).
        self.prefill_chunks_total = 0
        # Of those, the shared-K/V family's chunks that did not hold
        # their prompt's last token: run to the one cached layer's K/V
        # write and no deeper (models/shared_kv_hybrid.py).
        self.prefill_self_only_chunks_total = 0
        self.prefill_chunks_overlapped_total = 0
        # Positions the lane's chunks attended (their window rungs) over
        # positions written when each ran (its end, capped at the
        # prompt): how close the rung ladder keeps a chunk's gather to
        # what is there (prefill_stats' ``window_over_written``).
        self.prefill_window_positions_total = 0
        self.prefill_written_positions_total = 0
        # The lane's chunks by the window rung each ran at
        # (prefill_stats' ``chunks_by_window``,
        # dllm_prefill_chunks_by_window_total): a long prompt's chunks
        # laid against the ladder.
        self.prefill_chunks_by_window: Dict[int, int] = {}
        # ... and by what their program's latent attention was traced
        # with (``chunk_attention_form``): how often the kernel engages.
        self.prefill_chunks_by_form: Dict[str, int] = {}
        # perf_counter of the last plain tick's fetch return: the
        # latest moment the host saw the device reach whatever was
        # queued behind that tick (``_settle_chunk``'s clock).
        self._tick_fetched_t = 0.0

        # Session prefix reuse over pool blocks: a finished request's
        # prompt blocks are parked (ownership moves to the store) and a
        # later prompt extending it chunk-prefills only the suffix into
        # fresh blocks.  Evicted entries return their blocks via on_evict
        # (a refcounted decref: blocks still mapped by live sharers or a
        # longer parked entry stay resident).  The batch refcount reader
        # keeps reclaimable accounting honest under sharing.
        from .prefix_cache import PrefixCache
        self.prefix_cache = (
            PrefixCache(capacity=tier.prefix_cache_entries,
                        on_evict=self._prefix_evicted,
                        block_refcounts=self.allocator.refcounts)
            if tier.enable_prefix_cache and tier.prefix_cache_entries > 0
            else None)
        # Hierarchical KV spill tier (ISSUE 14, engine/kv_spill.py): a
        # host-RAM LRU under the device prefix cache.  Eviction of an
        # unpinned sole-owner entry DEMOTES it (async snapshot + copier
        # drain, see _try_demote); a later hit PROMOTES it back through
        # the chunked-prefill lane (_advance_promotion).  Requires the
        # chunk machinery — promotion grants ride its per-tick budget.
        self.kv_spill = None
        self._spill_fns: Dict[Any, Any] = {}
        self._spill_block_bytes = 0
        from ..config_registry import env_int
        host_kv_bytes = env_int("DLLM_HOST_KV_BYTES",
                                int(tier.host_kv_bytes or 0))
        if host_kv_bytes > 0 and self.prefix_cache is not None:
            if not self.chunk_tokens:
                logger.warning(
                    "tier %s: host_kv_bytes=%d ignored — the KV spill "
                    "tier needs chunked prefill (prefill_chunk_tokens) "
                    "to absorb promotion grants", tier.name, host_kv_bytes)
            else:
                from .kv_spill import HostKVSpill
                from .paged_kv import pool_block_bytes
                self._spill_block_bytes = pool_block_bytes(
                    self.cfg, tier.kv_block_size, tier.kv_quantize)
                self.kv_spill = HostKVSpill(
                    budget_bytes=host_kv_bytes,
                    block_bytes=self._spill_block_bytes,
                    copier_depth=tier.host_kv_copier_depth,
                    min_prefix=self.prefix_cache.min_prefix,
                    tier=tier.name)
        # Promotion stall bound, in scheduler passes: a claimed entry
        # whose demote copy never lands (wedged copier) must not park
        # the prefill lane forever — past this many stalled passes the
        # promotion aborts to a cold prefill (the race-fallback
        # contract, counted as a race).
        self._promote_wait_cap = 2000
        # Cross-request shared-prefix KV (ISSUE 10): a cache hit PINS the
        # parked entry and maps its full blocks read-only into the new
        # slot's table (copy-on-write at the mid-block boundary) instead
        # of taking exclusive ownership — N concurrent same-prefix
        # sessions hold ONE physical copy.  OFF restores the exclusive
        # take semantics exactly.
        self.share_prefix = bool(tier.share_prefix_kv
                                 and self.prefix_cache is not None)
        self._cow_fn = None
        # Batched speculative decoding (ISSUE 15): a small per-tier
        # draft model rides the SAME block tables as the target — its
        # own paged pool, indexed by the same block ids, so slot/block
        # lifecycle (admission, growth, parking, preemption, COW) is
        # bookkept once.  Each speculative tick drafts γ tokens per
        # slot (one scanned device call on the draft), verifies all
        # slots' chunks in ONE fused ragged_verify call on the target,
        # applies per-slot greedy acceptance, and rewinds rejected
        # tails' block frontiers.  Draft KV quality only moves the
        # acceptance rate — byte-identity to plain greedy decode is the
        # verify rule's, never the draft's.
        self.spec = False
        self.cfg_d = None
        self.params_d = None
        self.pool_d = None
        self._cow_fn_d = None
        self.spec_gamma_max = max(1, int(tier.spec_gamma_max))
        self._spec_fns: Dict[Any, Any] = {}
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        # Per-SLOT-INDEX lifetime draft/accept accumulators (bounded by
        # max_slots): the bench spec leg reports per-slot acceptance so
        # a skewed mix's low-acceptance tenant is visible next to the
        # aggregate ratio.
        self._spec_slot_acc: Dict[int, List[int]] = {}
        self._pool_home_d = None
        if tier.spec_decode and self._resolve_spec():
            self.spec = True
            dcfg = tier.draft_model()
            self.cfg_d = upgrade_attention_impl(dcfg, mesh)
            if tier.draft_preset == tier.model_preset:
                # Self-draft: the draft IS the target (weights shared,
                # zero extra parameter memory) — acceptance approaches
                # 1.0 and the tick's win is the fused γ+1-token verify
                # amortizing the per-tick dispatch.  The bench's spec
                # leg measures this configuration; a genuinely smaller
                # draft_preset swaps in transparently.  Under a TP mesh
                # the shared weights are the SHARDED weights, so the
                # draft rounds run through the same shard-mapped ragged
                # hook as the tick (PR 16).
                self.params_d = self.params
                self._pool_home_d = self._pool_home
            else:
                init_d = jax.jit(partial(models.init_params, self.cfg_d),
                                 static_argnames=("seed",), out_shardings=own)
                from ..ops.quant import maybe_quantize as _mq
                self.params_d = _mq(init_d(seed=seed + 1), tier, self.cfg_d)
                if mesh is not None:
                    # A genuinely smaller draft stays REPLICATED: each
                    # chip drafts the whole batch locally (its params
                    # are small by construction) and only the verify is
                    # sharded — no draft-side collectives, and the COW /
                    # rewind bookkeeping sees one draft pool image.
                    self.params_d = jax.device_put(self.params_d,
                                                   self._replicated)
                    self._pool_home_d = self._replicated
                else:
                    self._pool_home_d = self._pool_home
            # Draft pool: same geometry (block count/size) as the target
            # pool so the target's block tables index it directly.
            self.pool_d = self._new_pool(self.cfg_d, self._pool_home_d)
            from ..utils import roofline as _roofline
            self._wbytes_d = _roofline.weight_bytes(self.cfg_d,
                                                    tier.quantize)
        # Bounded γ program family: powers of two up to spec_gamma_max
        # (plus the max itself) — a speculative tick buckets the active
        # slots' max γ up to one of these, so the compiled draft/verify
        # program count is the bucket count, never per-γ or
        # per-acceptance-length.
        gmax = self.spec_gamma_max
        self._gamma_buckets = tuple(sorted(
            {1 << i for i in range(gmax.bit_length()) if (1 << i) <= gmax}
            | {gmax}))
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        # Scheduler-head requeue lane: KV-pressure deferrals and preempted
        # requests go back to the FRONT (appendleft), so a starved elder
        # re-admits before newer arrivals.  Only the scheduler thread pops
        # (GIL-safe deque ops; stop() drains it after joining the loop).
        self._head: "deque[_Request]" = deque()
        self._admit_seq = 0
        # Per-tenant scheduling state (ISSUE 17).  None = quotas OFF:
        # _next_request/_ensure_growth/_release/_slot_go_live all take
        # their exact pre-tenant paths (byte-identity contract, pinned
        # by tests).  When ON, _queue drains into per-tenant FIFO lanes
        # and admission order is deficit-weighted round-robin over them
        # (weights from the quota table); the head lane stays absolute-
        # first either way.  Scheduler-thread-only state.
        self._tenant_quotas = (dict(tier.tenant_quotas)
                               if tier.tenant_quotas is not None else None)
        self._tenant_default_q = None
        if self._tenant_quotas is not None:
            from ..serving.tenants import default_quota
            self._tenant_default_q = default_quota()
        self._tenant_lanes: Dict[str, "deque[_Request]"] = {}
        self._tenant_deficits: Dict[str, float] = {}
        # Mid-decode preemptions performed over this engine's life (the
        # chaos leg and tests read it; the obs counter mirrors it).
        self.preempted_total = 0
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lifecycle = threading.Lock()   # guards start()/stop()
        # Decode-watchdog heartbeat: the monotonic time of the last
        # COMPLETED unit of scheduler progress (an admission's prefill, a
        # decode tick's fanout, or an idle pass with nothing to do).  A
        # wedged device call (the round-5 failure mode) leaves the loop
        # stuck inside block_until_ready, so this goes stale while work
        # is pending — progress_stall_s() is the observable signal
        # EngineManager.health() and the HealthMonitor's watchdog read.
        # Single-word float write/read, GIL-safe.
        self._progress_t = time.monotonic()

        # Per-phase wall-time + roofline work (GET /stats, bench MFU/HBM
        # accounting — utils/telemetry.py, utils/roofline.py).  Only the
        # scheduler thread writes; snapshots from other threads read
        # whole-dict summaries, safe under the GIL.
        from ..utils.telemetry import PhaseTimer
        from ..utils import roofline
        self.phases = PhaseTimer()
        self._wbytes = roofline.weight_bytes(self.cfg, tier.quantize)
        # The decode ticks' roofline work is COUNTED on the tick and
        # computed when /stats asks (_account_tick,
        # _tick_work_estimate): {(kind, window, slots, γ bucket):
        # [ticks, Σ mean KV span]}, scheduler-thread writes only.
        self._tick_work: Dict[tuple, List[float]] = {}
        self.phases.lazy_work = self._tick_work_estimate
        # The tick's shape, plain and speculative (fixed for the
        # engine's life: ``ragged_*`` the fused tick, ``paged_decode``
        # the windowed one) and, per (kind, window rung), what serves
        # its attention with its metric children.
        q8 = "_q8" if tier.kv_quantize == "int8" else ""
        self._tick_kind = ("ragged_decode" if self.ragged
                           else "paged_decode") + q8
        self._tick_kind_spec = "ragged_verify" + q8
        self._tick_sink_cache: Dict[tuple, tuple] = {}

    # What a model family does not run is refused at build, by name,
    # rather than run wrong.  The latent family's pool has no heads to
    # quantize by or shard on, and no draft or spill path was written for
    # its rows.  The hybrid family keeps a recurrent row a slot beside
    # its K/V blocks: whatever rewinds or shares BY POSITION (a prefix
    # hit, a shared prefix, a spilled block, a draft's rejected tail)
    # would part a sequence from its state, and its state has no shards.
    # The shared-K/V family's rings are rows of the same kind (and a
    # chunk longer than a ring would write a slot twice).  Keyed by
    # ``ModelConfig.family``.
    _FAMILY_REFUSALS = {
        "latent": ("latent-attention",
                   ("kv_quantize", "tp", "draft_preset", "host_kv_bytes")),
        "shared_kv": ("shared-K/V hybrid",
                      ("kv_quantize", "tp", "draft_preset", "host_kv_bytes",
                       "enable_prefix_cache", "prefill_chunk_tokens")),
        "hybrid": ("state-space hybrid",
                   ("kv_quantize", "tp", "draft_preset", "host_kv_bytes",
                    "enable_prefix_cache", "prefill_chunk_tokens")),
    }

    def _refuse_unsupported(self, tier: TierConfig, mesh) -> None:
        family = self.cfg.family
        if family not in self._FAMILY_REFUSALS:
            return
        from ..config_registry import env_int
        span = -(-self.cfg.max_seq_len // tier.kv_block_size) \
            * tier.kv_block_size
        chunk = int(tier.prefill_chunk_tokens or 0)
        ring = self.cfg.attn_window     # a window layer's ring a slot, or 0
        on = {
            "kv_quantize": ("kv_quantize='int8'",
                            tier.kv_quantize != "none"),
            "tp": ("a tensor-parallel mesh (tp > 1)", mesh is not None),
            "draft_preset": ("draft_preset (speculative decoding)",
                             bool(tier.draft_preset)),
            "host_kv_bytes": ("host_kv_bytes (KV spill)", env_int(
                "DLLM_HOST_KV_BYTES", int(tier.host_kv_bytes or 0)) > 0),
            "enable_prefix_cache": (
                "enable_prefix_cache (prefix reuse and share_prefix_kv)",
                bool(tier.enable_prefix_cache
                     and tier.prefix_cache_entries > 0)),
            # Every prompt goes through the chunk program, which carries
            # the state; a chunk slid back at the table's end would feed
            # its overlap to the state twice.
            "prefill_chunk_tokens": (
                f"prefill_chunk_tokens={chunk} (it needs a chunk that "
                f"divides the slot's span of {span}"
                + (f" and fits the window's ring of {ring}" if ring else "")
                + ")",
                chunk <= 0 or span % chunk != 0 or 0 < ring < chunk),
        }
        name, keys = self._FAMILY_REFUSALS[family]
        bad = [on[key][0] for key in keys if on[key][1]]
        if bad:
            raise ValueError(
                f"tier {tier.name}: model {self.cfg.name} is of the "
                f"{name} family, which does not support {', '.join(bad)}")

    def _new_pool(self, cfg, home):
        """A zeroed paged pool for ``cfg``, made in place on the engine's
        device(s) ``home`` (one sharding, or a dict of them under a
        mesh; None: wherever jax puts it)."""
        make = partial(init_pool, cfg, self.paged, self.tier.kv_quantize)
        if home is None:
            return make()
        return jax.jit(make, out_shardings=home)()

    def _pool_program(self, fn, pool_arg: int, lead: int = 0,
                      draft: bool = False):
        """jit ``fn``, a program that takes a pool as argument
        ``pool_arg`` and returns it after ``lead`` small outputs.  The
        one place that knows how a pool is placed: it comes out where it
        rests (``_pool_home``) and, off the CPU, is donated — so XLA
        aliases the output to the input and the program updates the one
        buffer in place."""
        home = self._pool_home_d if draft else self._pool_home
        kw = {}
        if home is not None:
            kw["out_shardings"] = ((self._replicated,) * lead + (home,)
                                   if lead else home)
        if self._pool_donated:
            kw["donate_argnums"] = (pool_arg,)
        return jax.jit(fn, **kw)

    def _resolve_ragged(self) -> bool:
        """Whether the decode tick is the FUSED one (every slot's full
        table row, one program for the engine's life) or the WINDOWED one
        (tables cut to a bucketed rung, a program a rung).  In order: the
        latent and hybrid families and a mesh the shard-mapped hook cannot
        serve (``_tp_ragged_ok``) are windowed; ``DLLM_RAGGED`` forces
        either; ``TierConfig.attention_ragged`` False is windowed; then
        off the TPU fused, on it windowed.  The fused tick's gather spans
        the whole table whatever the slots hold, and it was never
        measured better on the chip; off it the rung ladder's compiles
        cost more than the tiny gather.  Tests and the chip so run
        different ticks: ROADMAP D3."""
        if self.cfg.latent or self.cfg.hybrid:
            return False
        if self.mesh is not None:
            from ..parallel.tp_attention import _tp_ragged_ok
            if not _tp_ragged_ok(self.mesh, self.cfg):
                return False
        from ..config_registry import env_str
        raw = env_str("DLLM_RAGGED")
        if raw is not None and raw not in ("0", "1"):
            raise ValueError(f"DLLM_RAGGED={raw!r}: expected '0' or '1'")
        if raw is not None:
            return raw == "1"
        return (self.tier.attention_ragged
                and jax.default_backend() != "tpu")

    def _resolve_spec(self) -> bool:
        """Whether ``TierConfig.spec_decode`` can actually arm batched
        speculation on this engine.  Requirements, each logged when it
        blocks: a ``draft_preset`` (the drafting model — the target's
        own preset is the zero-extra-weights self-draft), the fused
        ragged tick (the verify call takes every slot's full table
        row; the dense windowed tick has no verify shape — a TP mesh
        qualifies exactly when its tick went ragged, PR 16), a greedy
        tier default (per-REQUEST
        temperature>0 just degrades that slot to γ=0; a sampled tier
        default would degrade every slot, so it reads as
        misconfiguration), and a draft context covering the target's
        (positions are the target's)."""
        tier = self.tier
        if not tier.draft_preset:
            logger.warning("tier %s: spec_decode=True ignored — no "
                           "draft_preset configured", tier.name)
            return False
        if not self.ragged:
            logger.warning(
                "tier %s: spec_decode=True ignored — batched speculation "
                "needs the fused ragged tick (ragged=%s, mesh=%s)",
                tier.name, self.ragged, self.mesh is not None)
            return False
        if (tier.temperature or 0) > 0:
            logger.warning(
                "tier %s: spec_decode=True ignored — the tier default "
                "temperature=%s would degrade every slot to γ=0 "
                "(speculation is greedy-exact; per-request sampling "
                "rides the verify's sampled first row)",
                tier.name, tier.temperature)
            return False
        dcfg = tier.draft_model()
        if dcfg.vocab_size != self.cfg.vocab_size:
            logger.warning(
                "tier %s: spec_decode=True ignored — draft_preset=%s "
                "vocab %d != target vocab %d",
                tier.name, tier.draft_preset, dcfg.vocab_size,
                self.cfg.vocab_size)
            return False
        if dcfg.max_seq_len < self.cfg.max_seq_len:
            logger.warning(
                "tier %s: spec_decode=True ignored — draft_preset=%s "
                "max_seq_len %d < target %d (drafts run at the "
                "target's positions)",
                tier.name, tier.draft_preset, dcfg.max_seq_len,
                self.cfg.max_seq_len)
            return False
        return True

    def _tp_degree(self) -> int:
        """Tensor-parallel degree of this engine's mesh (1 unsharded) —
        part of every decode/draft/verify program-family key, so a tp=2
        engine's programs never alias a tp=1 engine's in the compiled-
        program accounting (ISSUE 16)."""
        if self.mesh is None:
            return 1
        return dict(self.mesh.shape).get("tp", 1)

    def _gamma_bucket(self, g: int) -> int:
        """Smallest registered γ bucket covering ``g`` — the static
        q-length the speculative tick compiles at (runtime per-slot γ
        caps acceptance INSIDE the program, so slot-level adaptation
        never mints a new one)."""
        return next(b for b in self._gamma_buckets if b >= g)

    def _adapt_gamma(self, ewma: float, cap: Optional[int] = None) -> int:
        """Acceptance EWMA → the slot's next γ: proportional scaling
        with a floor at 0 (degrade to plain ragged decode — the verify's
        first row only) once acceptance stops paying for draft FLOPs.
        ``cap`` is the tenant γ clamp (quotas ON; None = unclamped)."""
        gmax = (self.spec_gamma_max if cap is None
                else min(cap, self.spec_gamma_max))
        if gmax <= 0 or ewma < SPEC_EWMA_FLOOR:
            return 0
        return max(1, min(gmax, int(ewma * gmax + 0.5)))

    def _tenant_gamma_cap(self, req: Optional[_Request]) -> Optional[int]:
        """The tenant's speculative-γ clamp, or None (no clamp — quotas
        off, or the tenant's quota leaves spec_gamma_max unset)."""
        if self._tenant_quotas is None or req is None:
            return None
        q = self._tenant_quota(req.tenant)
        cap = q.spec_gamma_max if q is not None else None
        if cap is None:
            return None
        return max(0, min(int(cap), self.spec_gamma_max))

    # -- compiled stages ---------------------------------------------------

    def _note_compile(self, stage: str, key) -> None:
        """Record a NEW compiled program for ``stage`` (prefill bucket,
        chunk (bucket, window), pool writer, decode table width): logs the
        growth — warmup cost must be visible, a mid-serve compile stalls
        every active slot — and mirrors the per-stage count to the
        ``dllm_compiled_programs`` gauge.  The ragged decode tick pins the
        decode stage at ONE program; the dense rung ladder grows it per
        (bucket, window) rung crossed."""
        seen = self._compiled.setdefault(stage, set())
        if key in seen:
            return
        seen.add(key)
        if stage == "decode":
            window = key[0] * self.paged.block_size
            self._attention_forms[str(window)] = (
                self.decode_attention_form(window))
        if stage == "chunk_prefill" and self.cfg.kv_lora_rank:
            self._chunk_attention_forms[key] = (
                self.chunk_attention_form(*key))
        # Stitch the compile onto the profiler timeline: a mid-serve
        # trace stalls every active slot, and the tick record it lands
        # next to shows exactly which tick paid for it.
        self.profiler.event("compile", stage=stage, key=str(key))
        logger.info(
            "tier %s: compiling %s program %r (%d %s programs so far)",
            self.tier.name, stage, key, len(seen), stage)
        try:
            from ..obs import get_observability
            get_observability().m.compiled_programs.labels(
                self.tier.name, stage).set(len(seen))
        except Exception:
            pass

    def _prefill_fn(self, bucket: int):
        """Per bucket: forward the padded prompt, return the first sampled
        token and the per-layer K/V to page into the pool.  TP meshes take
        the shard-mapped flash prefill where Pallas is preferred
        (parallel/tp_attention.py), same policy as the sequential engine."""
        if bucket in self._prefill_fns:
            return self._prefill_fns[bucket]
        self._note_compile("prefill", bucket)
        cfg = self.cfg
        from ..parallel.tp_attention import tp_prefill_attn
        attn = tp_prefill_attn(self.mesh, cfg, bucket)

        def cold_prefill(params, tokens, true_len, rng, temp):
            b, s = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            hidden, rows = models.serving_prefill(
                cfg, params, tokens, positions, attn=attn)
            last = hidden[jnp.arange(b), true_len - 1]
            logits = transformer.logits_from_hidden(params, last)
            first = _sample_batched(logits, rng, temp[None])[0]
            # One array a pool array (K and V, or the latent family's
            # one), batch squeezed: what ``write_prefill_blocks`` takes.
            return (first,) + tuple(r[:, 0] for r in rows)

        fn = jax.jit(cold_prefill)
        self._prefill_fns[bucket] = fn
        return fn

    def _tick_attn_hook(self):
        """The decode tick's attention hook, or None for
        ``attention.paged_decode`` over the whole pool.  A TP tier's
        fused tick wraps that op in shard_map over the kv-head axis
        (PR 16: combine is a head concat); its windowed tick has no hook
        (the GSPMD XLA path)."""
        if self.cfg.num_experts != 1 or not self.ragged:
            return None
        from ..parallel.tp_attention import tp_ragged_decode_attn
        return tp_ragged_decode_attn(
            self.mesh, self.cfg, quantized=self.tier.kv_quantize == "int8")

    def decode_attention_form(self, window: Optional[int] = None) -> str:
        """What this tier's decode tick attends at a table window of
        ``window`` tokens (its full span by default) — a label, static
        per compiled rung (chip_smoke.py's what-ran lines, GET /stats):
        ``streamed`` the whole token-major pool read through the block
        table where it rests (``ops/rows_attention.py``), ``merged`` the
        XLA gather of the same pool's rows and
        ``ops.attention.merged_decode_attention``
        (``ops.attention.decode_form`` is the rule between the two),
        ``split`` a tp hook over a layer's head-major view, ``latent``
        the latent family's own absorbed attention (the hybrid family's
        "L" layers' too: whoever caches a latent row)."""
        if self.cfg.kv_lora_rank:
            return "latent"
        if self.cfg.shared_kv:
            return "merged"        # its own (shared_kv_hybrid.diff_merged)
        if self._tick_attn_hook() is not None:
            return "split"
        from ..ops import attention as attn_ops
        bs = self.paged.block_size
        span = self.paged.blocks_per_slot * bs
        window = span if window is None or self.ragged else window
        quantized = self.tier.kv_quantize == "int8"
        return attn_ops.decode_form(
            self.cfg.attention_impl, self.cfg.num_heads,
            self.cfg.head_dim, window // bs, bs,
            self.cfg.num_kv_heads * self.cfg.head_dim,
            jnp.int8 if quantized else self.cfg.dtype)

    def chunk_attention_form(self, chunk: int, window: int) -> Optional[str]:
        """What the chunk program of ``chunk`` tokens over a table window
        of ``window`` attends its latent rows with: ``blocks`` (the kernel
        of ``ops/latent_chunk_attention.py``) or ``plain`` (``einsum`` +
        ``softmax`` over the whole up-projected window), the static test
        ``latent_moe._attend`` makes on the program's shapes; None for a
        family that caches no latent row.  A fact of each compiled
        program, like ``decode_attention_form``."""
        if not self.cfg.kv_lora_rank:
            return None
        rows = self.pool["c"]
        return models.latent_moe.chunk_attention_form(
            self.cfg, chunk, window, rows.shape[-1], rows.dtype)

    def _decode_step(self):
        """One compiled tick for all slots: ``decode_steps_per_tick``
        sequential decode steps inside a single device call (lax.scan), so
        the host↔device round trip is amortized over T tokens per slot.
        Returns tokens [T, B]; the host applies budget/EOS per slot and
        discards the ≤T-1 overshoot a mid-tick finisher decodes (its writes
        land in its own still-allocated blocks, freed on finish).

        ``rng`` is the ENGINE's key: the tick splits it as the host did
        before each launch (the same ``jax.random.split``, so the stream
        is the same), steps on one half and returns the other.  Beside
        the tokens it returns what the next tick starts from — ``pos``
        and ``cur`` after the last step, and that key — which the engine
        keeps on the device (``_run_scheduler``): nothing of it is
        fetched."""
        if self._decode_fn is not None:
            return self._decode_fn
        cfg = self.cfg
        max_pos = cfg.max_seq_len - 1
        steps = self.steps_per_tick
        moe_counts = self._moe is not None
        attn = self._tick_attn_hook()

        def decode_tick(params, pool, tables, pos, cur, temps, rng):
            def step(carry, _):
                pool, pos, cur, rng = carry
                logits, pool, *n_exp = decode_step_paged(
                    cfg, params, cur, pos, pool, tables, attn=attn,
                    counts=moe_counts)
                with jax.named_scope("sample"):
                    rng, sub = jax.random.split(rng)
                nxt = _sample_batched(logits, sub, temps)
                # Clamp: finished/overshooting slots keep writing into
                # their own last cell instead of indexing past the table.
                return ((pool, jnp.minimum(pos + 1, max_pos), nxt, rng),
                        (nxt, *n_exp))

            with jax.named_scope("sample"):
                key, rng = jax.random.split(rng)
            with jax.named_scope("step_scan"):
                (pool, pos, cur, _), toks = jax.lax.scan(
                    step, (pool, pos, cur, rng), None, length=steps)
            # A slot that holds no sequence (its row starts at the trash
            # block) starts every tick where the host's mirrors keep
            # it: position 0, token 0.
            live = tables[:, 0] != TRASH_BLOCK
            # [T, B] tokens and, for a routed-expert model, the steps'
            # assignments an expert [T, expert layers, E]: one fetch.
            # Then the next tick's inputs, which stay on the device.
            return ((toks if moe_counts else toks[0]),
                    jnp.where(live, pos, 0), jnp.where(live, cur, 0),
                    key), pool

        self._decode_fn = self._pool_program(decode_tick, 1, lead=1)
        return self._decode_fn

    def _chunk_prefill_fn(self, bucket: int, window: int):
        """Per (suffix bucket, window): chunk-prefill a reclaimed prefix's
        extension straight into pool blocks and sample the first token."""
        key = ("chunk", bucket, window)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        self._note_compile("chunk_prefill", (bucket, window))
        cfg = self.cfg
        moe_counts = self._moe is not None

        def chunk_prefill(params, pool, tokens, start, true_len, table,
                          rng, temp):
            hidden, pool, *n_exp = chunk_prefill_paged(
                cfg, params, tokens, start, true_len, pool, table, window,
                counts=moe_counts)
            last = hidden[0, true_len[0] - start[0] - 1]
            logits = transformer.logits_from_hidden(params, last)
            first = _sample_batched(logits[None], rng, temp[None])[0]
            return ((first, n_exp[0][None]) if moe_counts else first), pool

        fn = self._pool_program(chunk_prefill, 1, lead=1)
        self._prefill_fns[key] = fn
        return fn

    def lower_pool_program(self, stage: str, key=None, pool=None):
        """One of the engine's OWN pool programs, lowered on abstract
        arguments against ``pool`` where it lives: the engine's pool (the
        default: its shapes only), or the shapes of a pool on a described
        chip (``jax.eval_shape(init_pool)``, as tests/test_tpu_compile.py
        builds it beside a tiny engine).
        ``stage`` ``"decode"`` takes the table window in blocks,
        ``"chunk_prefill"`` (chunk, window) in tokens, ``"copy_block"``
        nothing.  The ONE place that spells these programs' argument
        lists outside the scheduler.  Nothing runs."""
        pool = self.pool if pool is None else pool
        home = jax.tree.leaves(pool)[0].sharding

        def arg(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=home)

        pool = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding), pool)
        b, mb = self.paged.max_slots, self.paged.blocks_per_slot
        if stage == "decode":
            return self._decode_step().lower(
                self.params, pool, arg((b, key)), arg((b,)), arg((b,)),
                arg((b,), jnp.float32), arg((2,), jnp.uint32))
        if stage == "chunk_prefill":
            chunk, window = key
            return self._chunk_prefill_fn(chunk, window).lower(
                self.params, pool, arg((1, chunk)), arg((1,)), arg((1,)),
                arg((mb,)), arg((2,), jnp.uint32), arg((), jnp.float32))
        if stage == "copy_block":
            return self._cow_copy_fn().lower(pool, arg(()), arg(()))
        raise ValueError(f"no pool program of stage {stage!r}")

    def compile_pool_program(self, stage: str, key=None, pool=None):
        """``lower_pool_program``, compiled; for a program the engine has
        served that is a hit of the persistent compile cache."""
        return self.lower_pool_program(stage, key, pool).compile()

    def step_programs(self, stage: Optional[str] = None,
                      window_tokens: Optional[Sequence[int]] = None,
                      ops: bool = True) -> List[Dict[str, Any]]:
        """What GET /debug/programs says of this engine: for every decode
        tick and chunk program ``_note_compile`` has recorded (of
        ``stage`` and of the ``window_tokens`` given, where they are), its
        stage, its name on a device trace's module line, its table window
        (and chunk) in tokens, the tick's attention form and, with
        ``ops``, the seconds the entry took to build (lowering, compiling,
        reading the text), ``pool_sized_moves`` (what of the pool the
        compiled program copies: ``{}`` is nothing) and ``ops``: the named
        scope of every operation a trace of it can show
        (``obs/program_scopes.py``).

        Each program is compiled again for that (``lower_pool_program``)
        the FIRST time it is asked about with ``ops`` and kept by its key
        (those a request lacks are built side by side); nothing on the
        warm-up, admission or tick path comes here.  The scopes stand in
        the executable's metadata, which JAX leaves out of the persistent
        compile cache's key: the served programs' entries may have been
        compiled from a tree that named its scopes otherwise.  So this
        compile keys the cache WITH the metadata
        (``jax_compilation_cache_include_metadata_in_key``): entries of
        its own, found again by the next process that runs this very
        code.  No runtime this was tried on gives what stands in the
        parentheses of a trace's module name (``jit_decode_tick(<n>)``): a
        reader joins a traced program to its entry by the operations both
        name."""
        bs = self.paged.block_size
        wanted = []
        for st in ("decode", "chunk_prefill"):
            for key in sorted(self._compiled.get(st, ())):
                window = key[0] * bs if st == "decode" else key[1]
                if stage in (None, st) and (window_tokens is None
                                            or window in window_tokens):
                    wanted.append((st, key, window))
        missing = [w for w in wanted if w[:2] not in self._program_maps]
        if ops and missing:
            with concurrent.futures.ThreadPoolExecutor(
                    min(8, len(missing))) as workers:
                for (st, key, _), built in zip(missing, workers.map(
                        lambda w: self._program_map(*w[:2]), missing)):
                    self._program_maps[st, key] = built
        out = []
        for st, key, window in wanted:
            tick = st == "decode"
            entry = {"stage": st,
                     "program": "jit_decode_tick" if tick
                     else "jit_chunk_prefill",
                     "window_tokens": window,
                     "chunk_tokens": None if tick else key[0],
                     "attention_form": (self.decode_attention_form(window)
                                        if tick else
                                        self.chunk_attention_form(*key))}
            if ops:
                entry.update(self._program_maps[st, key])
            out.append(entry)
        return out

    def _program_map(self, stage: str, key) -> Dict[str, Any]:
        """``{"built_s", "pool_sized_moves", "ops"}`` of one recorded
        program."""
        from jax._src.config import (
            compilation_cache_include_metadata_in_key as keyed_by_metadata)
        from ..obs.program_scopes import op_scopes, pool_sized_moves
        stamps = [time.perf_counter()]
        lowered = self.lower_pool_program(
            stage, key[0] if stage == "decode" else key)
        stamps.append(time.perf_counter())
        with keyed_by_metadata(True):
            compiled = lowered.compile()
        stamps.append(time.perf_counter())
        text = compiled.as_text()
        ops = op_scopes(text)
        moves = pool_sized_moves(text, self.pool)
        stamps.append(time.perf_counter())
        return {"built_s": dict(zip(("lower", "compile", "read"),
                                    (b - a for a, b in
                                     zip(stamps, stamps[1:])))),
                "pool_sized_moves": moves, "ops": ops}

    def _writer_fn(self, nb: int):
        """Jitted pool scatter (donated pool → in-place page-in), one
        compile per prefill block count."""
        if nb not in self._writer_fns:
            self._note_compile("writer", nb)
            self._writer_fns[nb] = self._pool_program(
                write_prefill_blocks, 0)
        return self._writer_fns[nb]

    def _cow_copy_fn(self):
        """Jitted one-block COW copy (``paged_kv.copy_block``): ONE
        compiled program for every (src, dst) pair — the block ids are
        traced scalars, so the copy rides the bounded block-write
        program family like the prefill writers instead of minting a
        per-pair program on the admit path (the retrace-lint fixture
        pair in tests/test_lint.py pins the idiom)."""
        if self._cow_fn is None:
            from .paged_kv import copy_block
            self._note_compile("writer", "cow_copy")
            self._cow_fn = self._pool_program(copy_block, 0)
        return self._cow_fn

    def _cow_copy_fn_d(self):
        """Draft-pool twin of ``_cow_copy_fn``: the COW boundary copy
        must land in BOTH pools (the draft attends the same block
        tables), and the draft pool's layer/head shape differs, so it
        is its own single compiled program in the same bounded
        block-write family."""
        if self._cow_fn_d is None:
            from .paged_kv import copy_block
            self._note_compile("writer", "cow_copy_draft")
            self._cow_fn_d = self._pool_program(copy_block, 0, draft=True)
        return self._cow_fn_d

    def _draft_prefill_fn(self, bucket: int):
        """Per bucket: the DRAFT model's prompt forward — K/V only, no
        sampling (the target's prefill picks the first token; the draft
        just needs its own prefix KV to draft against).  Same bounded
        per-bucket family as the target prefill, under the "draft"
        compile stage."""
        key = ("draft_prefill", bucket)
        if key in self._spec_fns:
            return self._spec_fns[key]
        self._note_compile("draft", ("prefill", bucket))
        cfg_d = self.cfg_d
        from ..parallel.tp_attention import tp_prefill_attn
        attn = tp_prefill_attn(None, cfg_d, bucket)

        def draft_prefill(params_d, tokens):
            b, s = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            _, (k_all, v_all) = models.serving_prefill(
                cfg_d, params_d, tokens, positions, attn=attn)
            return k_all[:, 0], v_all[:, 0]              # squeeze batch
        fn = jax.jit(draft_prefill)
        self._spec_fns[key] = fn
        return fn

    def _draft_writer_fn(self, nb: int):
        """Draft-pool prefill scatter: one compile per prefill block
        count, like the target's ``_writer_fn`` (the draft pool's shape
        differs, so the programs are siblings, not shared)."""
        key = ("draft_writer", nb)
        if key not in self._spec_fns:
            self._note_compile("draft", ("writer", nb))
            self._spec_fns[key] = self._pool_program(
                write_prefill_blocks, 0, draft=True)
        return self._spec_fns[key]

    def _draft_chunk_fn(self, bucket: int, window: int):
        """Per (suffix bucket, window): seed the DRAFT pool for a
        prefix-reuse admission's suffix — the draft twin of
        ``_chunk_prefill_fn``, K/V writes only (sample discarded), so a
        shared/exclusive prefix hit stays speculation-eligible instead
        of drafting against a garbage suffix."""
        key = ("draft_chunk", bucket, window)
        if key in self._spec_fns:
            return self._spec_fns[key]
        self._note_compile("draft", ("chunk", bucket, window))
        cfg_d = self.cfg_d

        def draft_chunk(params_d, pool_d, tokens, start, true_len, table):
            _, pool_d = chunk_prefill_paged(
                cfg_d, params_d, tokens, start, true_len, pool_d, table,
                window)
            return pool_d
        fn = self._pool_program(draft_chunk, 1, draft=True)
        self._spec_fns[key] = fn
        return fn

    def _spec_draft_fn(self, gb: int):
        """Per γ bucket: the draft half of a speculative round — γ+1
        scanned draft decode steps over the DRAFT pool (the +1 writes
        the last draft's K/V so a fully-accepted round leaves no
        permanent cache hole, exactly the sequential engine's rule),
        returning the γ drafted tokens.  Compiled once per bucket: the
        γ-program family is ``_gamma_buckets``, bounded by config."""
        key = ("spec_draft", gb)
        if key in self._spec_fns:
            return self._spec_fns[key]
        self._note_compile("draft", (gb, self.paged.blocks_per_slot
                                     * self.paged.block_size,
                                     self._tp_degree()))
        cfg_d = self.cfg_d
        max_pos = self.cfg.max_seq_len - 1
        quantized = self.tier.kv_quantize == "int8"
        attn = None
        if self.mesh is not None and cfg_d.num_experts == 1:
            if self.params_d is self.params:
                # Self-draft shares the SHARDED target weights: draft
                # rounds run the same shard-mapped ragged hook as the
                # decode tick (PR 16).
                from ..parallel.tp_attention import tp_ragged_decode_attn
                attn = tp_ragged_decode_attn(self.mesh, cfg_d,
                                             quantized=quantized)
            else:
                # Replicated small draft: every chip drafts the full
                # batch locally inside an all-replicated shard_map
                # region: no collective in a draft round.
                from ..parallel.tp_attention import tp_local_ragged_decode
                attn = tp_local_ragged_decode(self.mesh,
                                              quantized=quantized)

        def spec_draft(params_d, pool_d, tables, pos, cur):
            def step(carry, _):
                pool_d, tok, p = carry
                logits, pool_d = decode_step_paged(
                    cfg_d, params_d, tok, p, pool_d, tables, attn=attn)
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (pool_d, nxt, jnp.minimum(p + 1, max_pos)), nxt
            (pool_d, _, _), drafted = jax.lax.scan(
                step, (pool_d, cur, pos), None, length=gb + 1)
            return jnp.swapaxes(drafted, 0, 1)[:, :gb], pool_d   # [B, γ]
        # The draft pool's placement (sharded for self-draft, replicated
        # for a small draft) is pinned like the target's — an unpinned
        # output is free to come back resharded, silently multiplying KV
        # memory.
        fn = self._pool_program(spec_draft, 1, lead=1, draft=True)
        self._spec_fns[key] = fn
        return fn

    def _spec_verify_fn(self, gb: int):
        """Per γ bucket: the verify half — ONE fused
        ``verify_step_paged`` call over every slot's γ+1 chunk
        (``attention.ragged_verify``), greedy acceptance with the
        per-slot runtime γ cap, and the emitted-token assembly, all on
        device.  Keyed ONLY by (γ_bucket, pool span, tp) through
        ``_note_compile("verify")``: per-slot γ and acceptance lengths
        are runtime operands, so adaptation never mints a program."""
        key = ("spec_verify", gb)
        if key in self._spec_fns:
            return self._spec_fns[key]
        self._note_compile("verify", (gb, self.paged.blocks_per_slot
                                      * self.paged.block_size,
                                      self._tp_degree()))
        cfg = self.cfg
        attn = None
        if self.mesh is not None and cfg.num_experts == 1:
            # ONE fused sharded verify call (PR 16): q [B, γ+1, Nq, D]
            # sharded on its head axis, combine is a head concat.
            from ..parallel.tp_attention import tp_ragged_verify_attn
            attn = tp_ragged_verify_attn(
                self.mesh, cfg,
                quantized=self.tier.kv_quantize == "int8")

        def spec_verify(params, pool, tables, pos, cur, drafted, gammas,
                        temps, rng):
            chunk = jnp.concatenate([cur[:, None], drafted], axis=1)
            logits, pool = verify_step_paged(cfg, params, chunk, pos,
                                             pool, tables, attn=attn)
            picks = jnp.argmax(logits, -1).astype(jnp.int32)  # [B, γ+1]
            # First-row pick is temperature-aware: a sampled slot rides
            # γ=0 and its one token per round must come from the same
            # distribution the plain tick samples (greedy slots get the
            # identical argmax).
            pick0 = _sample_batched(logits[:, 0], rng, temps)
            picks = picks.at[:, 0].set(pick0.astype(jnp.int32))
            agree = drafted == picks[:, :gb]                  # [B, γ]
            n_acc = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1),
                            axis=1)
            n_acc = jnp.minimum(n_acc, gammas)                # per-slot cap
            idx = jnp.arange(gb + 1)[None]
            out = jnp.where(
                idx < n_acc[:, None],
                jnp.pad(drafted, ((0, 0), (0, 1))),
                jnp.take_along_axis(picks, jnp.minimum(idx, n_acc[:, None]),
                                    axis=1))
            return out, n_acc, pool
        fn = self._pool_program(spec_verify, 1, lead=2)
        self._spec_fns[key] = fn
        return fn

    def _spill_gather_fn(self):
        """Jitted demote snapshot (``paged_kv.gather_blocks``): minted
        ONCE; jit retraces per distinct block count, a family bounded by
        the prompt-bucket ladder (ceil(bucket/bs) values) — the same
        boundedness as the prefill writers.  NOT donated: it reads the
        pool the next tick keeps using."""
        fn = self._spill_fns.get("gather")
        if fn is None:
            from .paged_kv import gather_blocks
            fn = jax.jit(gather_blocks)
            self._spill_fns["gather"] = fn
        return fn

    def _spill_write_fn(self):
        """Jitted promote write-back (``paged_kv.scatter_blocks``):
        donated pool → in-place page-in, same policy as the prefill
        writers; one trace per grant block count (bounded by the
        promote-budget block grain)."""
        fn = self._spill_fns.get("write")
        if fn is None:
            from .paged_kv import scatter_blocks
            fn = self._pool_program(scatter_blocks, 0)
            self._spill_fns["write"] = fn
        return fn

    def _prefix_evicted(self, entry) -> None:
        """on_evict sink for the device prefix cache: DEMOTE the entry
        to the host spill tier when eligible, else free its blocks (the
        historical behavior — a refcounted decref under sharing)."""
        blocks = (entry.cache.get("blocks")
                  if isinstance(entry.cache, dict) else None)
        if not blocks:
            return
        if not self._try_demote(entry.ids, blocks):
            self.allocator.free(blocks)

    def _try_demote(self, ids, blocks: List[int]) -> bool:
        """Demote an evicted prefix entry's blocks to host RAM.  True =
        the blocks were handled here (gathered and FREED — the
        functional snapshot owns its data, so they return to the pool at
        gather-issue time and the device→host pull drains on the spill
        copier, never the tick).  Only sole-owner data demotes: a block
        with refcount > 1 is still mapped by a live slot or another
        parked entry — freeing is just a decref and the data stays
        resident, so spilling a second copy would waste host budget."""
        spill = self.kv_spill
        if spill is None or self._stop.is_set():
            return False
        if any(r != 1 for r in self.allocator.refcounts(blocks)):
            return False
        nbytes = self._spill_block_bytes * len(blocks)
        if not spill.accepts(nbytes):
            return False

        def gather():
            self._note_compile("spill", ("gather", len(blocks)))
            return self._spill_gather_fn()(
                # dllm-lint: disable=retrace-dynamic-shape -- bounded: len(blocks) is ceil(parked-prompt/bs), one gather trace per prompt-bucket block count (the prefill-writer family's bound)
                self.pool, jnp.asarray(blocks, jnp.int32))

        # Phase stamps are scheduler-thread-only (the profiler is
        # single-writer); evictions driven from another thread (tests
        # poking pop_oldest, warmup on the builder thread) still demote,
        # just unstamped.
        try:
            if (self._thread is not None
                    and threading.get_ident() == self._thread.ident):
                with self.profiler.phase("demote"):
                    tiles = gather()
            else:
                tiles = gather()
        except Exception:
            # A failed gather must report "not handled" so the caller
            # falls back to freeing the blocks — raising past it would
            # leak them (nothing downstream knows they exist).
            return False
        # The snapshot owns its data: the blocks can go back to the
        # free list NOW — later pool writes build new pool arrays and
        # never reach it (see paged_kv.gather_blocks).
        self.allocator.free(blocks)
        spill.offer(ids, tiles, nbytes, nb=len(blocks))
        return True

    def _note_prefix_hit(self, kind: str) -> None:
        """Mirror one admission's prefix-cache lookup outcome to the
        ``dllm_prefix_hits_total{tier,kind}`` counter
        (kind = shared | exclusive | host | miss).  Counted per
        admission ATTEMPT — a KV-pressure requeue re-looks-up on
        re-admission, matching the cache's own hit/miss stats
        semantics.  ``host`` (ISSUE 14) is a spill-tier promotion
        claim: the DEVICE cache's own stats record it as a miss (or a
        reversed hit), so cache.stats() reconcilers should treat host
        hits as device misses.  No injection path on the engine (same
        pattern as the preemption counter): the process-global
        registry."""
        try:
            from ..obs import get_observability
            get_observability().m.prefix_hits.labels(
                self.tier.name, kind).inc()
        except Exception:
            pass

    # -- scheduler ---------------------------------------------------------

    def _suffix_window(self, needed: int) -> int:
        """Smallest bucketed attention window covering ``needed`` positions.
        Buckets are validated multiples of the block size; the fallback is
        the table's full span (blocks_per_slot·bs — max_seq_len itself may
        not divide evenly, and chunk_prefill_paged gathers whole blocks)."""
        return next((bb for bb in self._buckets if bb >= needed),
                    self.paged.blocks_per_slot * self.paged.block_size)

    def _table_row(self, blocks: List[int]) -> np.ndarray:
        row = np.full(self.paged.blocks_per_slot, TRASH_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        return row

    def _set_table_row(self, ix: int, row) -> None:
        """All block-table mutations funnel here so the cached device
        uploads (ragged full-table AND dense per-rung) are invalidated
        exactly when a row changes (admission, growth, finish,
        preemption) — the tick then re-uploads at most once per change,
        not once per tick."""
        self._tables[ix] = row
        self._tables_dev = None
        self._tables_dev_w.clear()
        # Any row change can mean a refcount change for some slot's
        # shared blocks (a sharer joined or left): recompute weights
        # lazily at the next attribution pass.
        self._kv_weights.clear()

    def _drop_carry(self, temps: bool = False) -> None:
        """A writer other than the plain emit changed ``_pos``/``_cur``
        (``temps``: ``_temps`` too): what the device holds of them is
        stale, and the next ``prepare`` uploads the mirrors."""
        self._carry.pop("pos", None)
        self._carry.pop("cur", None)
        if temps:
            self._carry.pop("temps", None)

    def _upload(self, host: np.ndarray):
        """A small tick input onto the device, committed where the
        tick's own small outputs come out (``_carry_home``)."""
        if self._carry_home is None:
            return jnp.asarray(host)
        return jax.device_put(host, self._carry_home)

    def _count_prepare_upload(self, what: str) -> None:
        """One upload made in ``prepare`` with the device idle."""
        self.prepare_uploads_total[what] = \
            self.prepare_uploads_total.get(what, 0) + 1
        sink = self._prepare_upload_sinks.get(what)
        if sink is None:
            try:
                # No injection path on the engine (same pattern as the
                # tick histogram): the process-global registry.
                from ..obs import get_observability
                sink = get_observability().m.tick_prepare_uploads.labels(
                    self.tier.name, what)
            except Exception:
                return
            self._prepare_upload_sinks[what] = sink
        sink.inc()

    def _sync_state_owner(self) -> bool:
        """The hybrid families: before a program that reads the recurrent
        rows (the shared-K/V family's window rings are rows too: a ring
        is its slot's like a state), make ``pool["owner"]`` say row =
        slot — a live slot's first block, the in-flight prefill's, 0 for
        a free slot — so a finished or preempted sequence's row is free
        the moment its blocks are,
        and a sequence admitted into the slot later claims (and zeroes)
        the same row.  A [slots] int32 upload, and only when it changed
        (returns whether it was made)."""
        if self._state_owner is None:
            return False
        owner = self._rows_owned()
        if np.array_equal(owner, self._state_owner):
            return False
        self._state_owner = owner
        self.pool = {**self.pool, "owner": jax.device_put(
            owner, self.pool["owner"].sharding)}
        return True

    def _rows_owned(self) -> np.ndarray:
        """[slots] the first block of each slot's sequence, 0 if none."""
        owner = self._tables[:, 0].copy()
        pf = self._prefill
        if pf is not None and pf.blocks:
            owner[pf.slot_ix] = pf.blocks[0]
        return owner

    def pool_stats(self) -> Dict[str, Any]:
        """The pool where it rests (GET /stats ``pool``): each array's
        format as the device held it when the pool was made
        (``paged_kv.pool_formats``), which every pool program gives back
        (``chip_smoke.pool_program_facts`` ``formats_match``)."""
        return {"formats": {key: dict(fmt) for key, fmt
                            in self._pool_formats.items()}}

    def state_stats(self) -> Optional[Dict[str, int]]:
        """The recurrent rows (GET /stats ``state``), or None for a model
        without them: the kind of mixer that owns them (``cca_tail``: the
        "C" attention layers' tail rows, with no state; ``kda``: the
        linear-attention layers' matrix a head and three conv tails;
        ``none``: a pattern with no row kind, whose rows are zero layers
        deep) and its layers, how many rows there are, how many name a
        sequence, what one holds, how many sequences started one from
        zero, and the K/V (under ``kda`` and ``none`` the latent layers'
        one row a token) beside them."""
        if self._state_owner is None:
            return None
        from ..utils.roofline import (kv_bytes_per_pos, ring_row_bytes,
                                      state_row_bytes)
        tails, kda = self.cfg.layers_of("C"), self.cfg.layers_of("K")
        ssm = self.cfg.layers_of("M")
        out = {"mixer": ("cca_tail" if tails else "kda" if kda else
                         "none" if not ssm else
                         "mamba1" if self.cfg.ssm_dt_rank else "mamba2"),
               "layers": tails or kda or ssm,
               "rows": int(self._state_owner.size),
               "rows_in_use": int(np.count_nonzero(self._rows_owned())),
               "row_bytes": int(state_row_bytes(self.cfg)),
               "resets_total": int(self.state_resets_total),
               # Beside the rows: the layers whose K/V the paged pool
               # holds by position, and what a token keeps there.
               "kv_layers": self.cfg.kv_layers,
               "kv_bytes_per_token": int(kv_bytes_per_pos(self.cfg))}
        if self.cfg.shared_kv:
            # The window layers' rings: a row a slot like the state, of
            # the window's positions whatever the sequence's length.
            out.update(ring_layers=self.cfg.layers_of("W"),
                       ring_positions=self.cfg.attn_window,
                       ring_bytes=int(ring_row_bytes(self.cfg)))
        return out

    def _alloc_evicting(self, n_blocks: int) -> Optional[List[int]]:
        """Allocate, evicting parked prefix entries (LRU) under pressure:
        live admissions always outrank parked caches.  Quotas ON adds a
        first pass over parked entries whose OWNING TENANT is over its
        KV block budget — an over-quota tenant's cold cache is sacrificed
        before any in-budget tenant's (ISSUE 17)."""
        blocks = self.allocator.alloc(n_blocks)
        if (self._tenant_quotas is not None and blocks is None
                and self.prefix_cache is not None):
            # The over-quota set is computed ONCE before the sweep (the
            # pop_oldest predicate runs under the cache lock, so it
            # cannot re-walk the cache itself); the slight over-eviction
            # of a tenant whose bill drops below budget mid-sweep is
            # the intended bias against the noisy tenant.
            over = self._overquota_parked_tenants()
            while (blocks is None and over
                   and self.prefix_cache.pop_oldest(
                       match=lambda e: isinstance(e.cache, dict)
                       and e.cache.get("tenant") in over) is not None):
                blocks = self.allocator.alloc(n_blocks)
        while (blocks is None and self.prefix_cache is not None
               and self.prefix_cache.pop_oldest() is not None):
            blocks = self.allocator.alloc(n_blocks)
        return blocks

    def _overquota_parked_tenants(self) -> set:
        """Tenants that (a) own tagged parked prefix entries and (b) are
        over their KV block budget — the eviction sweep's first-pass
        victims (quotas ON)."""
        tenants = set()
        for e in self.prefix_cache.entries_snapshot():
            if isinstance(e.cache, dict):
                t = e.cache.get("tenant")
                if t:
                    tenants.add(t)
        over = set()
        for t in tenants:
            q = self._tenant_quota(t)
            if (q is not None and q.kv_blocks
                    and self.tenant_kv_blocks(t) > float(q.kv_blocks)):
                over.add(t)
        return over

    def tenant_kv_blocks(self, tenant: Optional[str]) -> float:
        """The tenant's resident-KV bill in pool blocks, each block
        billed at 1/refcount (the PR 11 attribution currency: a block
        shared k ways costs each sharer 1/k, so prefix dedup LOWERS the
        bill).  Covers live slots owned by the tenant plus its tagged
        parked prefix entries; untagged entries (parked while quotas
        were off) bill nobody.  Advisory cross-thread read — the
        serving gate and the scheduler's victim policy both call it."""
        t = tenant or "default"
        owned: List[int] = []
        for slot in self._slots:
            if slot is not None and (slot.request.tenant or "default") == t:
                owned.extend(slot.blocks)
        if self.prefix_cache is not None:
            for e in self.prefix_cache.entries_snapshot():
                cache = e.cache
                if (isinstance(cache, dict) and cache.get("tenant") == t):
                    owned.extend(cache.get("blocks") or [])
        if not owned:
            return 0.0
        return sum(1.0 / r if r > 0 else 1.0
                   for r in self.allocator.refcounts(owned))

    def _slot_go_live(self, req: _Request, slot_ix: int,
                      blocks: List[int], *, prompt_len: int,
                      prompt_ids: tuple, budget: int, temp: float,
                      max_blocks: int, pos: int,
                      first: Optional[int] = None,
                      gen: Optional[List[int]] = None,
                      ttft_ms: float = 0.0,
                      pinned_entry: Optional[Any] = None,
                      spec_ok: bool = False) -> None:
        """The go-live tail shared by ALL FOUR admission paths
        (monolithic/chunked x cold/replay): construct the slot, publish
        its table row and per-slot decode state, emit the primed first
        token (cold: ``first``) or resume from the parked prefix
        (replay: ``gen``), and apply the termination checks.  Keeping
        this in one place is part of the byte-identity contract — a
        termination-rule change applied to the monolithic paths but not
        the chunked ones would silently diverge the modes."""
        if gen is None:
            tokens, cur = [first], first
        else:
            tokens, cur = list(gen), gen[-1]
            ttft_ms = req.replay_ttft_ms or 0.0
        # Speculation eligibility is decided HERE, once, for the slot's
        # life: the admission path must have seeded the draft pool
        # (spec_ok) and the slot must be greedy — a sampled slot rides
        # the verify's sampled first row at γ=0.
        spec = bool(self.spec and spec_ok and temp <= 0)
        # Tenant γ clamp (quotas ON): a capped tenant starts at its cap
        # — cap 0 disables drafting for the slot's life (γ is sticky at
        # 0, exactly the degraded-slot path).  None = no clamp.
        cap = self._tenant_gamma_cap(req)
        gamma0 = self.spec_gamma_max if cap is None else cap
        slot = _Slot(request=req, blocks=blocks, prompt_len=prompt_len,
                     budget=budget, temperature=temp, ttft_ms=ttft_ms,
                     tokens=tokens, prompt_ids=prompt_ids,
                     max_blocks=max_blocks, pinned_entry=pinned_entry,
                     spec=spec,
                     gamma=gamma0 if spec else 0)
        if gen is None:
            obs_spans.add_token(req.trace)   # the prefill's primed token
            if req.token_queue is not None:
                req.token_queue.put(first)
        else:
            req.replay_tokens = None
        self._slots[slot_ix] = slot
        self._set_table_row(slot_ix, self._table_row(blocks))
        self._pos[slot_ix] = pos
        self._cur[slot_ix] = cur
        self._temps[slot_ix] = temp
        self._drop_carry(temps=True)
        if gen is None:
            if first == self.tokenizer.eos_id or budget <= 1:
                self._finish(slot_ix)
        elif (cur in (self.tokenizer.eos_id, self.tokenizer.pad_id)
                or len(gen) >= budget):
            self._finish(slot_ix)            # was already done (paranoia)

    def _admit(self, req: _Request, slot_ix: int) -> bool:
        # Submit-to-prefill-start wait (the admission queue + any
        # KV-pressure requeues).  queue_wait_ms keeps its historical
        # name (the registry histogram reads it); admission_wait_ms is
        # its explicit half of the TTFT split — prefill_wait_ms (stamped
        # when the prefill completes) is the other — so a trace shows
        # whether TTFT went to WAITING for the scheduler or to
        # PREFILLING the prompt (chunked prefills can spend many ticks
        # there while decode keeps streaming).
        now = time.perf_counter()
        wait_ms = round((now - req.t_submit) * 1000.0, 3)
        if req.t_lane_blocked is not None:
            req.lane_wait_ms += (now - req.t_lane_blocked) * 1000.0
            req.t_lane_blocked = None
        obs_spans.annotate(req.trace, queue_wait_ms=wait_ms,
                           admission_wait_ms=wait_ms,
                           lane_wait_ms=round(req.lane_wait_ms, 3))
        with self.phases.phase("tokenize"):
            ids, bucket = prepare_prompt(self.tokenizer, req.history,
                                         self.tier.prefill_buckets,
                                         self.cfg.max_seq_len,
                                         self.tier.max_new_tokens)
        n = len(ids)
        budget = self.tier.max_new_tokens
        if req.max_new_tokens and req.max_new_tokens > 0:
            budget = min(budget, req.max_new_tokens)
        if req.admit_seq < 0:
            req.admit_seq = self._admit_seq
            self._admit_seq += 1
        if req.replay_tokens:
            return self._admit_replay(req, slot_ix, ids, n, budget)

        bs = self.paged.block_size
        max_seq = self.cfg.max_seq_len

        # Prefix reuse: a parked entry's blocks become this slot's
        # leading table rows and only the suffix prefills (shared
        # matching policy with the contiguous engine; m need not be
        # block-aligned — the suffix chunk overwrites its own positions
        # and stale entry KV past n-1 is masked).  share_prefix (the
        # default) PINS the entry and maps its blocks read-only so N
        # concurrent sessions ride one physical prefix; OFF takes
        # exclusive ownership exactly as before.
        from .prefix_cache import select_reuse
        reused = select_reuse(self.prefix_cache, ids, self._reuse_buckets,
                              max_seq, share=self.share_prefix)

        if self.kv_spill is not None:
            # Hierarchical KV (ISSUE 14): probe the host spill tier and
            # prefer it whenever it holds a LONGER prefix than the
            # device cache found (a session's demoted history beats a
            # stranger's short common opener).  A host hit becomes an
            # in-flight chunked prefill whose leading blocks are
            # PROMOTED (host→device grants under the chunk budget,
            # _advance_promotion) instead of recomputed; the prefetch
            # overlaps the request's own queue wait.  The single
            # prefill lane applies exactly as for a long cold prompt.
            dev_m = reused[1] if reused is not None else 0
            if self.kv_spill.peek(ids, max_len=n - 1) > dev_m:
                if self._prefill is not None:
                    if reused is not None:
                        # Hand the device hit back untouched — the
                        # deferred re-admission re-probes both tiers.
                        entry, m, _suffix, _sb = reused
                        if self.share_prefix:
                            self.prefix_cache.unshare(entry, m)
                        else:
                            self.prefix_cache.untake(entry, m)
                    # unshare/untake reversed the cache's hit into a
                    # miss (and a no-hit defer already counted one):
                    # mirror it so the counter tracks cache stats.
                    self._note_prefix_hit("miss")
                    req.needs_chunk = True
                    return False
                claimed = self.kv_spill.claim(ids, max_len=n - 1)
                if claimed is not None and claimed[1] > dev_m:
                    try:
                        if reused is not None:
                            entry, m, _suffix, _sb = reused
                            if self.share_prefix:
                                self.prefix_cache.unshare(entry, m)
                            else:
                                self.prefix_cache.untake(entry, m)
                            reused = None
                        self._note_prefix_hit("host")
                        self._start_prefill(req, slot_ix, ids, n, bucket,
                                            budget, promote=claimed)
                    except BaseException:
                        # The claim pinned the spill entry; until
                        # _start_prefill publishes the promotion the
                        # pin is ours to drop, or it never unpins.
                        self.kv_spill.release(claimed[0], promoted=False)
                        raise
                    return True
                if claimed is not None:
                    # The peeked entry shrank/died before the claim:
                    # the device hit (if any) still stands.
                    self.kv_spill.release(claimed[0], promoted=False)

        if self.prefix_cache is not None and reused is None:
            self._note_prefix_hit("miss")

        if reused is None and self._chunk_gate(bucket):
            # Long cold prompt: chunked prefill interleaved with decode
            # ticks instead of one monolithic call that would stall
            # every active stream for the whole prompt.  One in-flight
            # prefill at a time — a second long prompt waits at the
            # scheduler head (needs_chunk keeps the loop from
            # re-tokenizing it every tick) so admission order holds.
            if self._prefill is not None:
                req.needs_chunk = True
                return False
            self._start_prefill(req, slot_ix, ids, n, bucket, budget)
            return True

        self._rng, rng = jax.random.split(self._rng)
        temp = (self.tier.temperature if req.temperature is None
                else req.temperature)

        from ..utils import roofline
        pinned_entry = None
        if reused is not None:
            entry, m, suffix, sb = reused
            cover = max(m + sb, min(n + budget, max_seq))
            need = -(-cover // bs)
            boundary_src = None
            if self.share_prefix:
                # SHARED hit: the entry stays parked (pinned); its FULL
                # blocks map read-only into this slot's leading table
                # rows (incref — zero compute, zero new blocks for the
                # shared region).  The partially-filled BOUNDARY block
                # (m mid-block) is COW-copied into the first private
                # block below: this slot writes its suffix there, and
                # sharers must never see it.
                n_full = m // bs
                shared = list(entry.cache["blocks"][:n_full])
                if (m % bs) != 0:
                    boundary_src = entry.cache["blocks"][n_full]
                self.allocator.share(shared)
                try:
                    priv = self._alloc_evicting(need - n_full)
                except BaseException:
                    # _alloc_evicting can raise out of the eviction
                    # walk; the share incref and the cache hit must
                    # both unwind or the parked entry leaks a sharer.
                    self.allocator.free(shared)
                    self.prefix_cache.unshare(entry, m)
                    raise
                if priv is None:
                    self.allocator.free(shared)       # decref only
                    # unshare() reverses the cache's hit into a miss;
                    # mirror that so the counter tracks cache stats.
                    self.prefix_cache.unshare(entry, m)
                    self._note_prefix_hit("miss")
                    return False             # KV pressure: stay queued
                owned = shared + priv
                pinned_entry = entry
                self._note_prefix_hit("shared")
            else:
                # EXCLUSIVE take (share_prefix_kv=False): ownership of
                # the entry's blocks moves to the slot; the suffix may
                # write straight into the boundary block because nobody
                # else maps it.
                owned = list(entry.cache["blocks"])
                if len(owned) < need:
                    extra = self._alloc_evicting(need - len(owned))
                    if extra is None:
                        # untake() reverses the cache's hit into a miss;
                        # mirror that so the counter tracks cache stats.
                        self.prefix_cache.untake(entry, m)
                        self._note_prefix_hit("miss")
                        return False             # KV pressure: stay queued
                    owned += extra
                elif len(owned) > need:
                    self.allocator.free(owned[need:])
                    owned = owned[:need]
                self._note_prefix_hit("exclusive")
            try:
                if boundary_src is not None:
                    # One compiled program for every (src, dst) pair —
                    # priv[0] is the boundary position's table row
                    # (need > n_full always: the suffix has >= 1 token).
                    with self.profiler.phase("cow_copy"):
                        self.pool = self._cow_copy_fn()(
                            self.pool, jnp.asarray(boundary_src, jnp.int32),
                            jnp.asarray(priv[0], jnp.int32))
                        if self.spec:
                            # The draft attends the same tables: its
                            # boundary block must COW too, or the
                            # slot's suffix draft KV would land in the
                            # sharer-visible draft block.
                            self.pool_d = self._cow_copy_fn_d()(
                                self.pool_d,
                                jnp.asarray(boundary_src, jnp.int32),
                                jnp.asarray(priv[0], jnp.int32))
                row = self._table_row(owned)
                tokens = np.full((1, sb), self.tokenizer.pad_id, np.int32)
                tokens[0, :len(suffix)] = suffix
                window = next(w for w in self._reuse_windows
                              if w >= m + sb)
                with obs_spans.span(req.trace, "prefill", reused_tokens=m,
                                    suffix_bucket=sb), \
                        self.phases.phase("prefill"), \
                        self.profiler.phase("prefill"):
                    first, self.pool = self._chunk_prefill_fn(sb, window)(
                        self.params, self.pool, jnp.asarray(tokens),
                        jnp.asarray([m], np.int32), jnp.asarray([n], np.int32),
                        jnp.asarray(row), rng, jnp.float32(temp))
                    if self.spec:
                        # Seed the draft pool's suffix (K/V only): the
                        # parked prefix blocks already carry whatever
                        # draft KV their writers left — stale content
                        # only lowers acceptance, never correctness.
                        self.pool_d = self._draft_chunk_fn(sb, window)(
                            self.params_d, self.pool_d,
                            jnp.asarray(tokens),
                            jnp.asarray([m], np.int32),
                            jnp.asarray([n], np.int32), jnp.asarray(row))
                    # dllm-lint: disable=transfer-host-sync -- sanctioned: the FIRST token must reach the host NOW (TTFT is the SLO and the value seeds the slot) — one sync per admission, never per tick
                    first = jax.block_until_ready(first)
                    first = int(self._chunk_result(first))
                self.profiler.event("host_sync",
                                    site="prefill_first_token")
                self.phases.add_work("prefill", **roofline.prefill_work(
                    self.cfg, window, window - sb, wbytes=self._wbytes))
            except BaseException:
                # Don't leak pool blocks (refcounted: shared blocks just
                # decref back to their other holders).
                self.allocator.free(owned)
                if pinned_entry is not None:
                    self.prefix_cache.unpin(pinned_entry)
                raise
            blocks = owned
            max_blocks = len(owned)          # fully materialized: no growth
        else:
            max_blocks = -(-min(bucket + budget, max_seq) // bs)
            # Lazy growth: materialize only the prefill bucket plus one
            # decode tick NOW; the scheduler's pre-tick ensure allocates
            # the rest block-by-block as the sequence actually grows
            # (preempting the youngest slot when the pool runs dry), so a
            # fixed pool admits by real demand, not by worst case.
            need = min(max_blocks,
                       max(bucket // bs,
                           -(-min(n + self.steps_per_tick, max_seq) // bs)))
            blocks = self._alloc_evicting(need)
            if blocks is None:
                return False                 # KV pressure: stay queued

            try:
                tokens = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
                tokens[0, :n] = ids

                with obs_spans.span(req.trace, "prefill", bucket=bucket), \
                        self.phases.phase("prefill"), \
                        self.profiler.phase("prefill"):
                    first, *rows = self._prefill_fn(bucket)(
                        self.params, jnp.asarray(tokens),
                        jnp.asarray([n], np.int32), rng, jnp.float32(temp))
                    # Page the prefilled bucket into this slot's blocks.
                    nb_prefill = bucket // bs
                    blk_dev = jnp.asarray(blocks[:nb_prefill], np.int32)  # dllm-lint: disable=retrace-dynamic-shape -- bounded: nb_prefill only takes values from the validated prefill bucket set (one writer program per bucket, pinned by _note_compile's "writer" stage)
                    self.pool = self._writer_fn(nb_prefill)(
                        self.pool, blk_dev, *rows)
                    if self.spec:
                        # Seed the DRAFT pool with the prompt's K/V so
                        # this slot can speculate (ISSUE 15): same
                        # bucket, same blocks, the draft's own forward.
                        dk, dv = self._draft_prefill_fn(bucket)(
                            self.params_d, jnp.asarray(tokens))
                        self.pool_d = self._draft_writer_fn(nb_prefill)(
                            self.pool_d, blk_dev, dk, dv)
                    # dllm-lint: disable=transfer-host-sync -- sanctioned: the FIRST token must reach the host NOW (TTFT is the SLO and the value seeds the slot) — one sync per admission, never per tick
                    first = int(jax.block_until_ready(first))
                self.profiler.event("host_sync",
                                    site="prefill_first_token")
                self.phases.add_work("prefill", **roofline.prefill_work(
                    self.cfg, bucket, 0, wbytes=self._wbytes))
            except BaseException:
                self.allocator.free(blocks)  # don't leak pool blocks
                raise
        try:
            ttft_ms = (time.perf_counter() - req.t_submit) * 1000.0
            # The other half of the TTFT split (see the stamp at the
            # top): for a monolithic prefill it is the one compiled
            # call's wall.
            obs_spans.annotate(req.trace, prefill_wait_ms=round(
                max(0.0, ttft_ms - wait_ms), 3))
        except BaseException:
            # Blocks aren't owned by a slot until _slot_go_live below
            # publishes them; an annotate failure here would otherwise
            # strand them (refcounted: shared blocks just decref).
            self.allocator.free(blocks)
            if pinned_entry is not None:
                self.prefix_cache.unpin(pinned_entry)
            raise

        self._slot_go_live(req, slot_ix, blocks, prompt_len=n,
                           prompt_ids=tuple(ids), budget=budget, temp=temp,
                           max_blocks=max_blocks, pos=n, first=first,
                           ttft_ms=ttft_ms, pinned_entry=pinned_entry,
                           spec_ok=True)
        return True

    def _admit_replay(self, req: _Request, slot_ix: int, ids: List[int],
                      n: int, budget: int) -> bool:
        """Re-admission of a preempted request: replay prompt + generated
        prefix through ONE cold prefill (rebuilding KV for every position
        already consumed), then resume decoding from the last generated
        token.  Nothing is re-sampled or re-emitted — the prefix was
        already streamed — so under greedy decoding the continuation is
        byte-identical to an unpreempted run.  Returns False (stay at the
        scheduler head) while the pool still cannot hold the replay."""
        bs = self.paged.block_size
        max_seq = self.cfg.max_seq_len
        gen = list(req.replay_tokens)
        seq = list(ids) + gen[:-1]           # everything whose KV we need
        bucket = next((b for b in self._buckets if b >= len(seq)), None)
        if bucket is None:
            # No prefill bucket covers prompt+prefix (deep preemption on a
            # short bucket ladder): finish with what was already emitted —
            # the stream saw exactly these tokens, and a truncated tail
            # beats silently divergent text from an approximate replay.
            gen_ids = trim_at_eos(gen, self.tokenizer.eos_id,
                                  self.tokenizer.pad_id)
            with obs_spans.span(req.trace, "detokenize",
                                tokens=len(gen_ids)):
                text = self.tokenizer.decode(gen_ids)
            req.result = GenerationResult(
                text=text, token_ids=gen_ids, prompt_tokens=n,
                gen_tokens=len(gen_ids),
                ttft_ms=req.replay_ttft_ms or 0.0,
                total_ms=(time.perf_counter() - req.t_submit) * 1000.0)
            obs_spans.event(req.trace, "replay_truncated",
                            generated=len(gen_ids))
            if req.token_queue is not None:
                req.token_queue.put(None)
            req.done.set()
            return True
        if self._chunk_gate(bucket):
            # A deep replay is the same long-prefill stall as a cold
            # long prompt — chunk it too (the replay's sample is
            # discarded at the final chunk, decode resumes from the last
            # emitted token, so the byte-identity contract is unchanged).
            # replay_tokens stay parked on the request until the prefill
            # COMPLETES: a cancel-and-requeue must replay from the same
            # generated prefix.
            if self._prefill is not None:
                req.needs_chunk = True
                return False
            self._start_prefill(req, slot_ix, ids, n, bucket, budget,
                                gen=gen)
            return True
        max_blocks = -(-min(max(bucket, n + budget), max_seq) // bs)
        need = min(max_blocks,
                   max(bucket // bs,
                       -(-min(len(seq) + self.steps_per_tick, max_seq)
                         // bs)))
        blocks = self._alloc_evicting(need)
        if blocks is None:
            return False                     # still starved: stay at head
        try:
            # The rng split stays under this handler (and after the
            # starvation check above): a raise from here on must free
            # the replay's blocks, and a starved retry must not burn a
            # stream position.
            self._rng, rng = jax.random.split(self._rng)
            temp = (self.tier.temperature if req.temperature is None
                    else req.temperature)
            tokens = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
            tokens[0, :len(seq)] = seq
            with obs_spans.span(req.trace, "prefill", bucket=bucket,
                                replayed_tokens=len(gen)), \
                    self.phases.phase("prefill"), \
                    self.profiler.phase("prefill"):
                first, *rows = self._prefill_fn(bucket)(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray([len(seq)], np.int32), rng,
                    jnp.float32(temp))
                nb_prefill = bucket // bs
                blk_dev = jnp.asarray(blocks[:nb_prefill], np.int32)  # dllm-lint: disable=retrace-dynamic-shape -- bounded: nb_prefill only takes values from the validated prefill bucket set (one writer program per bucket)
                self.pool = self._writer_fn(nb_prefill)(
                    self.pool, blk_dev, *rows)
                if self.spec:
                    # Replay rebuilds the draft prefix too (same cold
                    # prefill shape), so a preempted speculating slot
                    # resumes speculating instead of degrading to γ=0.
                    dk, dv = self._draft_prefill_fn(bucket)(
                        self.params_d, jnp.asarray(tokens))
                    self.pool_d = self._draft_writer_fn(nb_prefill)(
                        self.pool_d, blk_dev, dk, dv)
                # The replay's sampled token is discarded: the last
                # generated token was already emitted pre-preemption and
                # decoding resumes FROM it, not after a fresh sample.
                # NO sync here (the transfer lint found one): blocking
                # the scheduler thread on a value nobody reads stalled
                # every OTHER active slot for the full replay prefill.
                # The next tick's decode queues behind this prefill on
                # the device stream anyway, and a deferred device error
                # still surfaces at that tick, where _fail_slot frees
                # the slot's blocks.
            from ..utils import roofline
            self.phases.add_work("prefill", **roofline.prefill_work(
                self.cfg, bucket, 0, wbytes=self._wbytes))
            obs_spans.event(req.trace, "replay", replayed_tokens=len(seq),
                            generated=len(gen))
        except BaseException:
            self.allocator.free(blocks)      # don't leak pool blocks
            raise
        self._slot_go_live(req, slot_ix, blocks, prompt_len=n,
                           prompt_ids=tuple(ids), budget=budget, temp=temp,
                           max_blocks=max_blocks, pos=len(seq), gen=gen,
                           spec_ok=True)
        return True

    # -- chunked prefill (the in-flight scheduler citizen) -----------------

    def _chunk_gate(self, bucket: int) -> bool:
        """Whether an admission prefills CHUNKED: only prompts whose
        bucket exceeds one chunk — a smaller prompt's monolithic prefill
        already meets the one-chunk TBT bound, and keeps the warm
        prefill-bucket program path.  (The hybrid family: always.)"""
        if self.cfg.hybrid:
            # The chunk program carries the recurrent state: this family
            # has no other prefill, whatever the prompt's length.
            return True
        return bool(self.chunk_tokens) and bucket > self.chunk_tokens

    def _start_prefill(self, req: _Request, slot_ix: int, ids: List[int],
                       n: int, bucket: int, budget: int,
                       gen: Optional[List[int]] = None,
                       promote: Optional[Any] = None) -> None:
        """Reserve ``slot_ix`` and register the request as the tick's
        in-flight chunked prefill.  No blocks yet — _advance_prefill
        allocates per chunk, so a long prompt's pool footprint grows
        with actual progress.  The rng splits ONCE here (same stream
        position as a monolithic admission), and the final chunk samples
        with it, so greedy first-token semantics are byte-identical to
        the one-shot path."""
        bs = self.paged.block_size
        max_seq = self.cfg.max_seq_len
        if gen is None:
            seq = list(ids)
            max_blocks = -(-min(bucket + budget, max_seq) // bs)
        else:
            seq = list(ids) + list(gen[:-1])
            max_blocks = -(-min(max(bucket, n + budget), max_seq) // bs)
        self._rng, rng = jax.random.split(self._rng)
        temp = (self.tier.temperature if req.temperature is None
                else req.temperature)
        pf = _Prefill(
            request=req, slot_ix=slot_ix, seq=seq, prompt_len=n,
            prompt_ids=tuple(ids), total=len(seq), budget=budget,
            temperature=temp, rng=rng, max_blocks=max_blocks,
            replay=list(gen) if gen is not None else None)
        if promote is not None:
            # Hierarchical-KV promotion (engine/kv_spill.py): the
            # claimed (pinned) HostEntry satisfies the leading blocks —
            # the ceil(m/bs) tiles covering the matched prefix; a
            # mid-block boundary is fine because the suffix chunks
            # overwrite their own positions in these PRIVATE blocks
            # (the exclusive-take rule) and stale tail KV is masked.
            entry, m = promote
            pf.promote_entry = entry
            pf.promote_tokens = m
            pf.promote_nb = -(-m // bs)
            obs_spans.event(req.trace, "kv_promote_start",
                            matched_tokens=m, blocks=pf.promote_nb)
        obs_spans.event(req.trace, "prefill_chunked", tokens=len(seq),
                        chunk_tokens=self.chunk_tokens,
                        replayed=bool(gen))
        # Publication is the LAST statement: once self._prefill is set,
        # the promotion pin belongs to the prefill machinery, and the
        # caller's exception handler must not also release it.
        self._prefill = pf

    def _advance_prefill(self, budget: Optional[int] = None) -> bool:
        """Spend up to ``budget`` tokens (default ``chunk_budget``)
        advancing the in-flight prefill — what a scheduler pass does
        for it outside the tick: all of a solo prefill's pass, and in a
        pass with a tick whatever ``_ride_chunk`` left of the budget,
        after the emit.  Each chunk scatters its K/V straight into the
        slot's pool blocks via the SAME compiled (chunk, window-rung)
        program family the prefix-reuse suffix path uses, and is NOT
        waited for (``_dispatch_chunk``); a prompt's last chunk is
        settled here and its slot goes live.  A dry pool stalls the
        prefill (retry next tick) rather than starving decode growth.
        Returns whether anything landed (False = stalled dry, or no
        budget), so a solo prefill's loop can back off instead of
        hot-spinning on an allocator that nothing will refill."""
        pf = self._prefill
        if pf is None:
            return True
        progressed = False
        c = self.chunk_tokens
        budget_left = self.chunk_budget if budget is None else budget
        try:
            if pf.promote_entry is not None:
                moved, budget_left = self._advance_promotion(pf,
                                                             budget_left)
                progressed = progressed or moved
                if pf.promote_entry is not None:
                    # Still mid-promotion (copier not landed, pool dry,
                    # or the promote share of this tick's budget spent):
                    # retry next tick — decode never waits on it.
                    return progressed
            while pf.consumed < pf.total and budget_left >= c:
                if not self._dispatch_chunk(pf, overlapped=False):
                    return progressed          # pool dry: retry next tick
                progressed = True
                budget_left -= c
            if pf.consumed >= pf.total:
                # The prompt's last chunk is out: the one wait a prefill
                # cannot do without, for the token its slot starts from.
                with self.profiler.phase("chunk_prefill"):
                    first = self._settle_chunk(pf)
                # The slot's going live is host work: a phase of its own,
                # one stamp a request, not the device wait's.
                with self.profiler.phase("first_token"):
                    self._finish_prefill(pf, int(first))
                return True
        except BaseException as exc:       # surface to the caller
            self._fail_prefill(pf, exc)
            return True
        return progressed

    def _ride_chunk(self) -> int:
        """Between a plain tick's dispatch and its fetch: enqueue the
        in-flight prefill's next chunk on the device BEHIND the tick, so
        the device runs tick -> chunk back to back while the host
        fetches, accounts and emits the tick.  One chunk at most (a
        larger ``chunk_budget`` spends its rest after the emit, where a
        wait for this chunk no longer stands between the tick and its
        tokens); a promotion in flight, and a prompt's landing, stay
        with ``_advance_prefill``.  Returns the budget tokens spent.  A
        failure fails the prefill's own request here and never reaches
        the tick's handler around this call."""
        pf = self._prefill
        if (pf is None or pf.promote_entry is not None
                or pf.consumed >= pf.total):
            # (All chunks out but not landed: the tick the last one rode
            # behind failed, and its handler skipped the landing.)
            return 0
        try:
            return (self.chunk_tokens
                    if self._dispatch_chunk(pf, overlapped=True) else 0)
        except BaseException as exc:       # surface to the caller
            self._fail_prefill(pf, exc)
            return 0

    def _dispatch_chunk(self, pf: _Prefill, overlapped: bool) -> bool:
        """One chunk of the in-flight prefill: its host work (blocks,
        with their evictions; the table row; the uploads), then the
        wait for the chunk BEFORE it, then its own dispatch, which is
        not waited for.  At most one chunk is unresolved at any time, so
        the host runs at most one chunk ahead of the device.  Ordering
        against the tick before it and whatever follows is the pool's
        data dependence (``self.pool``), never a host wait.  False on a
        dry pool (nothing dispatched).  ``overlapped``: dispatched
        behind a tick whose tokens the host has not fetched yet."""
        req = pf.request
        c = self.chunk_tokens
        bs = self.paged.block_size
        span = self.paged.blocks_per_slot * bs
        with self.profiler.phase("chunk_prefill"):
            start = pf.consumed
            if start + c > span:
                # Final sliver near the table's end: slide the chunk
                # back so every position stays inside the table (an
                # overflowing pad position would CLAMP its block
                # index onto a real block and corrupt live KV).  The
                # overlap recomputes identical K/V — harmless.
                start = span - c
            end = start + c
            need = min(pf.max_blocks, -(-min(end, pf.total) // bs))
            if len(pf.blocks) < need:
                extra = self._alloc_evicting(need - len(pf.blocks))
                if extra is None:
                    return False
                pf.blocks.extend(extra)
            window = next(w for w in self._chunk_windows if w >= end)
            k = min(end, pf.total) - start
            tokens = np.full((1, c), self.tokenizer.pad_id, np.int32)
            tokens[0, :k] = pf.seq[start:start + k]
            fn = self._chunk_prefill_fn(c, window)
            args = (jnp.asarray(tokens), jnp.asarray([start], np.int32),
                    jnp.asarray([pf.total], np.int32),
                    jnp.asarray(self._table_row(pf.blocks)), pf.rng,
                    jnp.float32(pf.temperature))
            self._sync_state_owner()
            if start == 0 and self._state_owner is not None:
                self.state_resets_total += 1
                try:
                    from ..obs import get_observability
                    get_observability().m.state_resets.labels(
                        self.tier.name).inc()
                except Exception:
                    pass
            # Everything this chunk needs is on its way: only now wait
            # for the chunk before it (if the device still runs it, the
            # launch below finds the stream busy, not idle).
            self._settle_chunk(pf)
            with obs_spans.span(req.trace, "prefill_chunk", start=start,
                                tokens=k, window=window):
                pf.pending, self.pool = fn(self.params, self.pool, *args)
            pf.pending_t = time.perf_counter()
        from ..utils import roofline
        self.phases.add_work("prefill", **roofline.prefill_work(
            self.cfg, end, start, wbytes=self._wbytes))
        self.prefill_chunks_total += 1
        self.prefill_chunks_overlapped_total += int(overlapped)
        written = min(end, pf.total)
        self.prefill_window_positions_total += window
        self.prefill_written_positions_total += written
        self.prefill_chunks_by_window[window] = \
            self.prefill_chunks_by_window.get(window, 0) + 1
        form = self._chunk_attention_forms.get((c, window))
        if form is not None:
            self.prefill_chunks_by_form[form] = \
                self.prefill_chunks_by_form.get(form, 0) + 1
        self_only = self.cfg.shared_kv and end < pf.total
        self.prefill_self_only_chunks_total += int(self_only)
        try:
            # No injection path on the engine (same pattern as the tick
            # histogram): the process-global registry.
            from ..obs import get_observability
            m = get_observability().m
            m.prefill_chunks.labels(
                self.tier.name,
                "behind_tick" if overlapped else "alone").inc()
            m.prefill_window_positions.labels(self.tier.name).inc(window)
            m.prefill_chunks_by_window.labels(self.tier.name,
                                              str(window)).inc()
            m.prefill_written_positions.labels(self.tier.name).inc(written)
            if form is not None:
                m.prefill_chunks_by_form.labels(self.tier.name, form).inc()
            if self_only:
                m.prefill_self_only_chunks.labels(self.tier.name).inc()
        except Exception:
            pass
        pf.consumed = written
        pf.chunks_done += 1
        return True

    def _settle_chunk(self, pf: _Prefill):
        """Resolve the prefill's one unresolved chunk, if it has one:
        wait for its outputs and return its sampled token (None if
        nothing was pending).  THE one place a chunk is waited for —
        before the next chunk's dispatch, at a prompt's landing, and
        before anything that ends the prefill early — so every
        dispatched chunk is settled exactly once, and a routed-expert
        model's assignments are counted exactly once, here; a chunk
        whose settle raises counts nothing and fails its own request
        in the caller's handler.  The caller stamps ``chunk_prefill``.

        ``dllm_prefill_chunk_ms`` is taken here, where the wait is: from
        when the host last saw the device reach the chunk (the fetch of
        the tick it was queued behind, or its own dispatch if that came
        later) to its outputs being ready — the chunk's device time,
        plus however late the host came to look."""
        out = pf.pending
        if out is None:
            return None
        pf.pending = None
        # dllm-lint: disable=transfer-host-sync -- sanctioned: the ONE wait per chunk, taken one chunk late (the host runs at most one chunk ahead of the device, so a failed chunk still fails its own request and the TBT bound stays one budget grant a tick); only a prompt's last chunk is waited for in its own pass, for the token its slot starts from
        out = jax.block_until_ready(out)
        chunk_ms = (time.perf_counter()
                    - max(pf.pending_t, self._tick_fetched_t)) * 1000.0
        self.phases.add_time("prefill", chunk_ms / 1000.0)
        try:
            from ..obs import get_observability
            get_observability().m.prefill_chunk_ms.labels(
                self.tier.name).observe(chunk_ms)
        except Exception:
            pass
        self._progress_t = time.monotonic()
        return self._chunk_result(out)

    def _fail_prefill(self, pf: _Prefill, exc: BaseException) -> None:
        """A chunk (its host work, its dispatch or its settle) raised:
        fail the prefill's OWN request and free what it held.  An
        unresolved chunk is dropped unwaited and uncounted: whatever
        reuses its blocks is ordered behind it by the pool."""
        req = pf.request
        self._prefill = None
        pf.pending = None
        if pf.promote_entry is not None and self.kv_spill is not None:
            self.kv_spill.release(pf.promote_entry, promoted=False)
            pf.promote_entry = None
        slot = self._slots[pf.slot_ix]
        if slot is not None and slot.request is req:
            # The final chunk had already gone live as a slot when
            # the failure surfaced: the SLOT owns the blocks now.
            self._fail_slot(pf.slot_ix, exc)
            return
        self.allocator.free(pf.blocks)
        req.error = exc
        if req.token_queue is not None:
            req.token_queue.put(None)
        req.done.set()

    def _advance_promotion(self, pf: _Prefill, budget_left: int):
        """Spend part of this tick's chunk budget landing host→device
        promotion grants (ISSUE 14): up to ``host_kv_promote_share`` of
        the budget, charged one block = one kv_block_size-token grant,
        so promotion competes with chunk grants under ONE budget and the
        active streams' TBT bound is unchanged.  Every copy is an async
        upload + jitted scatter — no sync; the suffix chunk prefill that
        follows depends on the writes ON DEVICE, so ordering is the
        stream's job, never a host wait.

        Returns (progressed, budget_left); clears ``pf.promote_*`` on
        completion (``consumed`` jumps to the matched length) or on
        abort — an invalidated entry or a wedged copier loses the race
        and the prefill restarts COLD from position 0 this same tick,
        byte-identical under greedy (the race-fallback contract)."""
        from .kv_spill import COPYING, DEAD
        spill = self.kv_spill
        entry = pf.promote_entry
        bs = self.paged.block_size
        req = pf.request
        state = spill.entry_state(entry)
        if state is COPYING:
            # Hit-during-demotion: the demote copy hasn't landed yet —
            # wait it out (the copier is ms away), bounded so a wedged
            # copier cannot park the prefill lane forever.
            pf.promote_waits += 1
            if pf.promote_waits <= self._promote_wait_cap:
                return False, budget_left
            state = DEAD                        # wedged: lost the race
        # Snapshot the host buffers WITH the state verdict: a concurrent
        # invalidation nulls entry.tiles, and a local reference cannot
        # be nulled under the grant loop below.
        host_tiles = entry.tiles
        if host_tiles is None and state is not DEAD:
            state = DEAD                        # invalidated between reads
        if state is DEAD:
            spill.release(entry, promoted=False, race=True)
            pf.promote_entry = None
            pf.promote_done = 0
            pf.consumed = 0
            obs_spans.event(req.trace, "kv_promote_race",
                            fallback="cold_prefill")
            return True, budget_left            # cold chunks proceed NOW
        share = max(0.0, min(1.0, self.tier.host_kv_promote_share))
        promo_budget = max(bs, int(self.chunk_budget * share))
        progressed = False
        spent = 0
        while pf.promote_done < pf.promote_nb:
            grain = min(budget_left, promo_budget - spent) // bs
            k = min(pf.promote_nb - pf.promote_done, grain)
            if k <= 0:
                break
            need = pf.promote_done + k
            if len(pf.blocks) < need:
                extra = self._alloc_evicting(need - len(pf.blocks))
                if extra is None:
                    # Pool dry: stall exactly like a dry chunk grant —
                    # retry next tick (growth starvation may cancel the
                    # whole prefill first, which releases the pin and
                    # requeues the request).
                    return progressed, budget_left
                pf.blocks.extend(extra)
            lo = pf.promote_done
            tiles = {name: jnp.asarray(arr[:, lo:lo + k])  # dllm-lint: disable=retrace-dynamic-shape -- bounded: k is whole blocks under the per-tick promote budget, so upload widths (and the scatter traces they feed) are capped at promote-budget blocks
                     for name, arr in host_tiles.items()}
            with self.profiler.phase("promote"):
                self._note_compile("spill", ("write", k))
                self.pool = self._spill_write_fn()(
                    # dllm-lint: disable=retrace-dynamic-shape -- bounded: k grants are whole blocks under the per-tick promote budget, so the write family is one trace per grant block count <= promote-budget blocks
                    self.pool, jnp.asarray(pf.blocks[lo:need], jnp.int32),
                    tiles)
            pf.promote_done = need
            budget_left -= k * bs
            spent += k * bs
            progressed = True
            self._progress_t = time.monotonic()
        if pf.promote_done >= pf.promote_nb:
            pf.consumed = pf.promote_tokens
            spill.release(entry, promoted=True)
            pf.promote_entry = None
            obs_spans.event(req.trace, "kv_promoted",
                            tokens=pf.promote_tokens,
                            blocks=pf.promote_nb)
        return progressed, budget_left

    def _finish_prefill(self, pf: _Prefill, first: int) -> None:
        """Last chunk landed: the reserved slot goes live.  Cold
        prefills emit the final chunk's sampled token exactly as the
        monolithic path did; replays discard it and resume from the last
        emitted token (nothing is re-emitted)."""
        req = pf.request
        ix = pf.slot_ix
        self._prefill = None
        obs_spans.annotate(req.trace, prefill_wait_ms=round(
            (time.perf_counter() - pf.t_start) * 1000.0, 3))
        if pf.replay is not None:
            obs_spans.event(req.trace, "replay", replayed_tokens=pf.total,
                            generated=len(pf.replay), chunked=True)
            self._slot_go_live(req, ix, pf.blocks,
                               prompt_len=pf.prompt_len,
                               prompt_ids=pf.prompt_ids, budget=pf.budget,
                               temp=pf.temperature,
                               max_blocks=pf.max_blocks, pos=pf.total,
                               gen=pf.replay)
            return
        ttft_ms = (time.perf_counter() - req.t_submit) * 1000.0
        self._slot_go_live(req, ix, pf.blocks, prompt_len=pf.prompt_len,
                           prompt_ids=pf.prompt_ids, budget=pf.budget,
                           temp=pf.temperature, max_blocks=pf.max_blocks,
                           pos=pf.total, first=first, ttft_ms=ttft_ms)

    def _cancel_prefill(self, reason: str) -> None:
        """Cancel-and-requeue the in-flight prefill: under pool
        starvation the prefill yields FIRST — it has emitted nothing, so
        requeueing it is free, while preempting a DECODING slot forces a
        full replay.  Its unresolved chunk is settled, then the blocks
        return to the pool (what reuses them runs behind the chunk on
        the device: the pool orders it); the request re-enters at the
        scheduler head and restarts from chunk 0 (a replay's parked
        tokens survive untouched, so the eventual stream is still
        byte-identical)."""
        pf = self._prefill
        if pf is None:
            return
        if pf.pending is not None:
            # Settle the unresolved chunk first (one chunk's device
            # time at most): its experts' assignments count like every
            # dispatched chunk's, and a chunk that failed fails its own
            # request instead of re-queueing it.  The blocks go back
            # through the allocator below either way.
            try:
                with self.profiler.phase("chunk_prefill"):
                    self._settle_chunk(pf)
            except BaseException as exc:   # surface to the caller
                self._fail_prefill(pf, exc)
                return
        self._prefill = None
        if pf.promote_entry is not None and self.kv_spill is not None:
            # Mid-promotion cancel (starvation/stop): drop the pin so
            # the host entry is evictable again; re-admission re-claims
            # it (or goes cold if it is gone by then).
            self.kv_spill.release(pf.promote_entry, promoted=False)
            pf.promote_entry = None
        self.allocator.free(pf.blocks)
        self.prefill_cancelled_total += 1
        req = pf.request
        req.needs_chunk = True
        obs_spans.event(req.trace, "prefill_cancelled", reason=reason,
                        consumed_tokens=min(pf.consumed, pf.total))
        self._head.appendleft(req)

    def _preempt(self, slot_ix: int) -> None:
        """Evict a RUNNING slot under block starvation: free its blocks,
        park its generated tokens on the request, and re-queue it at the
        scheduler head.  Its caller/stream sees a stall — no sentinel, no
        error — and _admit_replay later resumes it byte-identically."""
        slot = self._slots[slot_ix]
        req = slot.request
        req.replay_tokens = list(slot.tokens)
        req.replay_ttft_ms = slot.ttft_ms
        req.preempt_count += 1
        self.preempted_total += 1
        obs_spans.event(req.trace, "preempt", tier=self.tier.name,
                        generated=len(slot.tokens),
                        freed_blocks=len(slot.blocks))
        try:
            # No injection path on the engine (same pattern as the
            # manager's wedge counter): the process-global registry.
            from ..obs import get_observability
            get_observability().m.preemptions.labels(self.tier.name).inc()
        except Exception:
            pass
        self._release(slot_ix)               # free ALL blocks, no parking
        self._head.appendleft(req)

    def _spec_plan(self, active: List[int]) -> Optional[int]:
        """The γ bucket this tick's speculative round compiles at, or
        None for a plain decode tick (spec off, or no active slot is
        both eligible and above γ=0 — every degraded batch falls back
        to the T-step plain tick, so an all-low-acceptance engine pays
        zero speculative overhead)."""
        if not self.spec:
            return None
        gmax = 0
        for ix in active:
            slot = self._slots[ix]
            if slot is not None and slot.spec and slot.gamma > 0:
                gmax = max(gmax, slot.gamma)
        return self._gamma_bucket(gmax) if gmax else None

    def _ensure_spec_private(self, active: List[int], gb: int) -> None:
        """The PR 10 rollback constraint, enforced BEFORE the round: a
        speculative tick writes (and a rejection abandons) positions
        ``[pos, pos+γ]`` in every active slot, so every block covering
        that window must be slot-private — a shared (refcount>1) or
        parked-prefix block there is COW-copied first, exactly like the
        admit boundary (one decref'd reference back to the sharers,
        one fresh private copy in BOTH pools).  By construction the
        admission paths never map a shared block at the write frontier
        (the boundary COW runs at admit), so this is the defensive
        backstop the rollback contract demands, not a hot loop: the
        refcount probe is one batched read per slot per spec tick.  A
        pool too dry to COW preempts the slot (replay is the uniform
        starvation answer) rather than ever writing a sharer-visible
        block."""
        bs = self.paged.block_size
        for ix in active:
            slot = self._slots[ix]
            if slot is None:
                continue
            lo = int(self._pos[ix]) // bs
            hi = min((int(self._pos[ix]) + gb) // bs, len(slot.blocks) - 1)
            if hi < lo:
                continue
            idxs = list(range(lo, hi + 1))
            refs = self.allocator.refcounts(
                [slot.blocks[i] for i in idxs])
            for i, r in zip(idxs, refs):
                if r <= 1:
                    continue
                fresh = self._alloc_evicting(1)
                if fresh is None:
                    self._preempt(ix)
                    break
                try:
                    with self.profiler.phase("cow_copy"):
                        self.pool = self._cow_copy_fn()(
                            self.pool, jnp.asarray(slot.blocks[i], jnp.int32),
                            jnp.asarray(fresh[0], jnp.int32))
                        self.pool_d = self._cow_copy_fn_d()(
                            self.pool_d, jnp.asarray(slot.blocks[i], jnp.int32),
                            jnp.asarray(fresh[0], jnp.int32))
                except BaseException:
                    # The copy never landed: the slot still maps the
                    # shared block, so only the private copy unwinds.
                    self.allocator.free(fresh)
                    raise
                shared = slot.blocks[i]
                slot.blocks[i] = fresh[0]
                self.allocator.free([shared])    # decref: sharers keep it
                self._set_table_row(ix, self._table_row(slot.blocks))
                obs_spans.event(slot.request.trace, "spec_cow",
                                block=shared, copy=fresh[0])

    def _spec_steps(self, slot: _Slot, gb: Optional[int] = None) -> int:
        """Positions past ``pos`` a speculative round must land in REAL
        blocks for this slot: its own γ+1 chunk rows (capped by the
        tick's bucket when given).  Rows past a slot's γ still compute
        — the verify is one fused call — but their writes fall off the
        table row into the trash block and their picks are never
        accepted, so growth (and the rewound frontier) only ever covers
        the slot's OWN speculation depth, not the batch max."""
        g = slot.gamma if slot.spec else 0
        if gb is not None:
            g = min(g, gb)
        return g + 1

    def _rewind_frontier(self, ix: int) -> None:
        """Roll a slot's rejected speculative tail back: free every
        block past what the slot's NEXT round can write (its accepted
        frontier plus its own γ+1 runway — a γ that just adapted DOWN
        releases the deeper tail immediately, and a degraded γ=0 slot
        keeps exactly the plain-decode footprint).  Keeping the runway
        rather than rewinding to the bare frontier stops a healthy
        slot's alloc/free/table-upload ping-pong (growth would re-take
        the same blocks next round); under real pool pressure the
        growth path's eviction/preemption still reclaims runways.
        Leading shared-prefix blocks are never in the freed tail (the
        tail is the youngest, slot-private end of the block list), and
        freeing is a refcounted decref regardless — a rollback can
        shrink this slot's mapping but never mutate a sharer's."""
        slot = self._slots[ix]
        if slot is None:
            return
        bs = self.paged.block_size
        end = int(self._pos[ix]) + self._spec_steps(slot)
        need = max(1, min(slot.max_blocks, -(-end // bs)))
        if len(slot.blocks) <= need:
            return
        tail = slot.blocks[need:]
        del slot.blocks[need:]
        self.allocator.free(tail)
        self._set_table_row(ix, self._table_row(slot.blocks))

    def _ensure_growth(self, active: List[int],
                       spec_gb: Optional[int] = None) -> None:
        """Pre-tick lazy KV growth: every active slot's table must cover
        the positions this tick will write (bounded by the slot's own
        budget) — ``decode_steps_per_tick`` positions for a plain tick;
        for a speculative round (``spec_gb`` set) each slot's OWN γ+1
        chunk depth (deeper rows of the fused verify fall off the table
        into the trash block and are never accepted, so growing to the
        batch-max bucket would buy nothing).  When the pool runs dry —
        even after evicting parked prefixes — the YOUNGEST slot is
        preempted: freed blocks un-starve the elders, and the victim
        replays on re-admission."""
        for ix in active:
            slot = self._slots[ix]
            if slot is None:
                continue                     # preempted earlier this pass
            need = self._blocks_needed(
                ix, slot, self.steps_per_tick if spec_gb is None
                else self._spec_steps(slot, spec_gb))
            while len(slot.blocks) < need:
                extra = self._alloc_evicting(need - len(slot.blocks))
                if extra is not None:
                    slot.blocks.extend(extra)
                    self._set_table_row(ix, self._table_row(slot.blocks))
                    break
                if self._prefill is not None:
                    # The in-flight chunked prefill yields before any
                    # DECODING slot: it has emitted nothing, so a
                    # cancel-and-requeue costs only re-prefilling,
                    # while preempting a decoder forces a full replay.
                    self._cancel_prefill("kv pressure: decoding slot "
                                         "growth starved")
                    continue
                victims = [j for j in active if self._slots[j] is not None]
                if victims == [ix]:
                    # Sole occupant of a pool that cannot hold its next
                    # block: preempting itself would replay straight into
                    # the same wall (livelock).  Cap the generation here —
                    # a short answer beats no answer.
                    obs_spans.event(slot.request.trace, "kv_truncated",
                                    generated=len(slot.tokens))
                    self._finish(ix)
                    break
                if self._tenant_quotas is None:
                    victim = max(victims, key=lambda j:
                                 self._slots[j].request.admit_seq)
                else:
                    # Quotas ON: preempt the MOST-OVER-QUOTA tenant's
                    # slot first (resident-KV bill / block budget;
                    # budget-less tenants rank 0.0), breaking ties
                    # youngest-first — the noisy tenant pays for the
                    # pressure it created before any quiet tenant does.
                    bills: Dict[Optional[str], float] = {}
                    def _over(j: int) -> float:
                        t = self._slots[j].request.tenant
                        if t not in bills:
                            q = self._tenant_quota(t)
                            if q is None or not q.kv_blocks:
                                bills[t] = 0.0
                            else:
                                bills[t] = (self.tenant_kv_blocks(t)
                                            / float(q.kv_blocks))
                        return bills[t]
                    victim = max(victims, key=lambda j: (
                        _over(j), self._slots[j].request.admit_seq))
                self._preempt(victim)
                if victim == ix:
                    break                    # the grower itself yielded

    def _next_request(self) -> Optional[_Request]:
        """Head lane (KV-pressure deferrals, preempted replays) first,
        then the submission queue — FIFO when quotas are off, deficit-
        weighted round-robin over per-tenant lanes when on."""
        if self._head:
            return self._head.popleft()
        if self._tenant_quotas is None:
            try:
                return self._queue.get_nowait()
            except queue.Empty:
                return None
        return self._next_request_dwrr()

    def _next_request_dwrr(self) -> Optional[_Request]:
        """Deficit-weighted round-robin (quotas ON only): arrivals drain
        into per-tenant FIFO lanes; each pass tops every occupied lane's
        deficit up by the tenant's quota weight and serves lanes whose
        deficit covers one request (cost 1).  Tenants iterate in sorted
        order so admission order is deterministic for a given arrival
        interleaving; a lane that empties forfeits its deficit (no
        banking idle weight into a later burst)."""
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            t = req.tenant or "default"
            self._tenant_lanes.setdefault(t, deque()).append(req)
            self._tenant_deficits.setdefault(t, 0.0)
        occupied = sorted(t for t, lane in self._tenant_lanes.items()
                          if lane)
        if not occupied:
            return None
        # Each top-up adds >= the weight floor to every occupied lane,
        # so some deficit reaches 1.0 within a bounded pass count; the
        # final fallback pop keeps this loop total even if weights are
        # degenerate.
        for _ in range(64):
            for t in occupied:
                if self._tenant_deficits[t] >= 1.0:
                    self._tenant_deficits[t] -= 1.0
                    lane = self._tenant_lanes[t]
                    req = lane.popleft()
                    if not lane:
                        self._tenant_deficits[t] = 0.0
                    return req
            for t in occupied:
                self._tenant_deficits[t] += self._tenant_weight(t)
        t = occupied[0]
        lane = self._tenant_lanes[t]
        req = lane.popleft()
        if not lane:
            self._tenant_deficits[t] = 0.0
        return req

    def _tenant_quota(self, tenant: Optional[str]):
        """The quota row billing decisions read for ``tenant``: the
        tier's explicit map, else the env-assembled default (None only
        when quotas are off entirely)."""
        if self._tenant_quotas is None:
            return None
        return self._tenant_quotas.get(tenant or "default",
                                       self._tenant_default_q)

    def _tenant_weight(self, tenant: Optional[str]) -> float:
        q = self._tenant_quota(tenant)
        if q is None:
            return 1.0
        return max(1e-6, float(q.weight))

    def _finish(self, slot_ix: int) -> None:
        slot = self._slots[slot_ix]
        gen_ids = trim_at_eos(slot.tokens, self.tokenizer.eos_id,
                              self.tokenizer.pad_id)
        req = slot.request
        with obs_spans.span(req.trace, "detokenize", tokens=len(gen_ids)):
            text = self.tokenizer.decode(gen_ids)
        req.result = GenerationResult(
            text=text,
            token_ids=gen_ids,
            prompt_tokens=slot.prompt_len,
            gen_tokens=len(gen_ids),
            ttft_ms=slot.ttft_ms,
            total_ms=(time.perf_counter() - req.t_submit) * 1000.0,
        )
        self._release(slot_ix, park=True)
        if req.token_queue is not None:
            req.token_queue.put(None)        # end-of-stream sentinel
        req.done.set()

    def _release(self, slot_ix: int, park: bool = False) -> None:
        slot = self._slots[slot_ix]
        if slot.pinned_entry is not None and self.prefix_cache is not None:
            # Shared-hit slot: drop the pin FIRST (the entry becomes
            # evictable again); the block references themselves drop
            # through the uniform refcounted free()/park below.
            self.prefix_cache.unpin(slot.pinned_entry)
        parked = False
        if park and self.prefix_cache is not None and slot.prompt_ids:
            # Park the blocks covering the prompt (ownership moves to the
            # store); generation-only trailing blocks go back to the pool.
            keep = -(-slot.prompt_len // self.paged.block_size)
            if 0 < keep <= len(slot.blocks):
                cache: Dict[str, Any] = {"blocks": slot.blocks[:keep]}
                if self._tenant_quotas is not None:
                    # Tag the parked entry with its owning tenant so
                    # tenant_kv_blocks bills it and _parked_overquota
                    # can sacrifice it first (quotas-off dict shape
                    # unchanged — byte-identity contract).
                    cache["tenant"] = slot.request.tenant or "default"
                parked = self.prefix_cache.put(slot.prompt_ids, cache)
                if parked:
                    self.allocator.free(slot.blocks[keep:])
        if not parked:
            self.allocator.free(slot.blocks)
        self._slots[slot_ix] = None
        self._set_table_row(slot_ix, TRASH_BLOCK)
        self._pos[slot_ix] = 0
        self._cur[slot_ix] = 0
        self._drop_carry()

    def _fail_slot(self, slot_ix: int, exc: BaseException) -> None:
        slot = self._slots[slot_ix]
        if slot is None:
            # Already released (a preemption raced the failing tick's
            # active snapshot): failing it twice would NPE inside the
            # scheduler's exception handler and kill the loop.
            return
        req = slot.request
        self._release(slot_ix)
        req.error = exc
        if req.token_queue is not None:
            req.token_queue.put(None)
        req.done.set()

    def _emit_spec(self, active: List[int], out, n_acc, gammas) -> None:
        """Apply one speculative round's verdicts: per slot, emit the
        accepted draft prefix plus the target's pick (``n_acc+1``
        tokens, 1 for a γ=0/rejected-first slot — exactly plain decode's
        emission), fold the observed acceptance into the slot's EWMA →
        next-round γ, and rewind the rejected tail's block frontier.
        Budget/EOS/PAD termination applies per token with the SAME rules
        as the plain emit loop (mid-round stoppers discard the rest of
        their round, like a mid-tick finisher discards its overshoot)."""
        tick_drafted = tick_accepted = 0
        # The mirrors move by what was accepted, not by a tick's steps:
        # whatever the device holds of them is stale.
        self._drop_carry()
        with self.profiler.phase("emit"):
            for ix in active:
                slot = self._slots[ix]
                if slot is None:
                    continue                 # preempted by the COW guard
                k = int(n_acc[ix])
                g_i = int(gammas[ix])
                if slot.spec and g_i > 0:
                    rate = k / g_i
                    slot.accept_ewma = ((1.0 - SPEC_EWMA_ALPHA)
                                        * slot.accept_ewma
                                        + SPEC_EWMA_ALPHA * rate)
                    slot.gamma = self._adapt_gamma(
                        slot.accept_ewma,
                        cap=self._tenant_gamma_cap(slot.request))
                    slot.spec_drafted += g_i
                    slot.spec_accepted += k
                    tick_drafted += g_i
                    tick_accepted += k
                    acc = self._spec_slot_acc.setdefault(ix, [0, 0])
                    acc[0] += g_i
                    acc[1] += k
                    if slot.gamma == 0:
                        obs_spans.event(slot.request.trace,
                                        "spec_degraded",
                                        accept_ewma=round(
                                            slot.accept_ewma, 4))
                finished = False
                for t in range(k + 1):
                    tok = int(out[ix, t])
                    slot.tokens.append(tok)
                    obs_spans.add_token(slot.request.trace)
                    if slot.request.token_queue is not None:
                        slot.request.token_queue.put(tok)
                    self._pos[ix] += 1
                    self._cur[ix] = tok
                    hit_cap = len(slot.tokens) >= slot.budget
                    hit_end = (tok in (self.tokenizer.eos_id,
                                       self.tokenizer.pad_id)
                               or self._pos[ix]
                               >= self.cfg.max_seq_len - 1)
                    if hit_cap or hit_end:
                        self._finish(ix)
                        finished = True
                        break
                if not finished:
                    # Rejected-tail rollback: blocks grown for draft
                    # positions past the accepted frontier go back to
                    # the pool NOW (PR 5/9 frontier bookkeeping; stale
                    # KV inside kept blocks is masked until overwritten).
                    self._rewind_frontier(ix)
        self.spec_drafted_total += tick_drafted
        self.spec_accepted_total += tick_accepted
        if tick_drafted:
            try:
                # No injection path on the engine (same pattern as the
                # preemption counter): the process-global registry.
                from ..obs import get_observability
                m = get_observability().m
                m.spec_drafted.labels(self.tier.name).inc(tick_drafted)
                m.spec_accepted.labels(self.tier.name).inc(tick_accepted)
            except Exception:
                pass

    def _tick_tables(self, last_pos: int):
        """The block tables for a tick whose furthest slot stands at
        ``last_pos``: (device array, its width in blocks, whether it had
        to be uploaded now).  One upload per (table change, rung), not
        one per tick: ``_set_table_row`` clears the cache."""
        if self.ragged:
            # Ragged fused tick: the FULL tables go to one
            # attention.ragged_decode call with true per-slot lengths —
            # shape-stable, so exactly ONE compiled decode program
            # serves the engine's life.
            wb = self.paged.blocks_per_slot
            if self._tables_dev is not None:
                return self._tables_dev, wb, False
            with self.profiler.phase("table_upload"):
                self._tables_dev = jnp.asarray(self._tables)
            return self._tables_dev, wb, True
        # Dense windowed tick: bound the per-step pool gather by a
        # bucketed high-water mark over active slots (positions written
        # this tick stay < window); jit retraces per distinct width, one
        # compile per bucket crossed as conversations grow.
        wb = (self._suffix_window(last_pos + self.steps_per_tick)
              // self.paged.block_size)
        tables = self._tables_dev_w.get(wb)
        if tables is not None:
            return tables, wb, False
        with self.profiler.phase("table_upload"):
            # dllm-lint: disable=retrace-dynamic-shape -- bounded by design: wb only takes values from the validated bucket ladder, so this is the dense rung-ladder program family PR 6 documents (ragged mode removes it); the cache above bounds the UPLOADS to one per table change
            tables = jnp.asarray(self._tables[:, :wb])
        self._tables_dev_w[wb] = tables
        return tables, wb, True

    def _blocks_needed(self, ix: int, slot: _Slot, steps: int) -> int:
        """Blocks slot ``ix``'s table must hold for the positions up to
        ``steps`` past where it stands, bounded by its own budget."""
        end = min(int(self._pos[ix]) + steps,
                  slot.prompt_len + slot.budget, self.cfg.max_seq_len)
        return min(slot.max_blocks, -(-end // self.paged.block_size))

    def _prepare_ahead(self, active: List[int], in_flight: int = 1) -> None:
        """Between a plain tick's dispatch and its fetch, while the
        host would only wait: the blocks the NEXT tick's positions need
        (the mirrors still stand before the ``in_flight`` ticks
        dispatched and not emitted, so that span ends ``in_flight + 1``
        ticks ahead: two behind a tick alone, three behind one
        dispatched ahead of the fetch before it) and the upload of the
        table rung it will take.  Only what the allocator gives
        outright: a dry pool is left to the next pass's
        ``_plan_and_grow`` with its evictions, cancellations and
        preemptions (and keeps that tick from going ahead).  The
        running tick holds the table it was given; a slot that ends in
        it frees these blocks with its others.  Stamped
        ``prepare``/``table_upload`` like the work it takes off the next
        pass."""
        steps = in_flight * self.steps_per_tick
        with self.profiler.phase("prepare"):
            for ix in active:
                slot = self._slots[ix]
                if slot is None:
                    continue
                short = (self._blocks_needed(
                    ix, slot, steps + self.steps_per_tick)
                    - len(slot.blocks))
                if short <= 0:
                    continue
                extra = self.allocator.alloc(short)
                if extra is not None:           # else: the pass's own growth
                    slot.blocks.extend(extra)
                    self._set_table_row(ix, self._table_row(slot.blocks))
            self._tick_tables(int(self._pos[active].max()) + steps)

    def _plan_and_grow(self, active: List[int]):
        """The host work a tick needs before its uploads: the
        speculative plan and lazy KV growth (with the preemptions and
        COW copies either may force).  Returns the surviving active
        slots and the round's γ bucket (None = plain tick)."""
        # Speculative plan first (ISSUE 15): the round's γ bucket
        # decides how many positions this tick writes, so growth must
        # cover the chunk, not just the plain tick's T steps.
        # Re-planned after growth — a preemption may have evicted the
        # very slot that set the bucket.
        spec_gb = self._spec_plan(active)
        # Lazy KV growth (+ preemption under starvation) BEFORE the
        # tick: every surviving slot's table covers the positions this
        # tick writes.
        self._ensure_growth(active, spec_gb=spec_gb)
        active = [ix for ix, s in enumerate(self._slots) if s is not None]
        if spec_gb is not None:
            spec_gb = self._spec_plan(active)
            if spec_gb is not None:
                # Rollback contract guard (PR 10): every block the
                # round will write — or a rejection will abandon — must
                # be slot-private before the first draft write lands.
                # Runs OUTSIDE the tick's try, same discipline as growth
                # (whose preemption behavior it shares): a COW failure
                # in here must never reach the tick handler that fails
                # a pre-guard active list.
                self._ensure_spec_private(active, spec_gb)
                active = [ix for ix, s in enumerate(self._slots)
                          if s is not None]
                spec_gb = self._spec_plan(active)
            if spec_gb is None and active:
                # Growth (or the COW guard) preempted every speculating
                # slot: the tick falls back to the PLAIN T-step path,
                # but the survivors were only grown for their own γ+1
                # chunk rows (1 position for non-spec slots).  Re-grow
                # for the plain span — a plain tick over under-grown
                # tables would scatter real positions' K/V into the
                # trash block and silently corrupt every later read.
                self._ensure_growth(active, spec_gb=None)
                active = [ix for ix, s in enumerate(self._slots)
                          if s is not None]
        return active, spec_gb

    def _tick_sinks(self, kind: str, window: int):
        """(impl, tick histogram child, tick counter child) for one
        (tick kind, window rung): ``impl`` is 'pallas' where the rung's
        attention is the streamed rows kernel, else 'xla'
        (``decode_attention_form``; a verify step is always XLA), and
        the metric children are resolved once per rung, not per tick —
        what serves decode must be readable off /metrics, not guessed.
        No injection path on the engine (same pattern as the preemption
        counter): the process-global registry.  A registry failure
        leaves the children None and the tick unobserved, never
        failed."""
        sinks = self._tick_sink_cache.get((kind, window))
        if sinks is None:
            streamed = (kind == self._tick_kind and
                        self.decode_attention_form(window) == "streamed")
            impl = "pallas" if streamed else "xla"
            try:
                from ..obs import get_observability
                m = get_observability().m
                sinks = (impl, m.decode_tick_ms.labels(self.tier.name),
                         m.decode_ticks.labels(self.tier.name, kind, impl))
            except Exception:
                sinks = (impl, None, None)
            self._tick_sink_cache[(kind, window)] = sinks
        return sinks

    def _account_tick(self, active: List[int], tick_ms: float, wb: int,
                      spec_gb: Optional[int]) -> None:
        """The bookkeeping between a tick's fetch and its emit: the
        tick ring, per-request cost attribution, the tick's two
        metrics, and the COUNT the roofline estimate is later computed
        from (``_tick_work_estimate``, when ``/stats`` asks — the
        estimate itself is off the tick since ISSUE 26)."""
        window = wb * self.paged.block_size
        kind = self._tick_kind_spec if spec_gb is not None \
            else self._tick_kind
        self.tick_ms.append(tick_ms)
        self.phases.add_time("decode", tick_ms / 1000.0)
        if self.profiler.enabled:
            # Per-request cost attribution (ISSUE 11): the tick's
            # device time divides evenly across the slots it served
            # (one fused call decodes them together — an even split is
            # the honest division of a shared program), and each slot
            # bills blocks-held × 1 tick of KV residency, shared prefix
            # blocks at 1/refcount each (PR 10's dedup lowers the
            # bill).  Sums are conserved by construction: per tick the
            # shares add back up to tick_ms (tests pin 5%).
            share = tick_ms / len(active)
            for ix in active:
                slot = self._slots[ix]
                if slot is None:
                    continue     # ended under this tick, dispatched ahead
                trace = slot.request.trace
                if trace is None:
                    continue     # direct engine use: unbilled
                kv_ticks = self._kv_weights.get(ix)
                if kv_ticks is None:
                    kv_ticks = 0.0
                    for r in self.allocator.refcounts(slot.blocks):
                        kv_ticks += 1.0 / (r if r > 0 else 1)
                    self._kv_weights[ix] = kv_ticks
                obs_spans.charge(trace, share, kv_ticks)
        impl, tick_hist, tick_counter = self._tick_sinks(kind, window)
        if tick_hist is not None:
            tick_hist.observe(tick_ms)
            tick_counter.inc()
        # Roofline work, counted not computed: one entry per (kind,
        # window, slots served, γ bucket) with its ticks and the sum of
        # the per-tick mean KV span.  The XLA paths read the whole
        # window; the streamed rows kernel reads ceil((pos+1)/bs)
        # blocks of each row, taken mid-tick.
        if impl == "pallas":
            mid = (spec_gb if spec_gb is not None
                   else self.steps_per_tick) // 2
            bs = self.paged.block_size
            kv_ctx = float(np.minimum(
                window, ((self._pos[active] + mid) // bs + 1) * bs).mean())
        else:
            kv_ctx = float(window)
        key = (kind, window, len(active), spec_gb)
        acc = self._tick_work.get(key)
        if acc is None:
            acc = self._tick_work[key] = [0, 0.0]
        acc[0] += 1
        acc[1] += kv_ctx

    def _tick_work_estimate(self) -> Dict[str, Dict[str, float]]:
        """FLOPs / HBM bytes / tokens of every decode tick so far, from
        the per-shape tick counts (``PhaseTimer.lazy_work``: read by
        ``/stats``' ``work`` block, never on the tick).
        ``decode_work`` is linear in the KV span, so ticks × the mean
        span gives the same sums the per-tick calls gave."""
        from ..utils import roofline
        for _ in range(3):
            try:
                counts = list(self._tick_work.items())
                break
            except RuntimeError:       # resized by the scheduler mid-copy
                continue
        else:
            counts = []
        total: Dict[str, float] = {}
        kvq = self.tier.kv_quantize
        for (_kind, window, batch, spec_gb), (ticks, kv_sum) in counts:
            if not ticks:
                continue
            kv_ctx = kv_sum / ticks
            if spec_gb is None:
                parts = [roofline.decode_work(
                    self.cfg, self.steps_per_tick, window, batch=batch,
                    wbytes=self._wbytes, kv_quantize=kvq, kv_ctx=kv_ctx)]
            else:
                # Roofline split, sequential-engine style: the draft
                # pays γ+1 sequential small-model steps; the target
                # verify is ONE step whose γ+1 query rows share a
                # single KV read per slot (kv_batch charges B KV
                # streams, not B·(γ+1)).
                parts = [
                    roofline.decode_work(
                        self.cfg_d, spec_gb + 1, window, batch=batch,
                        wbytes=self._wbytes_d, kv_quantize=kvq,
                        kv_ctx=kv_ctx),
                    roofline.decode_work(
                        self.cfg, 1, window, batch=(spec_gb + 1) * batch,
                        wbytes=self._wbytes, kv_quantize=kvq,
                        kv_batch=batch, kv_ctx=kv_ctx)]
            for part in parts:
                for k, v in part.items():
                    total[k] = total.get(k, 0.0) + v * ticks
        return {"decode": total} if total else {}

    # The scheduler thread + fused decode tick: THE hot path.  The
    # transfer lint walks everything reachable from here, project-wide;
    # every device sync/round-trip below either moved to a tick boundary
    # or carries a justification naming why it is sanctioned.
    def _loop(self) -> None:          # dllm-lint: hot-path
        try:
            self._run_scheduler()
        finally:
            # Scheduler-thread-owned cleanup: a still-in-flight chunked
            # prefill re-queues at the head on exit, so stop()'s normal
            # queue drain fails it with the engine-stopped shape without
            # ever touching scheduler-private state from another thread
            # (the _prefill field stays single-writer, like _slots).
            if self._prefill is not None:
                self._cancel_prefill("engine stopping")

    def _run_scheduler(self) -> None:
        while not self._stop.is_set():
            # Admit while there are free slots and queued requests.  A
            # head request deferred because the single chunked-prefill
            # lane is busy stays parked (FIFO holds; re-popping it would
            # re-tokenize a long prompt every tick for nothing).
            admitted_any = False
            head_blocked = (self._prefill is not None and self._head
                            and self._head[0].needs_chunk)
            for ix in (() if head_blocked
                       else range(self.paged.max_slots)):
                if self._slots[ix] is not None:
                    continue
                if (self._prefill is not None
                        and self._prefill.slot_ix == ix):
                    continue             # reserved by the in-flight prefill
                req = self._next_request()
                if req is None:
                    break
                try:
                    # The admission phase covers tokenize + slot/block
                    # bookkeeping; the prefill/COW device calls inside
                    # stamp their own (nested) phases, so self-times
                    # stay disjoint.
                    with self.profiler.phase("admit"):
                        admitted = self._admit(req, ix)
                    if not admitted:
                        # No KV blocks yet, or the prefill lane is busy:
                        # back to the scheduler HEAD so the elder
                        # re-admits before newer work.
                        if (req.needs_chunk and self._prefill is not None
                                and req.t_lane_blocked is None):
                            req.t_lane_blocked = time.perf_counter()
                        self._head.appendleft(req)
                        break
                    admitted_any = True
                    self._progress_t = time.monotonic()
                except BaseException as exc:     # surface to the caller
                    req.error = exc
                    if req.token_queue is not None:
                        req.token_queue.put(None)
                    req.done.set()

            active = [ix for ix, s in enumerate(self._slots) if s is not None]
            spec_gb = None
            if active:
                # Host work before the launch, with the device idle:
                # the first half of the tick's ``prepare`` phase (the
                # second is whatever upload a writer made necessary,
                # below; the next tick's growth and table ride in this
                # tick's shadow, ``_prepare_ahead``).
                with self.profiler.phase("prepare"):
                    active, spec_gb = self._plan_and_grow(active)
            if not active:
                if self._prefill is not None:
                    # No decoding slots: the whole tick is prefill — a
                    # solo long prompt advances one budget grant per
                    # loop pass, so its TTFT approaches the monolithic
                    # path's (per-chunk dispatch overhead aside).  A
                    # DRY-pool stall here gets the same polite 20 Hz
                    # retry the monolithic requeue path gets from the
                    # idle branch below — nothing is decoding, so only
                    # stop()/drain or a freed parked prefix can change
                    # the allocator, and hot-spinning on it would peg
                    # the scheduler core (the serving kv-admission gate
                    # rejects permanently-oversized prompts upstream).
                    progressed = self._advance_prefill()
                    # Commit BEFORE any idle wait: the 50 ms backoff is
                    # not tick work, and folding it into the record's
                    # wall would collapse the coverage metric exactly
                    # when pool pressure makes the timeline interesting.
                    self.profiler.commit(0)
                    if not progressed:
                        with self.profiler.idle_wait():
                            self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    self._progress_t = time.monotonic()
                elif not admitted_any:
                    # Idle is trivially "progressing": the watchdog only
                    # measures staleness while work is pending.  Commit
                    # any stamped work (a failed KV-pressure admission)
                    # before sleeping, for the same coverage reason.
                    self._progress_t = time.monotonic()
                    self.profiler.commit(0)
                    with self.profiler.idle_wait():
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
                else:
                    # Admitted-and-already-finished pass: no wait ran.
                    self.profiler.commit(0)
                continue

            if spec_gb is not None:
                self._spec_round(active, spec_gb)
            else:
                self._plain_ticks(active)

    def _tick_inputs(self, active: List[int], in_flight: int = 0):
        """The second half of a tick's ``prepare``: its table rung and
        its small inputs as the device holds them, uploading what a
        writer dropped since the last tick, and only that (the mirrors
        are the authority); counts the launch.  ``in_flight`` ticks are
        dispatched and not emitted: the mirrors stand that far behind
        what this tick starts from.  (tables, rung in blocks, pos, cur,
        temps)."""
        tables_arg, wb, uploaded = self._tick_tables(
            int(self._pos[active].max()) + in_flight * self.steps_per_tick)
        if uploaded:
            self._count_prepare_upload("tables")
        for what, mirror in (("pos", self._pos), ("cur", self._cur),
                             ("temps", self._temps)):
            if what not in self._carry:
                self._carry[what] = self._upload(mirror)
                self._count_prepare_upload(what)
                uploaded = True
        if self._sync_state_owner():
            self._count_prepare_upload("owner")
            uploaded = True
        self.ticks_launched_total += 1
        self.ticks_resident_total += int(not uploaded)
        return (tables_arg, wb, self._carry["pos"], self._carry["cur"],
                self._carry["temps"])

    def _spec_round(self, active: List[int], spec_gb: int) -> None:
        """One speculative round: γ_bucket drafts per slot in one
        scanned draft call, then ONE fused γ+1-wide ragged verify with
        per-slot acceptance caps as runtime operands.  Two device
        calls, one sync (the verify pull) — the draft phase stamps
        dispatch wall, the verify phase carries the device wait
        (DESIGN.md "Batched speculation" documents the attribution).
        The round keeps the host's split of the key (and its uploads:
        ``_emit_spec`` moves the mirrors by what was accepted, and
        drops)."""
        try:
            with self.profiler.phase("prepare"):
                self._rng, rng = jax.random.split(self._rng)
                tables_arg, wb, pos_dev, cur_dev, temps_dev = \
                    self._tick_inputs(active)
                gammas = np.zeros(self.paged.max_slots, np.int32)
                for ix in active:
                    slot = self._slots[ix]
                    if slot is not None and slot.spec:
                        gammas[ix] = min(slot.gamma, spec_gb)
                gammas_dev = jnp.asarray(gammas)
            t_tick = time.perf_counter()
            with self.profiler.phase("draft"):
                drafted, self.pool_d = self._spec_draft_fn(
                    spec_gb)(self.params_d, self.pool_d,
                             tables_arg, pos_dev, cur_dev)
            with self.profiler.phase("verify"):
                out, n_acc, self.pool = self._spec_verify_fn(
                    spec_gb)(self.params, self.pool, tables_arg,
                             pos_dev, cur_dev, drafted,
                             gammas_dev, temps_dev, rng)
                out, n_acc = _fetch_tick((out, n_acc))
            self._tick_fetched_t = time.perf_counter()
            tick_ms = (self._tick_fetched_t - t_tick) * 1000.0
            with self.profiler.phase("account"):
                self._account_tick(active, tick_ms, wb, spec_gb)
        except BaseException as exc:
            self._tick_failed(active, exc)
            return
        self._emit_spec(active, out, n_acc, gammas)
        if self._prefill is not None:
            self._advance_prefill()
        self._progress_t = time.monotonic()
        self.profiler.commit(len(active))

    def _tick_failed(self, active: List[int], exc: BaseException,
                     key_in: Any = None) -> None:
        """A dead tick must not become a dead scheduler: fail the
        in-flight requests and keep serving new ones.  What the tick
        (and one dispatched ahead behind it, which is dropped unfetched)
        would have handed the next is not to be trusted: the mirrors
        are uploaded, and from ``key_in``, the key a dispatched tick
        was given, the key moves on as if that tick had split it."""
        for ix in active:
            self._fail_slot(ix, exc)
        self._drop_carry()
        if key_in is not None:
            self._rng = jax.random.split(key_in)[0]
        self.profiler.commit(len(active))

    def _launch_tick(self, active: List[int], ahead: bool = False) -> _Tick:
        """``prepare`` and ``dispatch`` of one plain tick.  It takes the
        engine's key, splits it itself and hands back what the next one
        starts from, with ``pos`` and ``cur``; only ``toks`` is ever
        fetched.  ``ahead``: the tick before it is dispatched and not
        fetched, so this one queues behind it on the device, takes its
        outputs as they stand there, and the mirrors lag by its
        steps."""
        with self.profiler.phase("prepare"):
            tables_arg, wb, pos_dev, cur_dev, temps_dev = self._tick_inputs(
                active, in_flight=int(ahead))
            if ahead:
                self._count_ahead()
        t0 = time.perf_counter()
        self._note_compile("decode", (wb, self._tp_degree()))
        with self.profiler.phase("dispatch"):
            key_in = self._rng
            try:
                (toks, self._carry["pos"], self._carry["cur"],
                 self._rng), self.pool = self._decode_step()(
                    self.params, self.pool, tables_arg,
                    pos_dev, cur_dev, temps_dev, key_in)
            except BaseException:
                self._rng = jax.random.split(key_in)[0]
                raise
        return _Tick(active, toks, wb, key_in, t0, ahead)

    def _count_ahead(self) -> None:
        self.ticks_ahead_total += 1
        if self._ahead_sink is None:
            try:
                # No injection path on the engine (same pattern as the
                # tick histogram): the process-global registry.
                from ..obs import get_observability
                self._ahead_sink = get_observability(
                ).m.decode_ticks_ahead.labels(self.tier.name)
            except Exception:
                return
        self._ahead_sink.inc()

    def _may_go_ahead(self, tick: _Tick) -> bool:
        """Whether the tick after ``tick`` (dispatched, not fetched) may
        be dispatched BEFORE that fetch, so that the host's fetch,
        account, emit and the next ``prepare`` run under a tick and not
        beside an idle chip.  Four tests on what the host already holds
        (DESIGN.md "The pass's two orders"); where any fails the pass
        settles ``tick`` first, today's order:

        1. every slot is taken: nothing queued could be admitted before
           a slot ends, so no arrival waits for a tick already queued;
        2. no slot reaches its budget or the span within ``tick`` (the
           mirrors stand before it): a known end is settled first and
           the freed slot re-admitted as early as ever;
        3. no chunked prefill is in flight, the next tick is a plain
           one, and the engine is not stopping;
        4. the carry is whole (no writer dropped it since ``tick`` was
           dispatched) and the next tick's blocks are there
           (``_prepare_ahead`` got them outright: no eviction, no
           preemption).

        What it cannot see is an EOS/PAD in ``tick``: that slot's rows
        of the next tick compute tokens that are thrown away
        (``ahead_dead_slot_steps_total``)."""
        if (self._stop.is_set() or self._prefill is not None
                or not self._carry.keys() >= {"pos", "cur", "temps"}
                or self._spec_plan(tick.active) is not None):
            return False
        steps = self.steps_per_tick
        last = self.cfg.max_seq_len - 1
        for ix, slot in enumerate(self._slots):
            if (slot is None
                    or len(slot.tokens) + steps >= slot.budget
                    or int(self._pos[ix]) + steps >= last
                    or self._blocks_needed(ix, slot, 2 * steps)
                    > len(slot.blocks)):
                return False
        return True

    def _plain_ticks(self, active: List[int]) -> None:
        """One plain tick from its launch to its emit and, for as long
        as ``_may_go_ahead`` allows, the ticks after it, each dispatched
        AHEAD of the fetch of the one before: one profiler record and
        one fetch a tick either way, and never more than one tick in
        flight beyond the one being fetched.  Returns with nothing in
        flight, so whatever runs at the loop's top (admissions, growth
        with its evictions and preemptions, a stop) sees the mirrors
        settled.

        ``decode`` is the tick as the device sees it, from its launch
        to the return of its fetch; between the two, while a chunked
        prefill is in flight, its chunk of this pass (``chunk_prefill``):
        enqueued behind the tick, so the device goes from the tick
        straight into the chunk while the host fetches, accounts and
        emits."""
        chunk_spent = 0
        tick = nxt = None
        while True:
            try:
                if tick is None:
                    tick = self._launch_tick(active)
                    if self._prefill is not None:
                        t_ride = time.perf_counter()
                        chunk_spent = self._ride_chunk()
                        tick.ride_s = time.perf_counter() - t_ride
                    # The device runs the tick: what the NEXT tick
                    # needs and this one's tokens do not decide.
                    self._prepare_ahead(active)
                # The decision stands where ``_plan_and_grow`` stands in
                # the other order, and is stamped like it.
                with self.profiler.phase("prepare"):
                    ahead = self._may_go_ahead(tick)
                nxt = None
                if ahead:
                    nxt = self._launch_tick(active, ahead=True)
                    self._prepare_ahead(active, 2)
                toks = self._fetch_and_account(tick)
            except BaseException as exc:
                self._tick_failed(active, exc,
                                  None if tick is None else tick.key_in)
                return
            self._emit_plain(tick, toks)
            if self._prefill is not None:
                # Decode slots served: what is left of the tick's
                # prefill budget after the chunk that rode behind the
                # tick (all of it if none could: a promotion, a pool
                # that was dry before this emit freed blocks), and a
                # prompt whose last chunk is out lands here — the
                # interleave that bounds active streams' TBT by one
                # budget grant instead of one whole prompt.
                self._advance_prefill(self.chunk_budget - chunk_spent)
            self._progress_t = time.monotonic()  # tick completed
            self.profiler.commit(len(active))
            if nxt is None:
                return
            tick = nxt

    def _fetch_and_account(self, tick: _Tick):
        """The one sanctioned sync, then everything between the fetch
        and the emit: ``account``.  What it holds is priced in PERF.md
        ("what tracing costs") — keep it to dict lookups and float
        adds.  Returns the tick's tokens [T, B]."""
        with self.profiler.phase("fetch"):
            toks = _fetch_tick(tick.toks)
            # Fetched: the device's copy goes here, under a stamp and
            # before the emit wakes anyone, not with the record after it
            # (freeing a device array releases the interpreter's lock).
            tick.toks = None
        if self._moe is not None:
            # The same fetch brought the steps' assignments an expert.
            toks, n_exp = toks
        # One tick's device wait: from its launch, or from the fetch
        # before it where it was dispatched ahead of that (it ran behind
        # that tick, not since its own launch), to its tokens; a riding
        # chunk's host section between the two is the chunk's
        # (dllm_prefill_chunk_ms), not the tick's.
        start = max(tick.t0, self._tick_fetched_t)
        self._tick_fetched_t = time.perf_counter()
        tick_ms = (self._tick_fetched_t - start - tick.ride_s) * 1000.0
        self.profiler.span_from("decode", tick.t0)
        with self.profiler.phase("account"):
            self._account_tick(tick.active, tick_ms, tick.wb, None)
            if self._moe is not None:
                self._moe.note("decode", n_exp)
        return toks

    def _emit_plain(self, tick: _Tick, toks) -> None:
        """A plain tick's tokens to their slots, streams and mirrors.  A
        slot that ended under a tick dispatched ahead (``slot is None``
        from the first step) takes none of them: its index is free, and
        is given to nobody before this emit."""
        active = tick.active
        with self.profiler.phase("emit"):
            if tick.ahead:
                self.ahead_dead_slot_steps_total += toks.shape[0] * sum(
                    1 for ix in active if self._slots[ix] is None)
            for t in range(toks.shape[0]):
                for ix in active:
                    slot = self._slots[ix]
                    if slot is None:
                        continue         # finished at an earlier t
                    tok = int(toks[t, ix])
                    slot.tokens.append(tok)
                    # Tick-granular decode timeline: a tick's T
                    # tokens stamp together because that is when
                    # they become observable (one device call per
                    # tick).  One list append per token — no span
                    # objects on this path.
                    obs_spans.add_token(slot.request.trace)
                    if slot.request.token_queue is not None:
                        slot.request.token_queue.put(tok)
                    self._pos[ix] += 1
                    self._cur[ix] = tok
                    hit_cap = len(slot.tokens) >= slot.budget
                    # PAD ends generation like EOS: trim_at_eos
                    # truncates the result there, so streaming past
                    # it would diverge.
                    hit_end = (tok in (self.tokenizer.eos_id,
                                       self.tokenizer.pad_id)
                               or self._pos[ix]
                               >= self.cfg.max_seq_len - 1)
                    if hit_cap or hit_end:
                        self._finish(ix)

    # -- public surface (InferenceEngine parity) ---------------------------

    def start(self) -> None:
        with self._lifecycle:
            if self._thread is not None:
                return
            self._stop.clear()
            self._thread = threading.Thread(target=self._loop, daemon=True,
                                            name=f"batcher-{self.tier.name}")
            self._thread.start()

    def stop(self) -> None:
        """Join the loop, then fail anything still in flight or queued so
        no caller is left blocked on done.wait()."""
        with self._lifecycle:
            if self._thread is not None:
                self._stop.set()
                self._wake.set()
                self._thread.join(timeout=5)
                self._thread = None
            # Error-SHAPED shutdown (serving/errors.py): TierClient
            # forwards ``.shape`` verbatim, so clients see the validated
            # reference dict, never a stringified bare RuntimeError.
            shutdown = EngineStoppedError(error_dict(
                f"Request failed: tier {self.tier.name} engine stopped "
                f"mid-flight"))
            # The in-flight chunked prefill holds blocks and possibly a
            # spill-promotion pin; cancel BEFORE the cache clear and the
            # spill stop so both unwind into live stores.  The requeued
            # request drains through the shutdown loop below.
            self._cancel_prefill("stop")
            if self.prefix_cache is not None:
                self.prefix_cache.clear()    # parked blocks → free list
                # (_try_demote stands down once _stop is set, so clear
                # frees straight to the allocator — no parting spills.)
            if self.kv_spill is not None:
                # Drain waits out in-flight copies: flush the copier
                # (bounded) before dropping the engine so the host tier
                # is consistent at rest — manager.drain reaches here via
                # stop_server after the request drain completes.
                self.kv_spill.stop()
            for ix, slot in enumerate(self._slots):
                if slot is not None:
                    self._fail_slot(ix, shutdown)
            while True:
                req = self._next_request()   # head lane + queue
                if req is None:
                    break
                req.error = shutdown
                if req.token_queue is not None:
                    req.token_queue.put(None)
                req.done.set()
            from ..config_registry import env_flag
            if env_flag("DLLM_KV_LEAK_CHECK"):
                # Dynamic twin of the lint's own-leak-on-path rule: with
                # every slot failed, the cache cleared, the prefill
                # cancelled and the spill drained, any surviving
                # refcount or pin is a leaked acquire on some path the
                # static pass was talked out of (or suppressed).
                stats = self.allocator.ref_stats()
                assert stats["allocated_blocks"] == 0, (
                    f"DLLM_KV_LEAK_CHECK: {stats['allocated_blocks']} "
                    f"block(s) still allocated after engine stop() "
                    f"(total_refs={stats['total_refs']})")
                if self.kv_spill is not None:
                    pinned = self.kv_spill.stats()["pinned_entries"]
                    assert pinned == 0, (
                        f"DLLM_KV_LEAK_CHECK: {pinned} spill entry "
                        f"pin(s) still held after engine stop()")

    # -- crash rescue (ISSUE 20) -------------------------------------------

    def capture_requests(self) -> List[_Request]:
        """Harvest every queued + in-flight request for a crash rescue:
        join the scheduler loop, park each decoding slot's generated
        prefix on its request (the ``_preempt`` capture — ``_admit_replay``
        later resumes it byte-identically under greedy), unwind the
        in-flight chunked prefill, and drain the head lane, tenant lanes
        and submission queue.  The SAME ``_Request`` objects come back —
        ``done`` events, token queues, traces and tenant identity intact,
        so blocked callers and streams STALL through the rescue instead
        of erroring — and the engine is left empty: a following
        ``stop()`` finds nothing to fail."""
        captured: List[_Request] = []
        with self._lifecycle:
            if self._thread is not None:
                self._stop.set()
                self._wake.set()
                self._thread.join(timeout=5)
                self._thread = None
            # In-flight chunked prefill: the cancel-and-requeue unwind
            # (blocks freed, promote pin dropped into the live spill)
            # parks the request back at the scheduler head, where the
            # drain below collects it.
            self._cancel_prefill("rescue_capture")
            for ix, slot in enumerate(self._slots):
                if slot is None:
                    continue
                req = slot.request
                req.replay_tokens = list(slot.tokens)
                req.replay_ttft_ms = slot.ttft_ms
                req.preempt_count += 1
                obs_spans.event(req.trace, "rescue_capture",
                                tier=self.tier.name,
                                generated=len(slot.tokens))
                self._release(ix)            # free ALL blocks, no parking
                captured.append(req)
            while True:
                req = self._next_request()   # head lane + tenant lanes
                if req is None:
                    break
                captured.append(req)
        return captured

    def adopt_requests(self, reqs: Sequence[_Request]) -> int:
        """Enqueue requests captured off a crashed/wedged sibling.  Each
        re-enters through the normal submission queue (tenant lanes and
        quota billing see the original ``req.tenant``) and a request
        carrying ``replay_tokens`` routes to ``_admit_replay`` on
        admission — identical params + greedy sampling means the
        continuation is byte-identical to the uninterrupted stream.
        Returns the number adopted."""
        self.start()
        n = 0
        for req in reqs:
            # The chunk bookmark belongs to the dead engine's prefill
            # lane; this engine's admission re-derives it.
            req.needs_chunk = False
            req.t_lane_blocked = None
            self._queue.put(req)
            n += 1
        if n:
            self._wake.set()
        return n

    def detach_spill(self) -> Optional["HostKVSpill"]:
        """Hand the host spill store out of the engine's lifetime
        (spill-state survival): flush in-flight demote copies so the
        host tier is consistent, then unhook the instance so a following
        ``stop()`` leaves it RUNNING.  Returns the live store (or None
        when the engine never had one)."""
        spill = self.kv_spill
        if spill is None:
            return None
        try:
            spill.flush(timeout_s=5.0)
        except Exception:
            pass
        self.kv_spill = None
        return spill

    def adopt_spill(self, spill: Optional["HostKVSpill"]) -> bool:
        """Install a surviving host spill store into this (freshly
        rebuilt) engine.  Geometry must match — same per-block host
        bytes and the same min-prefix floor — or the orphan is refused
        and the caller hands it to a sibling instead.  The fresh
        engine's own store, if it built one, is stopped and replaced:
        the survivor holds the warm entries."""
        if (spill is None or self.prefix_cache is None
                or not self.chunk_tokens):
            return False
        if (spill.block_bytes != self._spill_block_bytes
                or spill.min_prefix != self.prefix_cache.min_prefix):
            return False
        old = self.kv_spill
        if old is not None and old is not spill:
            old.stop()
        self.kv_spill = spill
        return True

    def submit(self, history: History,
               max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               token_queue: Optional["queue.Queue"] = None,
               tenant: Optional[str] = None) -> _Request:
        self.start()
        trace = obs_spans.current_trace()
        if tenant is None and trace is not None:
            # Serving path: the router stamps the tenant on the trace
            # (route_query annotate), so TierClient's generate() calls
            # need no signature change to bill correctly.
            try:
                tenant = trace.attrs.get("tenant")
            except Exception:
                tenant = None
        req = _Request(history=history, max_new_tokens=max_new_tokens,
                       temperature=temperature, token_queue=token_queue,
                       trace=trace, tenant=tenant)
        self._queue.put(req)
        self._wake.set()
        return req

    def generate(self, history: History,
                 max_new_tokens: Optional[int] = None,
                 temperature: Optional[float] = None,
                 tenant: Optional[str] = None) -> GenerationResult:
        req = self.submit(history, max_new_tokens, temperature,
                          tenant=tenant)
        req.done.wait()
        if req.error is not None:
            raise req.error
        return req.result

    def generate_stream(self, history: History,
                        max_new_tokens: Optional[int] = None,
                        temperature: Optional[float] = None,
                        tenant: Optional[str] = None):
        """Yield text deltas as tokens come off the shared decode loop
        (SURVEY.md §7 hard part 6 — the reference API is non-streaming,
        but TTFT-aware serving wants streaming internals).  The final
        GenerationResult is ``.result`` on the returned generator's
        request once exhausted; multi-byte UTF-8 sequences are held back
        until complete."""
        from .tokenizer import StreamDecoder
        # The consumer's lane on the tick profiler's timeline
        # (obs/profiler.py "Edge lanes"), made before the submit: it
        # notes how far the trace's token timeline is written already.
        lane = self.profiler.edge_lane(obs_spans.current_trace())
        req = self.submit(history, max_new_tokens, temperature,
                          token_queue=queue.Queue(), tenant=tenant)

        def deltas():
            decoder = StreamDecoder(self.tokenizer)
            tokens = req.token_queue
            taken = 0
            lane.open()
            try:
                while True:
                    # One consumer: what is queued stays queued, so a
                    # get after a non-empty look never waits.  The awake
                    # slice closes before a wait and opens after it;
                    # tokens that were queued extend the open slice.
                    if tokens.empty():
                        lane.sleep(taken)
                        tok = tokens.get()
                        lane.wake()
                    else:
                        tok = tokens.get()
                    if tok is None:
                        break
                    taken += 1
                    if tok in (self.tokenizer.eos_id,
                               self.tokenizer.pad_id):
                        continue
                    text = decoder.feed(tok)
                    if text:
                        yield text
                tail = decoder.flush()
                if tail:
                    yield tail
            finally:
                lane.close(taken)
            if req.error is not None:
                raise req.error

        return StreamHandle(deltas(), req)

    def queue_depth(self) -> int:
        """Requests submitted but not yet admitted to a batch slot
        (including KV-pressure deferrals, preempted replays waiting in
        the head lane, and the in-flight chunked prefill — admitted to
        the LANE but not yet decoding, it must stay visible to routing,
        drain, and the wait predictor)."""
        # Quotas ON parks arrivals in per-tenant DWRR lanes between
        # _queue and admission; they are still waiting work (lanes are
        # always empty when quotas are off).  list() snapshots the dict
        # against concurrent lane creation (advisory read).
        laned = sum(len(l) for l in list(self._tenant_lanes.values()))
        return (self._queue.qsize() + len(self._head) + laned
                + (1 if self._prefill is not None else 0))

    def pending_work(self) -> int:
        """Queued + requeued + active requests — the drain loop's
        completion signal (engine/manager.py drain())."""
        return (self.queue_depth()
                + sum(1 for s in self._slots if s is not None))

    # -- KV pressure surface (serving/tiers.py admission gate) -------------

    def kv_stats(self) -> Dict[str, int]:
        """Block-pool pressure snapshot for KV-aware admission: free
        blocks, blocks reclaimable by evicting parked prefix entries, and
        pool geometry.  Advisory reads — the allocator and prefix store
        guard their own state."""
        reclaimable = (self.prefix_cache.reclaimable_blocks()
                       if self.prefix_cache is not None else 0)
        # The in-flight chunked prefill's REMAINING demand: blocks it
        # still needs to finish prefilling.  The serving admission gate
        # subtracts this from supply — an admission that consumed those
        # blocks would force a prefill cancel, so they are spoken for
        # even though the allocator still counts them free.  Advisory
        # GIL-safe snapshot (the scheduler thread owns _prefill).
        pf = self._prefill
        pending = backlog = 0
        if pf is not None:
            done = min(pf.consumed, pf.total)
            backlog = pf.total - done
            pending = max(0, min(pf.max_blocks,
                                 -(-pf.total // self.paged.block_size))
                          - len(pf.blocks))
        # Sharing picture (ISSUE 10): physical blocks with >= 2 holders,
        # the dedup factor (logical references / physical blocks — what
        # sharing multiplied the effective pool by), and entries pinned
        # by live sharers.  reclaimable_blocks above already excludes
        # pinned entries and refcount>1 blocks, so the admission gate's
        # supply view (serving/tiers.py) never promises what sharing has
        # pinned; these fields make that view inspectable.
        rs = self.allocator.ref_stats()
        pinned = (self.prefix_cache.stats()["pinned_entries"]
                  if self.prefix_cache is not None else 0)
        # Hierarchical-KV spill picture (ISSUE 14): host-tier occupancy,
        # the demote/promote lifecycle counters, and the in-flight
        # promotion's REMAINING block demand.  Promotion rides the
        # chunked-prefill lane, so its unallocated blocks are already
        # inside prefill_pending_blocks above — the admission gate's
        # supply subtraction covers it with no double count; the
        # explicit backlog field makes a degraded warm-hit rate
        # diagnosable in one /stats call.
        spill_fields: Dict[str, int] = {}
        if self.kv_spill is not None:
            ss = self.kv_spill.stats()
            backlog = 0
            if pf is not None and pf.promote_entry is not None:
                backlog = max(0, pf.promote_nb - pf.promote_done)
            spill_fields = {
                "host_entries": ss["entries"],
                "host_blocks": ss["blocks"],
                "host_bytes": ss["bytes"],
                "host_budget_bytes": ss["budget_bytes"],
                "demotions_total": ss["demotions_total"],
                "promotions_total": ss["promotions_total"],
                "promotion_races_total": ss["promotion_races_total"],
                # Entries whose host copy has not landed (queued jobs'
                # entries are already in the copying state — counting
                # the queue too would double-bill them).
                "demote_inflight": ss["copying_entries"],
                "promote_backlog_blocks": backlog,
            }
        from ..utils.roofline import kv_readers
        cached = self.cfg.kv_layers
        return {
            **spill_fields,
            "free_blocks": self.allocator.available,
            "reclaimable_blocks": reclaimable,
            "block_size": self.paged.block_size,
            "total_blocks": self.paged.num_blocks - 1,   # minus trash
            "preempted_total": self.preempted_total,
            "prefill_pending_blocks": pending,
            "prefill_backlog_tokens": backlog,
            "shared_blocks": rs["shared_blocks"],
            "dedup_ratio": (round(rs["total_refs"]
                                  / rs["allocated_blocks"], 4)
                            if rs["allocated_blocks"] else 1.0),
            "pinned_entries": pinned,
            # Layers whose K/V the blocks hold, and layers that read them
            # (the shared-K/V family caches ONE layer for all after it).
            "cached_layers": cached,
            "cache_readers": cached * kv_readers(self.cfg),
        }

    def max_demand_blocks(self) -> int:
        """Worst-case per-request demand (largest prefill bucket + full
        decode budget), tokenization-free: when free+reclaimable covers
        this, the admission gate cannot fire and the serving thread skips
        the per-request prompt tokenization entirely."""
        bucket = max(self._buckets) if self._buckets else \
            self.cfg.max_seq_len
        return -(-min(bucket + self.tier.max_new_tokens,
                      self.cfg.max_seq_len) // self.paged.block_size)

    def projected_demand_blocks(self, history: History,
                                max_new_tokens: Optional[int] = None
                                ) -> int:
        """Pool blocks this request needs at FULL decode budget (prompt
        bucket + decode cap) — the demand side of the admission gate.
        Tokenizes the history with the same prepare_prompt as _admit;
        runs on the serving thread, before submit."""
        _, bucket = prepare_prompt(self.tokenizer, history,
                                   self.tier.prefill_buckets,
                                   self.cfg.max_seq_len,
                                   self.tier.max_new_tokens)
        budget = self.tier.max_new_tokens
        if max_new_tokens and max_new_tokens > 0:
            budget = min(budget, max_new_tokens)
        return -(-min(bucket + budget, self.cfg.max_seq_len)
                 // self.paged.block_size)

    def progress_stall_s(self) -> float:
        """Seconds since the scheduler last completed a unit of progress
        WHILE work is pending — the decode watchdog's signal.  0.0 when
        the engine is idle (nothing queued, no active slot) or the loop
        isn't running: an idle engine is not wedged.  A stale value with
        pending work means the loop is stuck inside a device call
        (wedged chip) or died — exactly what the round-5 probes couldn't
        see from outside."""
        if self._thread is None:
            return 0.0
        has_work = (self.queue_depth() > 0
                    or any(s is not None for s in self._slots))
        if not has_work:
            return 0.0
        return max(0.0, time.monotonic() - self._progress_t)

    def tick_stats(self) -> Dict[str, Any]:
        """Decode-tick latency quantiles over the recent-tick ring
        (``tick_ms``, maxlen 512) — the read API for the obs state
        sampler and the bench skew/open-loop legs — and the engine-life
        resident share (GET /stats ``tiers.<tier>.tick``).  Advisory
        GIL-safe read of a deque the scheduler thread appends to: a concurrent
        append can abort one iteration pass (RuntimeError), so retry a
        couple of times and report empty rather than block or raise —
        a telemetry read must never synchronize with the decode loop."""
        ticks: List[float] = []
        for _ in range(3):
            try:
                ticks = list(self.tick_ms)
                break
            except RuntimeError:
                continue
        launched = self.ticks_launched_total
        resident = self.ticks_resident_total
        ahead = self.ticks_ahead_total
        # How often the next tick's inputs were already on the device:
        # decode ticks launched with no upload in ``prepare``, and the
        # uploads that were made there, by what.  And how often the
        # next tick was on the device's queue before the host fetched
        # the one before it, with what that order wasted.
        out = {"n": len(ticks), "p50_ms": None, "p95_ms": None,
               "launched_total": launched, "resident_total": resident,
               "resident_share": (round(resident / launched, 4)
                                  if launched else None),
               "ahead_total": ahead,
               "ahead_share": (round(ahead / launched, 4)
                               if launched else None),
               "ahead_dead_slot_steps_total":
                   self.ahead_dead_slot_steps_total,
               "prepare_uploads": dict(self.prepare_uploads_total),
               "attention_form": dict(self._attention_forms)}
        if not ticks:
            return out
        # ONE snapshot, ONE sort, reused for every quantile: this runs
        # at the sampler's 4 Hz per tier, and nearest_rank's internal
        # sort per quantile re-sorted the whole 512-entry ring twice
        # per collect on top of the snapshot sort (the ISSUE 11 small
        # fix) — the <1 ms/sample budget has to survive rings and tier
        # counts growing.
        ticks.sort()

        def pct(q: float) -> float:
            return round(obs_metrics.nearest_rank(ticks, q,
                                                  presorted=True), 3)

        out.update(p50_ms=pct(0.5), p95_ms=pct(0.95))
        return out

    def moe_stats(self) -> Optional[Dict[str, Any]]:
        """Cumulative routed-expert load (GET /stats ``moe``), or None
        for a model without routed experts on this path."""
        if self._moe is None:
            return None
        return {**self._moe.stats(),
                "grouped_product": self.grouped_product_form()}

    def grouped_product_form(self) -> Dict[str, str]:
        """What the tick (``decode``) and the chunk program (``prefill``)
        were traced with for the routed experts' FFN: ``pallas_ffn`` (ONE
        call a layer, ``ops/grouped_product.py`` ``grouped_ffn``),
        ``pallas`` (a call of ``grouped_product`` a product) or
        ``ragged_dot`` — the static test ``latent_moe.expert_ffn`` makes,
        on these programs' shapes; a fact of each compiled program, like
        ``decode_attention_form``."""
        stacks = models.model_module(self.cfg).expert_stacks(self.params)
        return {stage: models.latent_moe.grouped_product_form(
                    self.cfg, stacks, tokens)
                for stage, tokens in (("decode", self.paged.max_slots),
                                      ("prefill", self.chunk_tokens))
                if tokens}

    def _chunk_result(self, out):
        """The sampled token of a chunk program's (already synced) first
        output; a routed-expert model's chunk brings its assignments an
        expert in the same output, counted here."""
        if self._moe is None:
            return out
        first, n_exp = out
        self._moe.note("prefill", n_exp)
        return first

    def slot_stats(self) -> Dict[str, Any]:
        """Live occupancy snapshot for health()/telemetry: queued
        requests, busy batch slots, and occupancy in [0,1].  Read from
        the scheduler's slot list without a lock — single-word reads of
        a list the scheduler thread owns, safe under the GIL; the
        snapshot is advisory (routing signal), not a synchronization
        point."""
        active = sum(1 for s in self._slots if s is not None)
        total = self.paged.max_slots
        pstats = self.prefill_stats()
        # Per-slot speculative γ (ISSUE 15): {slot_ix: γ} over ACTIVE
        # slots — γ=0 entries are slots degraded to plain ragged decode
        # (or spec-ineligible ones), so an operator sees at a glance
        # which tenants are still speculating.  Empty when spec is off.
        gammas: Dict[str, int] = {}
        if self.spec:
            for ix, s in enumerate(self._slots):
                if s is not None:
                    gammas[str(ix)] = s.gamma if s.spec else 0
        return {
            "queue_depth": self.queue_depth(),
            "active_slots": active,
            "max_slots": total,
            "slot_occupancy": round(active / max(1, total), 3),
            "preempted_total": self.preempted_total,
            # Chunked-prefill backlog rides the health()/GET /stats
            # snapshot: an operator reading a TTFT spike sees whether a
            # long prompt is mid-absorption.
            "prefill_inflight": pstats["inflight"],
            "prefill_backlog_tokens": pstats["backlog_tokens"],
            "spec_gammas": gammas,
        }

    def spec_stats(self) -> Dict[str, Any]:
        """Batched-speculation snapshot (ISSUE 15): lifetime draft /
        accept counters (the dllm_spec_* counters' source), the running
        acceptance ratio the ``dllm_spec_accept_ratio`` sampler gauge
        mirrors, and the live per-slot γ map.  Advisory GIL-safe reads
        of scheduler-owned state, same discipline as slot_stats."""
        drafted = self.spec_drafted_total
        accepted = self.spec_accepted_total
        return {
            "enabled": self.spec,
            "gamma_max": self.spec_gamma_max,
            "gamma_buckets": list(self._gamma_buckets),
            "drafted_total": drafted,
            "accepted_total": accepted,
            "accept_ratio": (round(accepted / drafted, 4)
                             if drafted else None),
            "slot_gammas": self.slot_stats()["spec_gammas"],
            "per_slot": {
                str(ix): {"drafted": d, "accepted": a,
                          "ratio": round(a / d, 4) if d else None}
                for ix, (d, a) in sorted(self._spec_slot_acc.items())},
        }

    def prefill_stats(self) -> Dict[str, Any]:
        """In-flight chunked-prefill snapshot: whether one is being
        absorbed, how many prompt tokens remain to dispatch (the backlog
        the ``dllm_prefill_backlog`` gauge samples), chunk progress, the
        engine-life cancel count, and how often a chunk rode behind an
        unfetched tick (``chunks_overlapped_total`` of ``chunks_total``;
        ``overlap_share`` None before the first chunk), and the positions
        the chunks attended over the positions written when they ran
        (``window_over_written``, None before the first chunk).  Advisory
        GIL-safe reads of state the scheduler thread owns — same
        discipline as slot_stats."""
        pf = self._prefill
        chunks = self.prefill_chunks_total
        overlapped = self.prefill_chunks_overlapped_total
        attended = self.prefill_window_positions_total
        written = self.prefill_written_positions_total
        out = {"inflight": 0, "backlog_tokens": 0, "chunks_done": 0,
               "cancelled_total": self.prefill_cancelled_total,
               "chunks_total": chunks,
               "chunks_overlapped_total": overlapped,
               "overlap_share": (round(overlapped / chunks, 4)
                                 if chunks else None),
               "window_positions_total": attended,
               "written_positions_total": written,
               "window_over_written": (round(attended / written, 4)
                                       if written else None),
               "chunks_by_window": dict(sorted(
                   self.prefill_chunks_by_window.items())),
               "chunks_by_attention_form": dict(
                   self.prefill_chunks_by_form),
               "attention_form": {
                   "%dx%d" % key: form for key, form in sorted(
                       self._chunk_attention_forms.items())}}
        if self.cfg.shared_kv:
            out["chunks_self_only_total"] = \
                self.prefill_self_only_chunks_total
        if pf is not None:
            out.update(inflight=1, chunks_done=pf.chunks_done,
                       backlog_tokens=max(0, pf.total - min(pf.consumed,
                                                            pf.total)))
        return out

    def prefix_affinity(self, history) -> int:
        """Longest parked-prefix token match in the paged pool for
        ``history`` (non-destructive; see InferenceEngine.prefix_affinity)."""
        if self.prefix_cache is None or not self._reuse_buckets:
            return 0
        return self.prefix_affinity_tokens(self.affinity_token_ids(history))

    def affinity_token_ids(self, history) -> List[int]:
        """Tokenize ``history`` exactly as admission would — the shared
        half of the affinity probe, split out so replica dispatch
        (serving/replicas.py) tokenizes ONCE and peeks every replica's
        cache with the same ids instead of paying N tokenizations per
        request."""
        ids, _ = prepare_prompt(self.tokenizer, history,
                                self.tier.prefill_buckets,
                                self.cfg.max_seq_len,
                                self.tier.max_new_tokens)
        return ids

    def prefix_affinity_tokens(self, ids: Sequence[int]) -> int:
        """Longest parked-prefix match for already-tokenized ``ids`` —
        the per-replica half of the affinity probe (the same
        select_reuse/_best_match longest-prefix matching block reuse
        runs on; non-destructive peek)."""
        if self.prefix_cache is None or not self._reuse_buckets:
            return 0
        # Same headroom cap as select_reuse's take() — the affinity score
        # must not promise tokens a real reclaim could not use.
        best = self.prefix_cache.peek(
            ids, max_len=self.cfg.max_seq_len - self._reuse_buckets[0])
        if self.kv_spill is not None:
            # Demoted entries are affinity-eligible (ISSUE 14): a
            # session follows its spilled prefix home — promotion beats
            # a cold prefill on a stranger replica.
            best = max(best, self.kv_spill.peek(
                ids, max_len=self.cfg.max_seq_len - self._reuse_buckets[0]))
        return best

    def demote_parked(self) -> int:
        """Evict every unpinned parked prefix entry NOW, each routed
        through the normal eviction sink (``_prefix_evicted`` →
        ``_try_demote``) — the scale-down retirement sweep
        (serving/replicas.py): with a spill tier attached, the retiring
        replica's refcount-1 prefixes land in host RAM for the caller to
        hand to a survivor; without one this is just an eviction sweep.
        Must run BEFORE ``stop()``/``drain`` flips ``_stop`` (after
        which ``_try_demote`` stands down).  Returns entries evicted."""
        if self.prefix_cache is None:
            return 0
        n = 0
        while self.prefix_cache.pop_oldest() is not None:
            n += 1
        return n

    def warmup(self, beat=None) -> None:
        """Compile the decode tick + smallest cold-prefill bucket (via one
        real request), then the chunk-prefill programs for the two smallest
        suffix buckets so the first prefix-reuse admission doesn't pay an
        XLA trace.  Runs before serving traffic: the scheduler is idle
        (no active slots), so mutating the pool here doesn't race a tick.
        ``beat`` fires after each compiled program (liveness for a
        caller's wedge watchdog through multi-minute on-chip warmups)."""
        beat = beat or (lambda: None)
        self.generate("warmup", max_new_tokens=2)
        beat()
        # The DENSE batched decode program retraces per gather-window
        # rung; a mid-serve retrace stalls EVERY active slot for the
        # compile.  The warm request covered the first rung — also
        # compile the second (typical multi-turn growth); deeper rungs
        # stay lazy (one compile each over an engine's life).  All slots
        # are free here (tables point at the trash block), so the extra
        # ticks write only trash.  The RAGGED tick is shape-stable — the
        # warm request already compiled its one program, so there is
        # nothing left to warm.
        for w in ([] if self.ragged else self._buckets[1:2]):
            wb = min(w // self.paged.block_size, self.paged.blocks_per_slot)
            self._note_compile("decode", (wb, self._tp_degree()))
            (toks, _, _, self._rng), self.pool = self._decode_step()(
                self.params, self.pool, jnp.asarray(self._tables[:, :wb]),
                self._upload(self._pos), self._upload(self._cur),
                self._upload(self._temps), self._rng)
            jax.block_until_ready(toks)
            beat()
        if self.spec:
            # Speculative program family (ISSUE 15): the warm request
            # above compiled the TOP γ bucket's draft/verify pair (fresh
            # slots start at γ=spec_gamma_max); the remaining buckets —
            # what adaptation can step a round down to — compile here
            # against the all-trash tables (slots are free, writes land
            # in the trash block), so a mid-serve γ drop never traces.
            zero = jnp.zeros(self.paged.max_slots, jnp.int32)
            for gb in self._gamma_buckets:
                self._rng, rng = jax.random.split(self._rng)
                drafted, self.pool_d = self._spec_draft_fn(gb)(
                    self.params_d, self.pool_d,
                    jnp.asarray(self._tables), zero, zero)
                out, n_acc, self.pool = self._spec_verify_fn(gb)(
                    self.params, self.pool, jnp.asarray(self._tables),
                    zero, zero, drafted, zero,
                    jnp.asarray(self._temps), rng)
                jax.block_until_ready(out)
                beat()
        if self.share_prefix:
            # The COW boundary-copy program: one compiled copy serves
            # every (src, dst) pair, warmed here so the first shared-hit
            # admission with a mid-block boundary doesn't trace on the
            # admit path.  Copy between two blocks allocated for the
            # purpose — a parked warmup prefix may already own low block
            # ids, and copying garbage INTO an owned block would corrupt
            # parked KV.
            blks = self.allocator.alloc(2)
            if blks is not None:
                try:
                    self.pool = self._cow_copy_fn()(
                        self.pool, jnp.asarray(blks[0], jnp.int32),
                        jnp.asarray(blks[1], jnp.int32))
                    jax.block_until_ready(self.pool)
                finally:
                    # A warmup compile failure must not strand the pair
                    # for the engine's whole lifetime.
                    self.allocator.free(blks)
                beat()
                if self.spec:
                    blks = self.allocator.alloc(2)
                    if blks is not None:
                        try:
                            self.pool_d = self._cow_copy_fn_d()(
                                self.pool_d, jnp.asarray(blks[0], jnp.int32),
                                jnp.asarray(blks[1], jnp.int32))
                            jax.block_until_ready(self.pool_d["k"])
                        finally:
                            self.allocator.free(blks)
                        beat()
        if self.prefix_cache is not None and self._buckets:
            row = self._table_row([])
            # Every (reuse suffix bucket, chunk window rung) an admit
            # can hit — the coarse ladders keep this product small enough
            # to warm completely (no mid-chat admit compiles).
            for sb in self._reuse_buckets:
                for window in self._reuse_windows:
                    if window < sb + 1:
                        continue
                    self._rng, rng = jax.random.split(self._rng)
                    first, self.pool = self._chunk_prefill_fn(sb, window)(
                        self.params, self.pool,
                        jnp.full((1, sb), self.tokenizer.pad_id, jnp.int32),
                        jnp.asarray([0], np.int32),
                        jnp.asarray([1], np.int32),
                        jnp.asarray(row), rng, jnp.float32(0.0))
                    jax.block_until_ready(first)
                    beat()
                    if self.spec:
                        # The draft's suffix-seed twin rides the same
                        # (sb, window) ladder — warm it so a prefix-hit
                        # admission never traces the draft mid-chat.
                        self.pool_d = self._draft_chunk_fn(sb, window)(
                            self.params_d, self.pool_d,
                            jnp.full((1, sb), self.tokenizer.pad_id,
                                     jnp.int32),
                            jnp.asarray([0], np.int32),
                            jnp.asarray([1], np.int32), jnp.asarray(row))
                        jax.block_until_ready(self.pool_d["k"])
                        beat()
        if (self.chunk_tokens and self._buckets
                and max(self._buckets) > self.chunk_tokens):
            # The cold-chunk program family: one (chunk_tokens, window)
            # program per rung of the lane's ladder (at an 8192 span 5
            # programs), ALL of them: a replayed or top-bucket prompt
            # reaches every rung, so a long prompt arriving mid-serve
            # never pays an XLA trace on the interleave path it exists
            # to keep smooth.
            c = self.chunk_tokens
            row = self._table_row([])
            for window in self._chunk_windows:
                if window < c:
                    continue
                self._rng, rng = jax.random.split(self._rng)
                first, self.pool = self._chunk_prefill_fn(c, window)(
                    self.params, self.pool,
                    jnp.full((1, c), self.tokenizer.pad_id, jnp.int32),
                    jnp.asarray([0], np.int32),
                    jnp.asarray([1], np.int32),
                    jnp.asarray(row), rng, jnp.float32(0.0))
                jax.block_until_ready(first)
                beat()


class StreamHandle:
    """Iterable of text deltas; ``.request`` exposes the final
    GenerationResult / error once the stream is exhausted."""

    def __init__(self, gen, request: _Request):
        self._gen = gen
        self.request = request

    def __iter__(self):
        return self._gen

    @property
    def result(self) -> Optional[GenerationResult]:
        return self.request.result
