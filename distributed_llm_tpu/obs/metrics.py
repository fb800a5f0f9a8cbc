"""Metrics registry: counters, gauges, log-bucketed histograms, and a
Prometheus text exposition — stdlib only.

The serving stack's internal signals (queue depth, breaker state, wedge
flags, admission rejects — PRs 1 and 2) previously surfaced only as an
untyped ``GET /stats`` dict; this registry gives them a typed, scrapeable
shape, served as Prometheus exposition text at ``GET /metrics``
(serving/app.py) and read programmatically by the benchmark's
per-layer readers (benchmark/layer_metrics/).

Shape notes:

- A metric is a FAMILY (name + help + label names) of children keyed by
  label values: ``reg.counter("x_total", "…", ("tier",)).labels("nano")``.
  A label-less family is its own single child (``.inc()`` directly).
- Histograms use a fixed LOG-SPACED millisecond bucket ladder
  (sub-ms to minutes): latencies span 4+ orders of magnitude between the
  tiny CPU tiers and a wedged chip's timeout, and log buckets hold the
  relative quantile error roughly constant across that range where
  linear buckets would collapse one end or the other.
- ``Histogram.quantile`` interpolates within the winning bucket
  (the same estimate PromQL's histogram_quantile makes) — good to the
  bucket's width, which is the honest precision of any bucketed store.
- Thread-safety: one lock per registry guards family/child creation;
  each child then updates under its own lock.  Hot-path cost is one
  dict lookup + one lock + a float add (see the overhead test in
  tests/test_obs.py).
"""

from __future__ import annotations

import bisect
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# Log-spaced ms ladder: 1-2-5 per decade from 0.5 ms to 120 s.
DEFAULT_BUCKETS_MS: Tuple[float, ...] = (
    0.5, 1, 2, 5, 10, 20, 50, 100, 200, 500,
    1000, 2000, 5000, 10000, 20000, 60000, 120000)


def nearest_rank(values: Iterable[float], q: float,
                 presorted: bool = False) -> Optional[float]:
    """Nearest-rank percentile over raw samples — rank
    ``round(q * (n - 1))`` of the sorted values, None when empty.  The
    ONE rank rule shared by the decode tick ring
    (``ContinuousBatchingEngine.tick_stats``), the per-request TBT
    cadence criterion (``RequestTrace.tbt_p95_ms``), the tick-phase
    profiler (obs/profiler.py) and the open-loop bench leg, so "p95"
    means the same thing in the sampler gauges, the SLO verdicts, and
    the bench artifact.  (Histogram.quantile is the OTHER estimator —
    bucket interpolation over the log ladder — used where raw samples
    are not retained.)

    ``presorted=True`` skips the sort for callers that already hold a
    sorted list and read several quantiles from it (tick_stats runs on
    the 4 Hz sampler path per tier — sorting the 512-entry ring once
    per quantile per collect was the ISSUE 11 small fix).  ``values``
    must then be an indexable sorted sequence."""
    vs = values if presorted else sorted(values)
    if not vs:
        return None
    ix = min(len(vs) - 1, int(q * (len(vs) - 1) + 0.5))
    return vs[ix]


def _fmt(v: float) -> str:
    """Prometheus sample formatting: integers without a trailing .0."""
    if v == int(v):
        return str(int(v))
    return repr(float(v))


def _label_str(names: Sequence[str], values: Sequence[str]) -> str:
    if not names:
        return ""
    inner = ",".join(f'{n}="{_escape(v)}"' for n, v in zip(names, values))
    return "{" + inner + "}"


def _escape(v: Any) -> str:
    return str(v).replace("\\", r"\\").replace('"', r'\"').replace(
        "\n", r"\n")


class Counter:
    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    __slots__ = ("_lock", "_value")

    def __init__(self):
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    __slots__ = ("_lock", "buckets", "counts", "sum", "count")

    def __init__(self, buckets: Sequence[float] = DEFAULT_BUCKETS_MS):
        self._lock = threading.Lock()
        self.buckets: Tuple[float, ...] = tuple(sorted(buckets))
        self.counts: List[int] = [0] * (len(self.buckets) + 1)  # +Inf tail
        self.sum = 0.0
        self.count = 0

    def observe(self, v: float) -> None:
        ix = bisect.bisect_left(self.buckets, v)
        with self._lock:
            self.counts[ix] += 1
            self.sum += v
            self.count += 1

    def merge(self, counts: Sequence[int], total: float) -> None:
        """Add observations that were bucketed elsewhere over the same
        ladder (``counts``: one a bucket and the +Inf tail; ``total``:
        their sum) — for a writer that may not take a lock an
        observation (obs/profiler.py edge lanes)."""
        if len(counts) != len(self.counts):
            raise ValueError("merge: bucket ladders differ")
        with self._lock:
            for ix, c in enumerate(counts):
                self.counts[ix] += c
            self.sum += total
            self.count += sum(counts)

    def quantile(self, q: float) -> Optional[float]:
        """Bucket-interpolated quantile estimate (None when empty).
        Matches PromQL histogram_quantile: linear within the winning
        bucket; the +Inf bucket clamps to the highest finite bound."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
        if total == 0:
            return None
        rank = q * total
        cum = 0.0
        for ix, c in enumerate(counts):
            cum += c
            if cum >= rank and c > 0:
                if ix >= len(self.buckets):          # +Inf bucket
                    return self.buckets[-1]
                lo = self.buckets[ix - 1] if ix > 0 else 0.0
                hi = self.buckets[ix]
                frac = (rank - (cum - c)) / c
                return lo + (hi - lo) * frac
        return self.buckets[-1]


class _Family:
    """One metric family: kind + help + label names + children."""

    def __init__(self, name: str, help_: str, kind: str,
                 label_names: Sequence[str],
                 buckets: Sequence[float] = DEFAULT_BUCKETS_MS):
        self.name = name
        self.help = help_
        self.kind = kind                     # "counter" | "gauge" | "histogram"
        self.label_names = tuple(label_names)
        self._buckets = tuple(buckets)
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], Any] = {}
        if not self.label_names:
            self._default = self._make()
            self._children[()] = self._default

    def _make(self):
        if self.kind == "counter":
            return Counter()
        if self.kind == "gauge":
            return Gauge()
        return Histogram(self._buckets)

    def labels(self, *values: Any):
        """The child for these label values (created on first use)."""
        if len(values) != len(self.label_names):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, "
                f"got {values!r}")
        key = tuple(str(v) for v in values)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.setdefault(key, self._make())
        return child

    # Label-less convenience: the family IS its single child.
    def inc(self, n: float = 1.0) -> None:
        self._children[()].inc(n)

    def set(self, v: float) -> None:
        self._children[()].set(v)

    def observe(self, v: float) -> None:
        self._children[()].observe(v)

    @property
    def value(self) -> float:
        return self._children[()].value

    def children(self) -> Dict[Tuple[str, ...], Any]:
        with self._lock:
            return dict(self._children)


class MetricsRegistry:
    """Named families; renders the whole set as Prometheus text."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, _Family] = {}

    def _family(self, name: str, help_: str, kind: str,
                labels: Sequence[str],
                buckets: Sequence[float] = DEFAULT_BUCKETS_MS) -> _Family:
        fam = self._families.get(name)
        if fam is not None:
            if fam.kind != kind or fam.label_names != tuple(labels):
                raise ValueError(
                    f"metric {name!r} re-registered as {kind}{tuple(labels)} "
                    f"(was {fam.kind}{fam.label_names})")
            return fam
        with self._lock:
            return self._families.setdefault(
                name, _Family(name, help_, kind, labels, buckets))

    def counter(self, name: str, help_: str = "",
                labels: Sequence[str] = ()) -> _Family:
        return self._family(name, help_, "counter", labels)

    def gauge(self, name: str, help_: str = "",
              labels: Sequence[str] = ()) -> _Family:
        return self._family(name, help_, "gauge", labels)

    def histogram(self, name: str, help_: str = "",
                  labels: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS_MS) -> _Family:
        return self._family(name, help_, "histogram", labels, buckets)

    def get(self, name: str) -> Optional[_Family]:
        return self._families.get(name)

    # -- exposition --------------------------------------------------------

    def render(self) -> str:
        """Prometheus text exposition (text/plain; version=0.0.4)."""
        lines: List[str] = []
        with self._lock:
            families = sorted(self._families.values(), key=lambda f: f.name)
        for fam in families:
            lines.append(f"# HELP {fam.name} {_escape(fam.help)}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            for key, child in sorted(fam.children().items()):
                if fam.kind == "histogram":
                    cum = 0
                    for ix, bound in enumerate(child.buckets):
                        cum += child.counts[ix]
                        labels = _label_str(
                            fam.label_names + ("le",),
                            key + (_fmt(bound),))
                        lines.append(f"{fam.name}_bucket{labels} {cum}")
                    labels = _label_str(fam.label_names + ("le",),
                                        key + ("+Inf",))
                    lines.append(f"{fam.name}_bucket{labels} {child.count}")
                    base = _label_str(fam.label_names, key)
                    lines.append(f"{fam.name}_sum{base} {_fmt(child.sum)}")
                    lines.append(f"{fam.name}_count{base} {child.count}")
                else:
                    labels = _label_str(fam.label_names, key)
                    lines.append(f"{fam.name}{labels} {_fmt(child.value)}")
        return "\n".join(lines) + "\n"


# -- the metric registry ------------------------------------------------------
#
# Every dllm_* family the serving stack emits, declared ONCE as data:
# (attribute, kind, name, label names, help).  ServingMetrics
# materializes the rows; METRICS.md is generated from them
# (``python -m distributed_llm_tpu.obs.metrics > METRICS.md``); the
# ``metrics_discipline`` lint checker fails tier-1 when an emission
# site and this table disagree in either direction, and every label
# name must carry a cardinality bound in BOUNDED_LABELS below.  Rows
# are PURE LITERALS (the checker reads them from the AST).

METRIC_REGISTRY: Tuple[Tuple[str, str, str, Tuple[str, ...], str], ...] = (
    ("requests", "counter", "dllm_requests_total",
     ("strategy", "tier", "outcome"),
     "Requests completed, by strategy/tier/outcome (outcome: "
     "ok|error|degraded)"),
    ("ttft_ms", "histogram", "dllm_ttft_ms", ("strategy",),
     "Time to first token per request (engine-true when reported, else "
     "first observed token)"),
    ("tbt_ms", "histogram", "dllm_tbt_ms", ("strategy",),
     "Mean time between tokens per request"),
    ("queue_wait_ms", "histogram", "dllm_queue_wait_ms", ("tier",),
     "Submit-to-batch-slot-admission wait in the tier's engine"),
    ("prefill_wait_ms", "histogram", "dllm_prefill_wait_ms", ("tier",),
     "Prefill start to first token in the tier's engine (the "
     "request's prefill_wait_ms annotation): one compiled call for a "
     "monolithic prefill, every chunk and the decode ticks between "
     "them for a chunked one — the other half of TTFT beside "
     "dllm_queue_wait_ms"),
    ("prefill_lane_wait_ms", "histogram", "dllm_prefill_lane_wait_ms",
     ("tier",),
     "Time a request that needs chunked prefill sat at the "
     "scheduler head because the single prefill lane was busy "
     "(lane_wait_ms; 0 for a request the lane never held up; "
     "part of dllm_queue_wait_ms)"),
    ("first_delta_hold_ms", "histogram", "dllm_first_delta_hold_ms",
     ("strategy",),
     "First generated token (the trace's token timeline) to the "
     "first delta the streaming edge yields: what the turn clipper's "
     "held-back characters add to a streamed request's visible TTFT; "
     "one observation per streamed request that yielded a delta"),
    ("request_ms", "histogram", "dllm_request_ms", ("strategy",),
     "End-to-end routed request wall time"),
    ("admission_rejected", "counter", "dllm_admission_rejected_total",
     ("tier",),
     "Requests shed by tier admission control"),
    ("retries", "counter", "dllm_retries_total", ("tier",),
     "Same-tier transient-error retries"),
    ("failovers", "counter", "dllm_failovers_total", ("tier", "kind"),
     "Tier failovers, by failed tier and kind (sync|stream_setup|"
     "mid_stream)"),
    ("breaker_transitions", "counter", "dllm_breaker_transitions_total",
     ("tier", "to"),
     "Circuit-breaker state transitions, by tier and target state"),
    ("breaker_state", "gauge", "dllm_breaker_state", ("tier",),
     "Circuit state per tier (0=closed, 1=half_open, 2=open)"),
    ("watchdog_wedged", "counter", "dllm_watchdog_wedged_total", ("tier",),
     "Decode-watchdog wedge declarations (health flips ok=False)"),
    ("cache_hits", "counter", "dllm_cache_hits_total", ("cache",),
     "Cache hits by tier of cache (response|response_degraded|"
     "routing|prefix_affinity)"),
    ("degraded", "counter", "dllm_degraded_total", (),
     "Requests served by the degraded path (all circuits open)"),
    ("flight_records", "counter", "dllm_flight_records_total", ("reason",),
     "Flight-recorder captures by reason (error|degraded|slow)"),
    # Resource-pressure family (PR 5): KV-aware admission, mid-decode
    # preemption with replay, context-overflow policy, graceful drain.
    ("preemptions", "counter", "dllm_preemptions_total", ("tier",),
     "Mid-decode slot preemptions under KV block starvation "
     "(victim replays byte-identically on re-admission)"),
    ("kv_admission_rejected", "counter", "dllm_kv_admission_rejected_total",
     ("tier",),
     "Requests shed because projected KV block demand exceeded "
     "free + reclaimable pool blocks"),
    ("overflow", "counter", "dllm_overflow_total", ("tier", "action"),
     "Context-overflow policy applications at the router, by tier "
     "and action (rejected|truncated)"),
    ("drained_requests", "counter", "dllm_drained_requests_total", ("tier",),
     "In-flight requests completed during a graceful drain"),
    # Ragged-decode family (PR 6): the serving path must SHOW which
    # attention kernel is actually running a tier's decode ticks and
    # what each tick costs — cross-round perf deltas get attributed
    # to a kernel, not guessed.
    ("decode_tick_ms", "histogram", "dllm_decode_tick_ms", ("tier",),
     "Batched decode tick as the host waits for it: its launch plus "
     "the fetch of its tokens (decode_steps_per_tick fused steps per "
     "observation).  The host section of a prefill chunk that rides "
     "between the two is not in it; the fetch then starts that much "
     "into the tick, and a tick queued behind a chunk nobody has "
     "waited for yet carries that chunk's tail"),
    ("decode_ticks", "counter", "dllm_decode_ticks_total",
     ("tier", "kind", "impl"),
     "Batched decode ticks, by the tick's shape (ragged_decode the "
     "fused one, paged_decode the windowed one, ragged_verify a "
     "speculative round; +_q8 over an int8 pool) and what serves its "
     "attention (pallas: the streamed rows kernel; else xla)"),
    ("decode_ticks_ahead", "counter", "dllm_decode_ticks_ahead_total",
     ("tier",),
     "Plain decode ticks dispatched AHEAD of the fetch of the tick "
     "before them (a full batch, no end known within that tick, no "
     "prefill in flight, the carry whole and the blocks there), so the "
     "host's fetch, account, emit and prepare ran under a tick and not "
     "beside an idle chip: GET /stats tiers.<tier>.tick.ahead_share is "
     "that share of the ticks launched"),
    ("tick_prepare_uploads", "counter", "dllm_tick_prepare_uploads_total",
     ("tier", "what"),
     "Uploads a decode tick's prepare phase made with the device idle, "
     "by what (pos|cur|temps: a writer other than the plain emit "
     "touched the host mirror since the last tick — a slot went live "
     "or ended, a speculative round, a tick that raised; tables: a row "
     "changed and the rung was not uploaded in the last tick's shadow; "
     "owner: the hybrid families' row owners changed).  A tick with "
     "none of them took everything from the tick before it: GET /stats "
     "tiers.<tier>.tick.resident_share is that share of "
     "dllm_decode_ticks_total"),
    ("compiled_programs", "gauge", "dllm_compiled_programs",
     ("tier", "stage"),
     "Distinct compiled XLA programs the batched engine has "
     "minted, by stage (prefill|chunk_prefill|writer|decode) — "
     "decode pins at 1 under ragged attention; growth is logged"),
    # Chunked-prefill family (PR 9): long prompts are absorbed one
    # chunk per tick between decode ticks — the chunk histogram IS
    # the TBT bound the design promises (an active stream stalls at
    # most one chunk grant), and the backlog gauge shows a long
    # prompt mid-absorption behind a TTFT spike.
    ("prefill_chunk_ms", "histogram", "dllm_prefill_chunk_ms", ("tier",),
     "One interleaved prefill chunk, taken where the host waits for "
     "it (a chunk is settled one chunk late, a prompt's last in its "
     "own pass): from when the host last saw the device reach it (the "
     "fetch of the tick it was queued behind, or its own dispatch if "
     "later) to its outputs being ready — its device time plus "
     "however late the host came to look; the upper bound a chunked "
     "admission adds to active streams' time-between-tokens per tick"),
    ("prefill_chunks", "counter", "dllm_prefill_chunks_total",
     ("tier", "kind"),
     "Prefill chunks dispatched, by whether the chunk was enqueued "
     "behind a decode tick whose tokens the host had not fetched yet "
     "(kind=behind_tick: the device runs tick then chunk back to back "
     "under the host's fetch and emit) or not (kind=alone: a solo "
     "prefill's chunks, the rest of a budget above one chunk, a chunk "
     "that stalled on a dry pool until the emit)"),
    ("prefill_self_only_chunks", "counter",
     "dllm_prefill_self_only_chunks_total", ("tier",),
     "Of dllm_prefill_chunks_total, the shared-K/V family's chunks that "
     "did not hold their prompt's last token and so ran to the one "
     "cached layer's K/V write and no deeper "
     "(models/shared_kv_hybrid.py): the layers after it feed a prompt "
     "position's own logits only"),
    ("prefill_window_positions", "counter",
     "dllm_prefill_window_positions_total", ("tier",),
     "Positions the chunked-prefill lane's chunks attended: each "
     "dispatched chunk's window rung (the smallest of 256, 1024, 2048, "
     "4096, ... and the slot's span that holds the chunk's end)"),
    ("prefill_chunks_by_window", "counter",
     "dllm_prefill_chunks_by_window_total", ("tier", "window"),
     "Of dllm_prefill_chunks_total, the chunks by the window rung each "
     "ran at (window: positions, one compiled chunk program a rung): a "
     "16 k-token prompt's 64 chunks laid against the ladder, 1 at 256, "
     "3 at 1024, 4, 8, 16 and 32 at the doublings (/stats "
     "prefill.chunks_by_window)"),
    ("prefill_chunks_by_form", "counter",
     "dllm_prefill_chunks_by_attention_form_total", ("tier", "form"),
     "Of a latent-row tier's dllm_prefill_chunks_total, the chunks by "
     "what their compiled program attends the latent rows with (form: "
     "blocks, the kernel of ops/latent_chunk_attention.py; plain, einsum "
     "+ softmax over the whole up-projected window; a static test on "
     "the program's shapes: /stats prefill.attention_form names it a "
     "program)"),
    ("prefill_written_positions", "counter",
     "dllm_prefill_written_positions_total", ("tier",),
     "Positions written when each of those chunks ran (its end, capped "
     "at the prompt's length): dllm_prefill_window_positions_total over "
     "this is /stats prefill.window_over_written, under 2 past position "
     "1024 and 1 for a ladder that followed the prompt exactly"),
    # Routed-expert family (models/latent_moe.py): what the tick and
    # the chunk program count beside their tokens — how many expert
    # assignments a stage computed, and how many experts those touched
    # (an expert touched in a step is an expert's weights read).
    ("moe_assignments", "counter", "dllm_moe_assignments_total",
     ("tier", "stage"),
     "Token-to-expert assignments computed, by stage (decode|prefill): "
     "rows x experts_per_token x expert layers, idle slots and chunk "
     "padding included; of a program that holds a share of a layer's "
     "experts (models/hybrid_ssm.py), those to the experts it holds"),
    ("moe_experts_touched", "counter", "dllm_moe_experts_touched_total",
     ("tier", "stage"),
     "Routed experts with at least one token, summed over expert layers "
     "and over steps (decode) or chunks (prefill): the experts whose "
     "weights a step had to read (under a top-1 router, "
     "ModelConfig.router_hidden, at most one a token a layer: the "
     "benchmark's moe.top1_experts_touched_per_step, and through it the "
     "experts' bytes of step.decode_hbm_share_cca_moe and "
     "attn.cca_kv_share_of_step_bytes; the experts' device time is "
     "moe.grouped_product_share_of_step_ms, from the trace: the kernels "
     "whose names begin with grouped_product, the one fused call a layer "
     "grouped_product_ffn among them)"),
    ("moe_absent_assignments", "counter",
     "dllm_moe_absent_assignments_total", ("tier", "stage"),
     "Token-to-expert assignments the router made to experts this "
     "program does not hold (ModelConfig.experts_first/experts_count: "
     "the other ranks of an expert-parallel group compute them: one "
     "other where half are held, three where 64 of 256 are), by stage; "
     "held + absent = every assignment (the benchmark's "
     "moe.held_assignment_share)"),
    # The row families (models/hybrid_ssm.py, shared_kv_hybrid.py): a
    # sequence's recurrent row is zeroed when its prompt's first chunk
    # starts.
    ("state_resets", "counter", "dllm_state_resets_total", ("tier",),
     "Recurrent rows started from zero: prompts (and preemption "
     "replays) whose first chunk was dispatched; a row is a state-space "
     "layer's state and conv tail, a linear-attention layer's float32 "
     "matrix a head and its three conv tails (GET /stats state.mixer "
     "kda; the benchmark's kda.state_share_of_step_bytes counts its "
     "bytes), or the tail a compressed convolutional attention layer "
     "keeps beside its paged K/V"),
    # Batched-speculation family (ISSUE 15): drafted vs accepted
    # draft tokens per tier (the counter pair whose ratio IS the
    # realized acceptance rate) and the engine's running acceptance
    # ratio mirrored by the system-state sampler — an operator reads
    # whether speculation is paying for its draft FLOPs without
    # diffing counters.
    ("spec_drafted", "counter", "dllm_spec_drafted_total", ("tier",),
     "Draft tokens proposed by batched speculative decoding "
     "(per-slot γ summed over rounds)"),
    ("spec_accepted", "counter", "dllm_spec_accepted_total", ("tier",),
     "Draft tokens accepted by the fused verify's greedy "
     "acceptance rule"),
    ("spec_accept_ratio_g", "gauge", "dllm_spec_accept_ratio", ("tier",),
     "Engine-lifetime accepted/drafted ratio for batched "
     "speculation (sampled; absent until the first draft)"),
    ("prefill_backlog_g", "gauge", "dllm_prefill_backlog", ("tier",),
     "Prompt tokens of the in-flight chunked prefill not yet "
     "absorbed (sampled by the system-state sampler; 0 = no "
     "prefill in flight)"),
    # System-state timeline family (PR 7, obs/sampler.py): the
    # background sampler mirrors its latest per-tier sample to these
    # gauges so dashboards plot the same series the timeline ring
    # stores.  The *_g attribute suffix keeps them apart from the
    # identically-themed request-path counters above.
    ("queue_depth_g", "gauge", "dllm_queue_depth", ("tier",),
     "Requests waiting beyond the tier's batch slots (sampled)"),
    ("active_slots_g", "gauge", "dllm_active_slots", ("tier",),
     "Busy batch slots per tier (sampled)"),
    ("max_slots_g", "gauge", "dllm_max_slots", ("tier",),
     "Configured batch slots per tier (sampled)"),
    ("kv_free_blocks_g", "gauge", "dllm_kv_free_blocks", ("tier",),
     "Free paged-KV pool blocks per tier (sampled)"),
    ("kv_reclaimable_blocks_g", "gauge", "dllm_kv_reclaimable_blocks",
     ("tier",),
     "Pool blocks reclaimable by evicting parked prefixes "
     "(sampled; under shared-prefix KV only refcount-1 blocks of "
     "unpinned entries count — what an eviction sweep could "
     "actually free)"),
    # Shared-prefix KV family (ISSUE 10): how much physical pool the
    # refcounted copy-on-write sharing is saving, and what kind of
    # prefix-cache hits admissions are taking.
    ("kv_shared_blocks_g", "gauge", "dllm_kv_shared_blocks", ("tier",),
     "Physical pool blocks with >= 2 holders (live slots mapping "
     "a shared prefix read-only and/or parked entries; sampled)"),
    ("kv_dedup_ratio_g", "gauge", "dllm_kv_dedup_ratio", ("tier",),
     "Logical block references / physical allocated blocks — the "
     "factor shared-prefix KV multiplies the effective pool by "
     "(1.0 = nothing shared; sampled)"),
    ("prefix_hits", "counter", "dllm_prefix_hits_total", ("tier", "kind"),
     "Prefix-cache lookup outcomes on the batched admit path, "
     "per admission attempt (shared = pinned read-only mapping, "
     "exclusive = take-ownership reuse, host = spill-tier "
     "promotion claim, miss = cold prefill)"),
    # Hierarchical-KV spill family (ISSUE 14, engine/kv_spill.py):
    # the host tier's occupancy and the demote/promote lifecycle —
    # warm TTFT as a function of host-RAM size must be observable,
    # and a promotion losing its race must be countable.
    ("kv_host_blocks_g", "gauge", "dllm_kv_host_blocks", ("tier",),
     "Pool-block equivalents of demoted prefix KV resident in "
     "the host spill tier (sampled)"),
    ("kv_host_bytes_g", "gauge", "dllm_kv_host_bytes", ("tier",),
     "Host bytes held by the KV spill tier against "
     "TierConfig.host_kv_bytes (sampled)"),
    ("kv_promote_backlog_g", "gauge", "dllm_kv_promote_backlog", ("tier",),
     "Blocks the in-flight promotion still has to land "
     "host→device (sampled; 0 = no promotion in flight)"),
    ("kv_demotions", "counter", "dllm_kv_demotions_total", ("tier",),
     "Prefix-cache evictions demoted to the host spill tier "
     "(copy landed; the async device→host copy drains on the "
     "spill copier, never the tick)"),
    ("kv_promotions", "counter", "dllm_kv_promotions_total", ("tier",),
     "Demoted prefixes promoted back to the device pool "
     "(budgeted host→device grants riding the chunked-prefill "
     "lane)"),
    ("kv_promotion_races", "counter", "dllm_kv_promotion_races_total",
     ("tier",),
     "Promotions that lost the race (entry invalidated / copier "
     "stalled) and fell back to a byte-identical cold prefill"),
    ("tier_draining_g", "gauge", "dllm_tier_draining", ("tier",),
     "1 while the tier is gracefully draining, else 0 (sampled)"),
    ("decode_tick_p50_g", "gauge", "dllm_decode_tick_p50_ms", ("tier",),
     "p50 decode-tick device time over the engine's recent-tick "
     "ring (sampled)"),
    # SLO / goodput family (PR 7, obs/slo.py): fed from the router's
    # exactly-once _finish_request exit (obs_discipline lint pins the
    # single feed site).
    ("slo_goodput", "gauge", "dllm_slo_goodput", ("strategy", "tier"),
     "Sliding-window fraction of requests meeting the tier's SLO "
     "(TTFT and p95 TBT targets)"),
    ("slo_violations", "counter", "dllm_slo_violations_total", ("kind",),
     "Requests missing their SLO, by kind (error|ttft|tbt)"),
    ("overload_incidents", "counter", "dllm_overload_incidents_total",
     ("tier",),
     "Rising-edge overload incidents (tier goodput under the "
     "floor); each lands in the flight recorder with a timeline "
     "slice"),
    # Tick-forensics family (ISSUE 11, obs/profiler.py): per-request
    # device-time / KV-residency attribution aggregated at the
    # router's exactly-once completion exit, plus sampled per-phase
    # tick breakdown gauges — the accounting substrate per-tenant
    # quotas and goodput-per-replica-second economics bill against.
    ("device_time", "counter", "dllm_device_time_ms_total",
     ("tier", "strategy", "session"),
     "Attributed decode device time (each tick's device ms "
     "divided across the slots it served), per serving tier, "
     "strategy and session ('-' = sessionless)"),
    ("kv_block_ticks", "counter", "dllm_kv_block_ticks_total",
     ("tier", "strategy", "session"),
     "Attributed KV residency: pool blocks held x decode ticks, "
     "shared prefix blocks charged 1/refcount to each holder"),
    ("tick_phase_p50_g", "gauge", "dllm_tick_phase_p50_ms",
     ("tier", "phase"),
     "p50 per-tick SELF time of one scheduler phase (admit|"
     "prefill|cow_copy|table_upload|decode|draft|verify|emit|"
     "chunk_prefill|demote|promote; decode at its full duration, "
     "its time being all in its dispatch and fetch children) over "
     "the profiler ring's recent tail (sampled)"),
    ("tick_phase_ms", "counter", "dllm_tick_phase_ms_total",
     ("tier", "phase"),
     "Lifetime SELF time of one scheduler phase in ms (the tick "
     "profiler's totals, exported when sampled and when scraped; "
     "self-times partition the scheduler's stamped time, so phases "
     "add).  Per decode tick: divide a delta by the delta of "
     "dllm_decode_ticks_total summed over kind and impl"),
    ("tick_phase_cpu_ms", "counter", "dllm_tick_phase_cpu_ms_total",
     ("tier", "phase"),
     "Lifetime SELF CPU time of one scheduler phase in ms: the "
     "scheduler thread's own CPU clock (time.thread_time) read "
     "beside every stamp of dllm_tick_phase_ms_total and exported "
     "with it.  A phase's ms_total minus this is the time the "
     "thread stood in the phase without running: blocked in a "
     "call (fetch, idle_wait), or waiting for the interpreter's "
     "lock or for a core.  Where a reading of that clock costs "
     "over 2 us (a sandboxed kernel) one scheduler pass in five "
     "reads it and counts fivefold: an estimate there"),
    ("sched_runqueue_wait_ms", "counter",
     "dllm_sched_runqueue_wait_ms_total", ("tier",),
     "Time the tier's scheduler thread stood runnable with no "
     "core (second field of /proc/thread-self/schedstat, read "
     "once a scheduler pass): the part of the scheduler's off-CPU "
     "time that is the machine's, not the interpreter's.  Absent "
     "where that file cannot be read"),
    ("edge_awake_ms", "counter", "dllm_edge_awake_ms_total",
     ("tier", "clock"),
     "Time the tier's stream consumer threads spent awake, from a "
     "token_queue.get that had to wait returning to the thread's "
     "next wait (decoder, turn clipper, SSE framing and whatever "
     "the consumer does per delta): clock=wall by perf_counter; "
     "clock=cpu the growth of each thread's own CPU clock, read "
     "once a second a stream and at its end (a thread that waits "
     "uses none, so it is the slices' CPU and the wake-ups' own) "
     "— interpreter time the scheduler thread could not have"),
    ("edge_wakeups", "counter", "dllm_edge_wakeups_total", ("tier",),
     "Awake slices of the tier's stream consumer threads: one per "
     "get that had to wait"),
    ("edge_tokens", "counter", "dllm_edge_tokens_total", ("tier",),
     "Tokens the tier's stream consumers took off their queues; "
     "over dllm_edge_wakeups_total it is the tokens a wake-up "
     "(decode_steps_per_tick = one wake a slot a tick, near 1 = a "
     "wake a put)"),
    ("edge_wake_lag_ms", "histogram", "dllm_edge_wake_lag_ms", ("tier",),
     "From the engine's stamp of an awake slice's first token "
     "(the trace's token timeline) to the slice's start: how long "
     "a token that exists waits for its stream's thread to run; "
     "one observation per awake slice that took a token"),
    ("profile_coverage_g", "gauge", "dllm_profile_coverage", ("tier",),
     "Fraction of tick wall time covered by stamped phase self-"
     "times (sampled; the bench profile leg pins >= 0.95)"),
    # Replicated-tier family (ISSUE 12, serving/replicas.py): how
    # dispatch chose among a tier's engine replicas, and how much of
    # the tier's replica capacity is currently healthy.
    ("replica_routed", "counter", "dllm_replica_routed_total",
     ("tier", "policy"),
     "Requests dispatched to a tier replica, by how the replica "
     "was chosen (affinity|affinity_overridden|least_loaded|"
     "random|single|breaker_fallback)"),
    ("replica_healthy_g", "gauge", "dllm_replica_healthy", ("tier",),
     "Replicas of the tier currently serving (running, not "
     "wedged, breaker not open) out of TierConfig.replicas "
     "(sampled)"),
    # Crash-rescue family (ISSUE 20, serving/replicas.py
    # restart_replica): what happened to a restarted replica's
    # in-flight work and its host spill store.
    ("replica_rescues", "counter", "dllm_replica_rescues_total",
     ("tier", "outcome"),
     "Requests captured off a crashed/wedged replica at restart, "
     "by where they resumed (sibling = adopted by a live sibling "
     "replica, requeue = re-queued on the restarted engine, "
     "failed = no home — failed with the engine-stopped shape)"),
    ("spill_reattach", "counter", "dllm_spill_reattach_total",
     ("tier",),
     "Host KV spill stores that survived an engine restart and "
     "re-attached to the rebuilt engine (spill-state survival — "
     "restart cost is warm-TTFT promotion, not cold prefill)"),
    # Elastic-capacity family (ISSUE 18, serving/autoscaler.py):
    # live membership and the autoscaler's actuation decisions.
    ("replica_count_g", "gauge", "dllm_replica_count", ("tier",),
     "Live replica membership of the tier — static it equals "
     "TierConfig.replicas; under the autoscaler it moves between "
     "autoscale_min_replicas and autoscale_max_replicas "
     "(sampled)"),
    ("autoscale_events", "counter", "dllm_autoscale_events_total",
     ("tier", "direction", "reason"),
     "Autoscaler membership transitions, by direction (up|down) "
     "and the signal that fired them (goodput_floor|queue_growth"
     "|shed|idle|manual)"),
    # Per-tenant isolation family (ISSUE 17, serving/tenants.py):
    # the measured bill and enforcement decisions per tenant.  Every
    # ``tenant`` label value MUST pass through a BoundedLabels set
    # (64-char truncation, 256 distinct then '~overflow') — metric
    # children are permanent, so an unbounded tenant flood would
    # otherwise grow /metrics without bound.
    ("tenant_device_time", "counter", "dllm_tenant_device_time_ms_total",
     ("tier", "tenant"),
     "Attributed decode device time billed to the tenant "
     "(PR 11 per-request attribution, '-' = tenantless direct "
     "engine use)"),
    ("tenant_kv_block_ticks", "counter",
     "dllm_tenant_kv_block_ticks_total", ("tier", "tenant"),
     "Attributed KV residency billed to the tenant (blocks held "
     "x decode ticks at 1/refcount)"),
    ("tenant_rejected", "counter", "dllm_tenant_rejected_total",
     ("tier", "tenant"),
     "Requests shed by per-tenant quota enforcement (in-flight/"
     "queue caps, device-time token bucket, or KV budget)"),
    ("tenant_inflight_g", "gauge", "dllm_tenant_inflight",
     ("tier", "tenant"),
     "Requests a tenant currently has admitted against its "
     "quota (in flight or waiting)"),
    ("tenant_goodput_g", "gauge", "dllm_tenant_goodput", ("tenant",),
     "Sliding-window fraction of the tenant's requests meeting "
     "their SLO (obs/slo.py per-tenant windows)"),
)

# Every label name in METRIC_REGISTRY carries its cardinality bound
# here — metric children are permanent, so a label without a bound is
# a /metrics memory leak waiting for a hostile client.  The
# ``metrics_discipline`` checker fails tier-1 on a registry label
# missing from this table.  Closed sets are enforced by the emitting
# call sites; open (caller-supplied) sets MUST ride a BoundedLabels.

BOUNDED_LABELS: Dict[str, str] = {
    "strategy": "closed set: the router's routing strategies "
                "(serving/router.py STRATEGIES)",
    "tier": "closed set: config-enumerated tier names (TierConfig)",
    "outcome": "closed per-family enums (request outcomes ok|error|"
               "degraded; rescue outcomes sibling|requeue|failed)",
    "kind": "closed per-family enums (failover / dispatch / SLO-violation"
            " / prefix-hit kinds; see each family's help)",
    "to": "closed set: breaker states closed|half_open|open",
    "cache": "closed set: response|response_degraded|routing|"
             "prefix_affinity",
    "reason": "closed per-family enums (flight-record triggers, "
              "autoscale signals)",
    "action": "closed set: rejected|truncated",
    "impl": "closed set: xla|pallas",
    "stage": "closed set: prefill|chunk_prefill|writer|decode",
    "phase": "closed set: obs/profiler.py PHASES (admit|prefill|cow_copy|"
             "prepare|table_upload|decode|dispatch|fetch|draft|verify|"
             "account|emit|chunk_prefill|first_token|demote|promote|"
             "idle_wait)",
    "session": "open set: BoundedLabels(cap=256) — 64-char truncation, "
               "257th distinct value collapses to '~overflow'",
    "tenant": "open set: BoundedLabels(cap=256) — 64-char truncation, "
              "257th distinct value collapses to '~overflow'",
    "policy": "closed set: affinity|affinity_overridden|least_loaded|"
              "random|single|breaker_fallback",
    "direction": "closed set: up|down",
    "what": "closed set: pos|cur|temps|tables|owner (the decode tick's "
            "small inputs, engine/batching.py _count_prepare_upload)",
    "clock": "closed set: wall|cpu",
    "window": "closed set: the rungs of the chunked-prefill lane's window "
              "ladder (engine/batching.py _window_ladder: 256, 1024 and "
              "its doublings below the slot's span, the span; 7 at a "
              "span of 32768)",
    "form": "closed set: blocks|plain (what a chunk program attends its "
            "latent rows with, engine/batching.py chunk_attention_form)",
}


class ServingMetrics:
    """The serving stack's standard metric set, materialized from
    METRIC_REGISTRY so the router, breaker hooks, engine managers,
    /metrics, and the benchmark all read/write the same families (one
    assembler, no name drift — the table above is the only place a
    family is declared)."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        for attr, kind, name, labels, help_ in METRIC_REGISTRY:
            setattr(self, attr, getattr(registry, kind)(
                name, help_, labels))


# -- METRICS.md generation ----------------------------------------------------

def render_markdown() -> str:
    """The METRICS.md body (pinned in sync by tests/test_lint.py)."""
    lines = [
        "# Metrics registry",
        "",
        "Generated from `distributed_llm_tpu/obs/metrics.py` "
        "(`python -m distributed_llm_tpu.obs.metrics > METRICS.md`).",
        "The `metrics_discipline` lint checker fails tier-1 when an "
        "emission site and this registry disagree in either direction.",
        "",
        "## Metric families (`dllm_*`)",
        "",
        "| Name | Kind | Labels | Semantics |",
        "|---|---|---|---|",
    ]

    def cell(text: str) -> str:
        return text.replace("|", "\\|")     # keep table cells intact

    for _attr, kind, name, labels, help_ in sorted(
            METRIC_REGISTRY, key=lambda r: r[2]):
        lab = ", ".join(f"`{x}`" for x in labels) if labels else "(none)"
        lines.append(f"| `{name}` | {kind} | {lab} | {cell(help_)} |")
    lines += [
        "",
        "## Label cardinality bounds",
        "",
        "Metric children are permanent; every label name above rides "
        "one of these bounds.",
        "",
        "| Label | Bound |",
        "|---|---|",
    ]
    for label in sorted(BOUNDED_LABELS):
        lines.append(f"| `{label}` | {cell(BOUNDED_LABELS[label])} |")
    return "\n".join(lines) + "\n"


class BoundedLabels:
    """Cardinality bound for caller-supplied metric label values — the
    PR 11 session-label policy, reusable: '-' when absent, values
    truncated to 64 chars, and past ``cap`` DISTINCT values every new
    one collapses to '~overflow'.  Metric children are permanent, so
    without this a client minting fresh tenant/session ids would grow
    /metrics (and every labeled family) without bound."""

    def __init__(self, cap: int = 256):
        self._cap = cap
        self._seen: set = set()
        self._lock = threading.Lock()

    def label(self, raw: Any) -> str:
        if not raw:
            return "-"
        s = str(raw)[:64]
        with self._lock:
            if s in self._seen:
                return s
            if len(self._seen) < self._cap:
                self._seen.add(s)
                return s
        return "~overflow"


_BREAKER_STATE_VALUE = {"closed": 0, "half_open": 1, "open": 2}


def breaker_state_value(state: str) -> int:
    return _BREAKER_STATE_VALUE.get(state, 0)


if __name__ == "__main__":
    import sys
    sys.stdout.write(render_markdown())
