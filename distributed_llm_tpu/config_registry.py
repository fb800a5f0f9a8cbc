"""Central registry of every configuration surface the repo exposes.

Two kinds of drift kept hitting review: a ``DLLM_*`` env var would grow a
new reader with its own inline default (one knob at one point had
three different fallbacks), and ``TierConfig`` /
``ClusterConfig`` fields would gain semantics documented only in a commit
message.  This module is the single source of truth for both:

- ``ENV_VARS``: every ``DLLM_*`` environment variable — default, the
  module that consumes it, and one-line semantics.  The typed accessors
  (``env_str`` / ``env_int`` / ``env_float`` / ``env_flag``) raise
  ``UnknownConfigError`` on any name not registered here, so a typo'd
  var name fails loudly at the read site instead of silently serving the
  default forever.
- ``CONFIG_FIELDS``: every ``TierConfig`` / ``ClusterConfig`` dataclass
  field with a one-line summary (the full rationale lives at the field's
  declaration in config.py).

``distributed_llm_tpu/lint`` checker ``config-drift`` enforces both
directions statically: an env read or dataclass field missing here — or
a registry entry whose variable/field no longer exists in code — fails
tier-1.  ``CONFIG.md`` is generated from this module
(``python -m distributed_llm_tpu.config_registry``) and pinned in sync
by tests/test_lint.py.

Deliberately stdlib-only (no jax, no package imports): tests/conftest.py
reads it before jax may be imported, and the lint CLI runs on CPU-only
boxes without the accelerator stack.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional


class UnknownConfigError(KeyError):
    """An env accessor was asked for a name not in ENV_VARS (typo guard)."""


@dataclasses.dataclass(frozen=True)
class EnvVar:
    name: str
    # The DOCUMENTED default — what the consumer does when the var is
    # unset, rendered into CONFIG.md.  Always a literal value string or
    # None (never prose): the typed accessors take their authoritative
    # fallback at the call site, and ``env_str`` falls back to this
    # value, so a non-literal here would leak into behavior.
    default: Optional[str]
    consumer: str                   # module that reads it
    doc: str                        # one-line semantics


def _e(name: str, default: Optional[str], consumer: str, doc: str) -> EnvVar:
    return EnvVar(name=name, default=default, consumer=consumer, doc=doc)


ENV_VARS: Dict[str, EnvVar] = {v.name: v for v in (
    _e("DLLM_ATTENTION", None, "ops/attention.py",
       "Explicit attention override: 'pallas' = the flash prefill and "
       "the streamed rows decode kernel wherever they serve, 'xla' = "
       "neither; unset = each engine's own attention_impl (kernels on "
       "an unsharded TPU tier)."),
    _e("DLLM_RAGGED", None, "engine/batching.py",
       "'1' forces the batched engine's fused decode TICK (full table "
       "rows, one program), '0' the windowed one (a program a rung); "
       "unset = TierConfig.attention_ragged asks for the fused tick and "
       "gets it off the TPU only.  On a qualifying TP mesh the fused "
       "tick runs under shard_map over the kv-head axis "
       "(parallel/tp_attention._tp_ragged_ok); non-qualifying meshes "
       "and the latent and hybrid families keep the windowed tick "
       "regardless of this flag."),
    _e("DLLM_NATIVE", None, "native/__init__.py",
       "'0' disables the g++-built native tokenizer/counter helpers; "
       "behavior is bit-identical to the pure-Python fallback."),
    _e("DLLM_LINT_CHANGED", "HEAD", "lint/__main__.py",
       "Base git ref for `scripts/lint.sh --changed` (dllm-lint's "
       "diff-scoped mode): per-file checkers report only findings in "
       "files changed vs this ref; whole-project checkers (locks, "
       "retrace, transfer, thread_lifecycle, config_drift) auto-widen "
       "to full reporting because their verdicts cross files."),
    _e("DLLM_PROFILE", "1", "obs/profiler.py",
       "'0' disables the batched engines' tick-phase profiler AND the "
       "per-request device-time/KV-residency attribution (zero-cost "
       "null object); default on (measured <= 1% of tick p50)."),
    _e("DLLM_PROFILE_TICKS", "4096", "obs/profiler.py",
       "Tick-phase profiler ring capacity in tick records per engine "
       "(GET /debug/trace exports the ring's span): 120 s at 30 "
       "scheduler passes a second, 8-9 MB an engine when full."),
    _e("DLLM_OBS_SLOW_MS", "30000", "obs/__init__.py",
       "Global flight-recorder slow-request threshold in ms; '0'/'off' "
       "disables the slow trigger (failed/degraded still record)."),
    _e("DLLM_OBS_FLIGHT_CAPACITY", "32", "obs/__init__.py",
       "Global flight-recorder ring size (failed/degraded/slow requests "
       "plus overload incidents retained for GET /stats?debug=1)."),
    _e("DLLM_OBS_SAMPLE_MS", "250", "serving/router.py",
       "System-state sampler period in ms (obs/sampler.py timeline + "
       "/metrics gauges); '0' disables the sampler thread."),
    _e("DLLM_OBS_TIMELINE_SAMPLES", "240", "serving/router.py",
       "System-state timeline ring size in samples (60 s of history at "
       "the default 250 ms period)."),
    _e("DLLM_SLO_TTFT_MS", None, "serving/router.py",
       "Global TTFT SLO target override in ms for the goodput monitor "
       "(obs/slo.py); unset = each tier's TierConfig.slo_ttft_ms."),
    _e("DLLM_SLO_TBT_MS", None, "serving/router.py",
       "Global p95 time-between-tokens SLO target override in ms "
       "(obs/slo.py); unset = each tier's TierConfig.slo_tbt_ms."),
    _e("DLLM_FLAGSHIP_KV_INT8", None, "config.py",
       "'1' opts the single-chip flagship orin tier into int8 KV cache "
       "(default off: no chip measurement justifies it)."),
    _e("DLLM_HOST_KV_BYTES", None, "engine/batching.py",
       "Global override of TierConfig.host_kv_bytes — the host-RAM "
       "budget of the hierarchical KV spill tier in bytes ('0' disables "
       "it everywhere); unset = each tier's config decides."),
    _e("DLLM_KV_LEAK_CHECK", None, "engine/batching.py",
       "'1' arms the dynamic twin of the lint's ownership rules: engine "
       "stop() asserts zero allocated pool blocks and zero live spill "
       "pins once every slot, parked prefix, in-flight prefill and "
       "queued request has unwound.  Debug/test-only (the assert costs "
       "one ref_stats() sweep per stop); tests/conftest.py arms it for "
       "the whole suite."),
    _e("DLLM_TENANT_MAX_INFLIGHT", None, "serving/tenants.py",
       "Default per-tenant in-flight request cap for tenants absent "
       "from TierConfig.tenant_quotas (int); unset = unlimited.  Only "
       "read when a tier has tenant quotas ON (tenant_quotas set)."),
    _e("DLLM_TENANT_MAX_QUEUED", None, "serving/tenants.py",
       "Default per-tenant cap on requests waiting beyond the in-flight "
       "cap before admission rejects (int); unset = unlimited.  Quota-ON "
       "tiers only."),
    _e("DLLM_TENANT_DEVICE_MS_PER_S", None, "serving/tenants.py",
       "Default per-tenant device-time rate budget in measured "
       "device-milliseconds per wall second (float) — the token-bucket "
       "refill rate debited from each request's PR 11 device_time_ms "
       "bill; unset = unlimited.  Quota-ON tiers only."),
    _e("DLLM_TENANT_KV_BLOCKS", None, "serving/tenants.py",
       "Default per-tenant resident-KV budget in physical refcounted "
       "blocks, billed at 1/refcount per block (int); unset = "
       "unlimited.  Quota-ON tiers only."),
    _e("DLLM_TENANT_GAMMA_MAX", None, "serving/tenants.py",
       "Default per-tenant speculative γ cap (int) — PR 14's per-slot "
       "EWMA γ clamps to it; unset = the tier's spec_gamma_max.  "
       "Quota-ON tiers only."),
    _e("DLLM_REPLICA_POLICY", None, "serving/replicas.py",
       "Global replica-dispatch policy override for replicated tiers "
       "('affinity' | 'load' | 'random'); unset = "
       "TierConfig.replica_affinity decides (affinity when True, else "
       "least-loaded).  'random' exists for the dilution comparison "
       "(tests/test_replicas.py), not production."),
    _e("DLLM_AUTOSCALE", "1", "serving/router.py",
       "Elastic-capacity kill switch: '0' disarms every tier's "
       "ReplicaAutoscaler (no controller threads, membership stays the "
       "static PR 12 path, pinned byte-identical); any other value "
       "lets TierConfig.autoscale decide per tier."),
)}


# One-line summaries; authoritative rationale lives at each field's
# declaration in config.py (the lint checker pins NAME coverage both
# ways, not prose).
CONFIG_FIELDS: Dict[str, str] = {
    # -- TierConfig --------------------------------------------------------
    "TierConfig.name": "Tier identity ('nano' | 'orin' | ...).",
    "TierConfig.model_preset": "Key into MODEL_PRESETS for this tier's "
                               "architecture.",
    "TierConfig.tp": "Tensor-parallel degree (submesh size).",
    "TierConfig.sp": "Sequence-parallel degree for prefill (ring "
                     "attention over the 'sp' axis; dense only).",
    "TierConfig.ep": "Expert-parallel degree for MoE tiers (whole experts "
                     "sharded over 'ep').",
    "TierConfig.hbm_gb_per_chip": "Per-chip HBM budget (GB): when set, "
                                  "start_server eval_shape-budgets "
                                  "params + KV against the deployed "
                                  "submesh and refuses cleanly "
                                  "(TierOverCapacityError) when it "
                                  "doesn't fit; None = no admission "
                                  "budget.",
    "TierConfig.max_new_tokens": "Decode cap per request (reference "
                                 "num_predict).",
    "TierConfig.temperature": "Sampling temperature; 0 = greedy "
                              "(reference default).",
    "TierConfig.prefill_buckets": "Padded prompt-length rungs, one "
                                  "compiled program each.",
    "TierConfig.decode_batch": ">1 serves through the continuous-batching "
                               "engine with that many concurrent slots.",
    "TierConfig.kv_block_size": "Paged KV pool block granularity "
                                "(engine/paged_kv.py).",
    "TierConfig.decode_steps_per_tick": "Sequential decode steps fused "
                                        "into one device call per tick.",
    "TierConfig.attention_ragged": "Asks for the fused decode tick: ONE "
                                   "paged-attention call over full "
                                   "block tables with per-slot lengths "
                                   "(no bucketed window rungs); granted "
                                   "off the TPU, qualifying TP meshes "
                                   "run it under shard_map over the "
                                   "kv-head axis.",
    "TierConfig.prefill_chunk_tokens": "Cold prompts past one chunk "
                                       "prefill in fixed chunks of this "
                                       "many tokens interleaved with "
                                       "decode ticks (multiple of "
                                       "kv_block_size); 0/None = "
                                       "monolithic one-shot prefill.",
    "TierConfig.prefill_chunk_budget": "Prefill tokens one scheduler "
                                       "tick may spend advancing the "
                                       "in-flight prefill (whole "
                                       "chunks); None = one chunk per "
                                       "tick.",
    "TierConfig.admission_max_queue": "Max requests waiting beyond the "
                                      "slots before fail-fast; None "
                                      "disables admission control.",
    "TierConfig.kv_admission": "Gate admission on projected KV block "
                               "demand vs free + reclaimable parked "
                               "blocks; False = slot/queue admission "
                               "only.",
    "TierConfig.kv_pool_blocks": "Paged KV pool size override in blocks; "
                                 "None = full per-slot residency (no "
                                 "pressure possible).",
    "TierConfig.overflow_policy": "Over-length prompt policy at the "
                                  "router: 'reject' fails fast, "
                                  "'truncate_left' drops oldest turns "
                                  "(surfaced in the response).",
    "TierConfig.drain_timeout_s": "Graceful-drain deadline: in-flight "
                                  "requests get this long to finish "
                                  "after admission stops.",
    "TierConfig.checkpoint_path": "Orbax dir to serve trained weights "
                                  "from; None = deterministic random "
                                  "init.",
    "TierConfig.draft_preset": "Draft model preset for speculative "
                               "decoding; None = plain decoding.",
    "TierConfig.speculative_gamma": "Draft tokens proposed per "
                                    "speculative round (sequential "
                                    "decode_batch=1 engine).",
    "TierConfig.spec_decode": "Batched speculative decoding on the "
                              "ragged paged kernel (decode_batch>1 + "
                              "draft_preset): per-slot drafts verified "
                              "in ONE fused ragged_verify call, greedy "
                              "acceptance, rejected-tail frontier "
                              "rewind; byte-identical greedy outputs. "
                              "Tri-state: None=AUTO (EngineManager arms "
                              "it on batched draft tiers), True=force "
                              "on, False=operator kill switch (draft "
                              "tier serves plain batched decode).",
    "TierConfig.spec_gamma_max": "Per-slot adaptive γ cap for batched "
                                 "speculation: slots start here, an "
                                 "acceptance EWMA scales each down "
                                 "(γ=0 = plain ragged decode); the "
                                 "compiled draft/verify family is the "
                                 "power-of-two bucket ladder up to it.",
    "TierConfig.enable_prefix_cache": "Park finished requests' KV for "
                                      "suffix-only re-prefill "
                                      "(multi-turn chats).",
    "TierConfig.prefix_cache_entries": "Parked KV prefixes kept per tier "
                                       "(each pins HBM).",
    "TierConfig.share_prefix_kv": "Prefix-cache hits on batched paged "
                                  "engines map the parked blocks "
                                  "read-only into N concurrent slots "
                                  "(refcounted, copy-on-write at the "
                                  "boundary block) instead of taking "
                                  "exclusive ownership; False restores "
                                  "one-live-session-per-prefix.",
    "TierConfig.host_kv_bytes": "Host-RAM byte budget of the "
                                "hierarchical KV spill tier (demoted "
                                "prefix-cache entries; async copies "
                                "off the tick path); 0/None disables "
                                "it.  DLLM_HOST_KV_BYTES overrides "
                                "globally.",
    "TierConfig.host_kv_promote_share": "Fraction of the per-tick "
                                        "chunked-prefill budget "
                                        "promotion host→device grants "
                                        "may spend (floored at one "
                                        "block per tick).",
    "TierConfig.host_kv_copier_depth": "Spill copier queue depth "
                                       "(pending demote snapshots); a "
                                       "full queue drops further "
                                       "demotions instead of backing "
                                       "up the scheduler.",
    "TierConfig.quantize": "Weight-only serving quantization ('none' | "
                           "'int8').",
    "TierConfig.kv_quantize": "KV-cache quantization ('none' | 'int8'); "
                              "dense family only.",
    "TierConfig.endpoint": "Base URL of a cross-host tpu_api server; "
                           "set = no local engine is built.",
    "TierConfig.spawn_cmd": "Supervisor argv that (re)starts the remote "
                            "tier process (must kill-then-start).",
    "TierConfig.request_timeout_s": "Per-request wall cap; past it the "
                                    "reference error shape returns and "
                                    "the worker is abandoned.",
    "TierConfig.slo_ttft_ms": "TTFT SLO target (ms) for the goodput "
                              "monitor; None disables the criterion.",
    "TierConfig.slo_tbt_ms": "Per-request p95 time-between-tokens SLO "
                             "target (ms); None disables the criterion.",
    "TierConfig.watchdog_stall_s": "Decode-watchdog deadline: pending "
                                   "work with no step progress for this "
                                   "long reads as wedged.",
    "TierConfig.replicas": ">1 gives the tier that many data-parallel "
                           "engine replicas (own queue/breaker/watchdog/"
                           "drain each; health and KV stats aggregate "
                           "with per-replica breakdown).",
    "TierConfig.replica_affinity": "Route requests to the replica "
                                   "already holding their parked KV "
                                   "prefix (select_reuse matching); "
                                   "False = pure least-loaded dispatch.",
    "TierConfig.replica_affinity_min_tokens": "Minimum parked-prefix "
                                              "token match that binds a "
                                              "request to a replica.",
    "TierConfig.replica_affinity_override_s": "Affinity yields to "
                                              "least-loaded when the "
                                              "affine replica's "
                                              "predicted queue wait "
                                              "exceeds the best "
                                              "replica's by more than "
                                              "this many seconds.",
    "TierConfig.tenant_quotas": "Per-tenant isolation budgets (tenant "
                                "name → TenantQuota): admission caps, a "
                                "device-time-rate token bucket billed "
                                "from measured cost, DWRR weights, "
                                "resident-KV block budgets at "
                                "1/refcount, and speculative γ caps; "
                                "None = quotas OFF (byte-identical "
                                "pre-tenant behavior).",
    "TierConfig.autoscale": "Arms the per-tier SLO-driven replica "
                            "autoscaler (serving/autoscaler.py); False "
                            "= static membership, byte-identical to the "
                            "replicated-tier path (pinned).  "
                            "DLLM_AUTOSCALE=0 disarms globally.",
    "TierConfig.autoscale_min_replicas": "Membership floor the "
                                         "autoscaler never scales "
                                         "below (also the initial size "
                                         "when larger than replicas).",
    "TierConfig.autoscale_max_replicas": "Membership ceiling the "
                                         "autoscaler never scales "
                                         "above.",
    "TierConfig.autoscale_interval_s": "Controller cadence: one signal "
                                       "read + decision per interval.",
    "TierConfig.autoscale_goodput_floor": "Scale-up trigger: windowed "
                                          "SLO goodput sustained below "
                                          "this fraction breaches.",
    "TierConfig.autoscale_queue_high": "Scale-up trigger: queue depth "
                                       "sustained above this many "
                                       "requests per live replica "
                                       "breaches.",
    "TierConfig.autoscale_breach_window_s": "How long a breach must "
                                            "persist before scale-up "
                                            "fires (hysteresis).",
    "TierConfig.autoscale_idle_window_s": "How long the tier must be "
                                          "fully idle before "
                                          "scale-down fires.",
    "TierConfig.autoscale_up_cooldown_s": "Minimum seconds after any "
                                          "membership event before the "
                                          "next scale-up.",
    "TierConfig.autoscale_down_cooldown_s": "Minimum seconds after any "
                                            "membership event before "
                                            "the next scale-down.",
    "TierConfig.autoscale_warm_pool": "True pre-warms min..max standby "
                                      "replicas at tier start and "
                                      "parks drained replicas, so "
                                      "scale-up publishes a warm "
                                      "standby in milliseconds; False "
                                      "builds/destroys engines at "
                                      "actuation time.",
    "TierConfig.replica_rescue": "Crash rescue: a replica restart "
                                 "captures its queued + in-flight "
                                 "requests and re-dispatches them to a "
                                 "sibling (or requeues on the restarted "
                                 "engine), resuming byte-identically "
                                 "under greedy; False fails them with "
                                 "the engine-stopped shape.",
    "TierConfig.spill_survive_restart": "Host KV spill store outlives a "
                                        "replica restart and re-attaches "
                                        "to the rebuilt engine (or hands "
                                        "entries to a survivor), so "
                                        "restart cost is warm-TTFT "
                                        "promotion, not cold prefill; "
                                        "False stops the store with the "
                                        "engine.",
    # -- ClusterConfig -----------------------------------------------------
    "ClusterConfig.nano": "The weak/cheap tier's TierConfig.",
    "ClusterConfig.orin": "The strong/costly tier's TierConfig.",
    "ClusterConfig.seed": "Deterministic init seed shared by both tiers.",
    "ClusterConfig.breaker_failures": "Consecutive error-shaped results "
                                      "that open a tier's circuit; 0 "
                                      "disables the breaker.",
    "ClusterConfig.breaker_cooldown_s": "Open-circuit cooldown before a "
                                        "half-open canary.",
    "ClusterConfig.retry_attempts": "Bounded same-tier retries for "
                                    "transient error shapes.",
    "ClusterConfig.retry_backoff_s": "Initial jittered backoff between "
                                     "transient retries.",
}


# -- typed env accessors (the loud-failure path) ------------------------------

def _entry(name: str) -> EnvVar:
    try:
        return ENV_VARS[name]
    except KeyError:
        raise UnknownConfigError(
            f"env var {name!r} is not in config_registry.ENV_VARS — "
            f"register it (with a docstring) or fix the typo") from None


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw registered read; ``default`` overrides the registry default
    for call sites whose fallback is contextual."""
    entry = _entry(name)
    if default is None:
        default = entry.default
    return os.environ.get(name, default)


def env_flag(name: str) -> bool:
    """Boolean convention used across the repo: set to '1' = on."""
    return os.environ.get(_entry(name).name) == "1"


def env_float(name: str, default: float) -> float:
    """Float read that never throws on garbage: a bad value must not
    lose the run — fall back and keep going."""
    raw = os.environ.get(_entry(name).name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def env_int(name: str, default: int) -> int:
    raw = os.environ.get(_entry(name).name)
    if raw is None:
        return default
    try:
        return int(raw)
    except ValueError:
        return default


# -- CONFIG.md generation -----------------------------------------------------

def render_markdown() -> str:
    """The CONFIG.md body (pinned in sync by tests/test_lint.py)."""
    lines = [
        "# Configuration registry",
        "",
        "Generated from `distributed_llm_tpu/config_registry.py` "
        "(`python -m distributed_llm_tpu.config_registry > CONFIG.md`).",
        "The `config-drift` lint checker fails tier-1 when code and this "
        "registry disagree in either direction.",
        "",
        "## Environment variables (`DLLM_*`)",
        "",
        "| Variable | Default | Consumer | Semantics |",
        "|---|---|---|---|",
    ]
    def cell(text: str) -> str:
        return text.replace("|", "\\|")     # keep table cells intact

    for v in sorted(ENV_VARS.values(), key=lambda v: v.name):
        default = "(unset)" if v.default is None else f"`{v.default}`"
        lines.append(f"| `{v.name}` | {default} | {cell(v.consumer)} "
                     f"| {cell(v.doc)} |")
    lines += [
        "",
        "## Config dataclass fields",
        "",
        "One-line summaries; the authoritative rationale lives at each "
        "field's declaration in `distributed_llm_tpu/config.py`.",
        "",
        "| Field | Semantics |",
        "|---|---|",
    ]
    for field in sorted(CONFIG_FIELDS):
        lines.append(f"| `{field}` | {cell(CONFIG_FIELDS[field])} |")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import sys
    sys.stdout.write(render_markdown())
