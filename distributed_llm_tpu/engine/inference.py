"""The TPU inference engine: compiled prefill + autoregressive decode.

This replaces the reference's entire Ollama dependency (the "/api/generate"
hot loop, SURVEY.md §3.1): tokenize → bucketed prefill → XLA-compiled
``lax.while_loop`` decode with the KV cache resident in HBM → detokenize.

Compilation strategy (the part the reference never had to think about):

- **Prefill** is jitted once per (batch, bucket) shape.  Prompts are
  right-padded up to the nearest bucket so arbitrary prompt lengths reuse a
  handful of compiled programs instead of recompiling per length.
- **Decode** is ONE jitted ``lax.while_loop`` over a fixed-size KV cache
  (cfg.max_seq_len), compiled once per engine regardless of bucket: the
  whole multi-token generation is a single device call, with data-dependent
  early exit on EOS — no per-token host round-trips.
- The prefill call also seeds the cache and samples the first token, so
  TTFT == one device call after tokenize.

Timing: TTFT and total latency are measured around the two device calls,
feeding the perf routing strategy and the req/s + p50 TTFT headline metric
(BASELINE.json).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..config import TierConfig
from .. import models
from ..models import transformer
from ..ops.sampling import sample_token_dynamic

logger = logging.getLogger(__name__)
from .tokenizer import ByteTokenizer, get_tokenizer


@dataclasses.dataclass
class GenerationResult:
    text: str
    token_ids: List[int]
    prompt_tokens: int
    gen_tokens: int
    ttft_ms: float
    total_ms: float

    @property
    def tokens_per_s(self) -> float:
        if self.total_ms <= 0 or self.gen_tokens == 0:
            return 0.0
        return 1000.0 * self.gen_tokens / self.total_ms


def pick_bucket(buckets: Sequence[int], n: int, max_seq: int) -> int:
    """Smallest configured prefill bucket holding ``n`` tokens (capped at
    the model's max_seq_len)."""
    for b in buckets:
        if n <= b and b <= max_seq:
            return b
    return min(max(buckets), max_seq)


def prepare_prompt(tokenizer: ByteTokenizer, history, buckets: Sequence[int],
                   max_seq: int, reserve: int,
                   allow_long: bool = False) -> Tuple[List[int], int]:
    """Tokenize + tail-truncate a prompt and pick its bucket.

    ``reserve`` tokens are kept free for generation; overlong prompts keep
    their TAIL (most recent turns), mirroring the reference's silent
    context truncation (SURVEY.md §5.7).  With ``allow_long`` the bucket
    cap does NOT truncate: prompts beyond the largest bucket keep their
    full (max_seq-bounded) length for chunked prefill — only engines that
    implement the chunk loop pass this.
    """
    ids = tokenizer.encode_history(history)
    max_prompt = max_seq - reserve
    if len(ids) > max_prompt:
        ids = ids[-max_prompt:]
    bucket = pick_bucket(buckets, len(ids), max_seq)
    if len(ids) > bucket and not allow_long:
        ids = ids[-bucket:]
    return ids, bucket


def trim_at_eos(tokens: Sequence[int], eos_id: int, pad_id: int) -> List[int]:
    """Generated ids up to (excluding) the first EOS/PAD."""
    out: List[int] = []
    for t in tokens:
        if t in (eos_id, pad_id):
            break
        out.append(int(t))
    return out


def upgrade_attention_impl(cfg, mesh) -> Any:
    """Unsharded tiers on TPU upgrade "auto" attention to the Pallas flash
    kernels; sharded meshes stay on the GSPMD-partitionable XLA path (a
    pallas_call has no sharding rule — see ops/attention.py)."""
    if (cfg.attention_impl == "auto" and mesh is None
            and jax.default_backend() == "tpu"):
        return dataclasses.replace(cfg, attention_impl="pallas")
    return cfg


class InferenceEngine:
    """Single-tier engine: one model, one (sub)mesh, synchronous generate().

    ``shardings`` (optional) carries NamedShardings for params/cache built by
    parallel/sharding.py; without it everything lives on one device.
    """

    def __init__(
        self,
        tier: TierConfig,
        seed: int = 0,
        params: Optional[Dict[str, Any]] = None,
        mesh: Optional[jax.sharding.Mesh] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ):
        self.tier = tier
        self.cfg = upgrade_attention_impl(tier.model(), mesh)
        self.tokenizer = get_tokenizer(self.cfg)
        self.mesh = mesh
        self._rng = jax.random.PRNGKey(seed ^ 0x5EED)

        if devices is None and mesh is not None:
            devices = list(mesh.devices.flat)
        self.devices = devices

        if params is None:
            if tier.checkpoint_path:
                # Serve the tier's published weights (the reference serves
                # pretrained models, src/devices/nano_api.py:15-16); only
                # checkpoint-less tiers fall back to deterministic random
                # init.  EngineManager pre-loads and passes params in; this
                # covers direct engine construction.
                from ..utils.checkpoint import load_params_for_tier
                params = load_params_for_tier(
                    tier.checkpoint_path, self.cfg, mesh=mesh,
                    devices=self.devices)
            else:
                params = self._init_params(seed)
        from ..ops.quant import maybe_quantize
        self.params = maybe_quantize(params, tier, self.cfg, mesh=mesh)

        self._prefill_fns: Dict[Any, Any] = {}
        self._decode_fns: Dict[int, Any] = {}
        self._grow_fns: Dict[Any, Any] = {}
        self._max_seq = self.cfg.max_seq_len
        # Usable prefill buckets, ascending — the single source for both
        # generate()'s suffix-bucket choice and warmup()'s precompiles.
        self._buckets = sorted(set(
            b for b in tier.prefill_buckets if b <= self._max_seq))
        # Sequence-parallel tiers extend the ladder to max_seq: each chip
        # holds only S/sp of the activations, so the whole model context
        # prefills as ONE ring-attention call — the O(S²) long-prompt case
        # sp exists for.  Without this, prompts past the largest bucket
        # would fall to the chunk-stride path, which the sp hook does not
        # cover (suffix chunks are O(delta) and stay GSPMD-sharded).
        # Prefix-reuse SUFFIX bucketing keeps the unextended tier ladder:
        # a long new turn should chunk-stride (O(delta), warmed programs),
        # not pad out to a giant unsharded suffix prefill.
        self._suffix_buckets = list(self._buckets)
        # Suffix buckets a prompt will REUSE a parked prefix through:
        # the ≤256-token rungs cover typical chat turns, and warmup
        # compiles every (reuse bucket, cache rung) suffix program — a
        # prefix-hit turn can never trace mid-chat.  Longer new turns
        # take the (warmed) chunk-stride path via allow_long_suffix
        # instead of minting ever more suffix shapes.  Selecting by SIZE
        # (not the first three rungs) keeps a short ladder like
        # (64, 256, 2048) from promoting its max-shape rung into a
        # warmup suffix compile and from padding mid-size follow-ups to
        # the top bucket (code review r5).
        self._reuse_buckets = ([b for b in self._buckets if b <= 256][:3]
                               or self._buckets[:1])
        if (mesh is not None and dict(mesh.shape).get("sp", 1) > 1
                and self.cfg.num_experts == 1
                and self._buckets and self._buckets[-1] < self._max_seq):
            ladder = self._buckets[-1]
            while ladder * 2 <= self._max_seq:
                ladder *= 2
                self._buckets.append(ladder)
            if self._buckets[-1] < self._max_seq:
                self._buckets.append(self._max_seq)
        # Bucketed KV-cache lengths: decode attention reads the WHOLE cache
        # every step, so sizing it to the conversation (next candidate ≥
        # prompt + decode cap) instead of max_seq_len cuts decode's HBM
        # traffic up to max_seq/256× for short chats.  A coarse ladder keeps
        # the compile count at ≤3 decode programs per engine.
        self._cache_lens = sorted(
            {c for c in (256, 1024) if c < self._max_seq} | {self._max_seq})
        # Per-phase wall-time attribution (tokenize/prefill/decode/detok) —
        # the jax.profiler-adjacent view surfaced at GET /stats (§5.1/§5.5).
        from ..utils.telemetry import PhaseTimer
        self.phases = PhaseTimer()
        # Roofline work accounting (utils/roofline.py): weight bytes one
        # decode step streams, for MFU / HBM-utilization in the bench.
        from ..utils import roofline
        self._wbytes = roofline.weight_bytes(self.cfg, tier.quantize)
        # int8 contiguous KV cache (models/transformer.py seed/decode/
        # chunk paths).  Dense only: the MoE family keeps a bf16 cache.
        self._kv_quantize = tier.kv_quantize
        if self._kv_quantize != "none" and self.cfg.num_experts > 1:
            logger.warning("tier %s: kv_quantize=%s ignored for the MoE "
                           "family (bf16 cache)", tier.name,
                           self._kv_quantize)
            self._kv_quantize = "none"

        # Session KV prefix reuse (engine/prefix_cache.py), both model
        # families (transformer/moe each export chunk_prefill).  Each
        # parked entry pins a full KV cache in HBM, so capacity is a tier
        # knob.
        from .prefix_cache import PrefixCache
        self.prefix_cache = (
            PrefixCache(capacity=tier.prefix_cache_entries)
            if tier.enable_prefix_cache and tier.prefix_cache_entries > 0
            else None)

        # Sequence-parallel DECODE (parallel/sp_attention.py): keep the
        # KV cache's sequence axis sharded over 'sp' so context capacity
        # and per-chip KV streaming both scale with the sp degree (ring
        # attention already covers prefill).  Dense bf16 caches only.
        # The suffix/chunk prefix-reuse paths would regather the sharded
        # cache per layer — exactly the buffer sp exists to split — so
        # prefix reuse turns off on these tiers.
        self._sp_shard = (mesh is not None
                          and dict(mesh.shape).get("sp", 1) > 1
                          and self.cfg.num_experts == 1
                          and self._kv_quantize == "none")
        self._cache_shardings = None
        if self._sp_shard:
            if self.prefix_cache is not None:
                logger.info("tier %s: prefix cache disabled under "
                            "sequence-parallel decode", tier.name)
                self.prefix_cache = None
            from ..parallel.sharding import kv_cache_shardings
            self._cache_shardings = kv_cache_shardings(
                mesh, sp_axis="sp")

    def _constrain_cache(self, cache, cache_len: int):
        """Pin the sequence-sharded cache layout (no-op otherwise)."""
        if self._cache_shardings is None or cache_len % dict(
                self.mesh.shape)["sp"]:
            return cache
        return jax.lax.with_sharding_constraint(cache,
                                                self._cache_shardings)

    # ------------------------------------------------------------------

    def _init_params(self, seed: int) -> Dict[str, Any]:
        init = jax.jit(partial(models.init_params, self.cfg),
                       static_argnames=("seed",))
        if self.mesh is not None:
            from ..parallel.sharding import param_shardings
            shardings = param_shardings(self.cfg, self.mesh)
            init = jax.jit(partial(models.init_params, self.cfg),
                           static_argnames=("seed",), out_shardings=shardings)
        elif self.devices:
            init = jax.jit(partial(models.init_params, self.cfg),
                           static_argnames=("seed",),
                           out_shardings=jax.sharding.SingleDeviceSharding(self.devices[0]))
        return init(seed=seed)

    # -- compiled stages ---------------------------------------------------

    def _pick_cache_len(self, needed: int) -> int:
        """Smallest cache-length candidate covering ``needed`` positions."""
        return next(c for c in self._cache_lens if c >= min(needed,
                                                            self._max_seq))

    def _sp_attn(self, bucket: int):
        """Prefill attention override for mesh tiers: ring attention when
        the mesh has an 'sp' axis dividing this bucket (dense only —
        models.serving_prefill ignores the hook for MoE); otherwise the
        shard-mapped flash kernel on tp-only meshes where Pallas is the
        preferred prefill impl (parallel/tp_attention.py — round 1 left
        sharded tiers entirely on XLA)."""
        mesh = self.mesh
        if mesh is None or self.cfg.num_experts > 1:
            return None
        if ("sp" in mesh.shape and mesh.shape["sp"] > 1
                and bucket % mesh.shape["sp"] == 0):
            from ..parallel.ring_attention import ring_attention
            head_axis = "tp" if mesh.shape.get("tp", 1) > 1 else None
            return lambda q, k, v: ring_attention(q, k, v, mesh, "sp",
                                                  head_axis=head_axis)
        from ..parallel.tp_attention import tp_prefill_attn
        return tp_prefill_attn(mesh, self.cfg, bucket)

    def _prefill_fn(self, bucket: int, cache_len: int):
        """Jitted per (prompt bucket, cache length): embed+forward the
        padded prompt, seed a cache sized for this conversation, sample the
        first token."""
        key = (bucket, cache_len)
        if key in self._prefill_fns:
            return self._prefill_fns[key]

        cfg = self.cfg
        sp_attn = self._sp_attn(bucket)

        def run(params, tokens, true_len, rng, temperature):
            b, s = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            hidden, (k_all, v_all) = models.serving_prefill(
                cfg, params, tokens, positions, attn=sp_attn)
            # logits only at each sequence's last real position
            last = hidden[jnp.arange(b), true_len - 1]
            logits = transformer.logits_from_hidden(params, last)
            first = sample_token_dynamic(logits, rng, temperature)

            cache = transformer.seed_kv_cache(cfg, k_all, v_all, cache_len,
                                              self._kv_quantize)
            return first, self._constrain_cache(cache, cache_len)

        fn = jax.jit(run)
        self._prefill_fns[key] = fn
        return fn

    def _init_cache_fn(self, cache_len: int):
        """Jitted per length: a fresh zero cache (chunked long prefill
        starts from one instead of a prefill-seeded cache)."""
        key = ("init", cache_len)
        if key not in self._grow_fns:
            cfg = self.cfg
            kvq = self._kv_quantize
            self._grow_fns[key] = jax.jit(
                lambda: self._constrain_cache(
                    transformer.init_kv_cache(cfg, 1, cache_len, kvq),
                    cache_len))
        return self._grow_fns[key]

    def _long_prefill(self, ids, cache_len: int, rng, temp,
                      cache=None, start0: int = 0):
        """Chunked prefill for prompts beyond the largest bucket: stride
        the prompt through the suffix-prefill program in largest-bucket
        chunks (each attending the bucketed window of everything before
        it).  The reference silently truncates here (Ollama's context
        window, SURVEY.md §5.7); owning the engine, we serve the model's
        whole max_seq_len with a handful of compiled programs.

        ``cache``/``start0``: resume from a reclaimed prefix cache holding
        positions < start0 (long-suffix prefix reuse) instead of a fresh
        zero cache.

        Returns (first sampled token, seeded cache) like a prefill fn —
        only the LAST chunk's sample (at the true final position) is
        meaningful, and only it is used.
        """
        n = len(ids)
        # Stride with the SUFFIX ladder's largest bucket: on sp tiers the
        # prompt ladder extends to max_seq (ring prefill), but chunk
        # striding should keep the warmed tier-bucket-sized programs.
        cb = self._suffix_buckets[-1]
        if cache is None:
            cache = self._init_cache_fn(cache_len)()
        first = None
        for start in range(start0, n, cb):
            chunk = ids[start:start + cb]
            tokens = np.full((1, cb), self.tokenizer.pad_id, np.int32)
            tokens[0, :len(chunk)] = chunk
            window = min(self._suffix_window(start + cb), cache_len)
            first, cache = self._suffix_prefill_fn(cb, window)(
                self.params, cache, jnp.asarray(tokens),
                jnp.asarray([start], np.int32), jnp.asarray([n], np.int32),
                rng, temp)
        return first, cache

    def _grow_fn(self, src_len: int, dst_len: int):
        """Jitted per pair: copy a parked cache into a longer one (prefix
        reuse across conversations that outgrew the parked length)."""
        key = ("grow", src_len, dst_len)
        if key not in self._grow_fns:
            cfg = self.cfg

            kvq = self._kv_quantize

            def run(cache):
                b = cache["k"].shape[1]
                big = transformer.init_kv_cache(cfg, b, dst_len, kvq)
                return {
                    key: jax.lax.dynamic_update_slice(
                        big[key], cache[key], (0,) * big[key].ndim)
                    for key in big
                }

            donate = (0,) if jax.default_backend() != "cpu" else ()
            self._grow_fns[key] = jax.jit(run, donate_argnums=donate)
        return self._grow_fns[key]

    def _suffix_prefill_fn(self, bucket: int, window: int):
        """Jitted per (suffix bucket, attention window): forward only a
        prompt SUFFIX against a parked prefix cache (session KV reuse — see
        engine/prefix_cache.py), then sample the first token.  ``window``
        statically bounds the attended cache prefix so cost is O(prefix
        bucket), not O(max_seq).  The cache is donated: the entry was
        removed from the prefix cache by take(), so no live alias remains."""
        key = ("suffix", bucket, window)
        if key in self._prefill_fns:
            return self._prefill_fns[key]

        cfg = self.cfg

        def run(params, cache, tokens, start, true_len, rng, temperature):
            b = tokens.shape[0]
            hidden, cache = models.model_module(cfg).chunk_prefill(
                cfg, params, tokens, start, true_len, cache, window=window)
            last = hidden[jnp.arange(b), true_len - start - 1]
            logits = transformer.logits_from_hidden(params, last)
            first = sample_token_dynamic(logits, rng, temperature)
            return first, cache

        donate = (1,) if jax.default_backend() != "cpu" else ()
        fn = jax.jit(run, donate_argnums=donate)
        self._prefill_fns[key] = fn
        return fn

    def _suffix_window(self, needed: int) -> int:
        """Smallest bucketed attention window covering ``needed`` cache
        positions (falls back to the full sequence)."""
        return next((b for b in self._buckets if b >= needed), self._max_seq)

    def _decode_loop(self, cache_len: int):
        """Jitted per cache length: the full generation loop as one device
        call (the loop body's shapes are fixed by the cache, so one program
        serves every conversation at that length)."""
        if cache_len in self._decode_fns:
            return self._decode_fns[cache_len]

        cfg = self.cfg
        eos = self.tokenizer.eos_id
        pad = self.tokenizer.pad_id
        max_new = self.tier.max_new_tokens   # static cap: sizes the buffer
        # Sequence-parallel tiers: partial+merge decode over the
        # 'sp'-sharded cache (parallel/sp_attention.py).  TP-only tiers
        # keep the GSPMD XLA path.  Dense models only.
        decode_kw = {}
        if (cfg.num_experts == 1 and self._kv_quantize == "none"
                and self._sp_shard):
            from ..parallel.sp_attention import sp_decode_attn
            hook = sp_decode_attn(self.mesh, cfg, cache_len)
            if hook is not None:
                decode_kw["attn"] = hook

        def run(params, cache, first_token, prompt_len, rng, temperature,
                token_budget):
            # ``token_budget`` is a runtime operand (≤ max_new): per-request
            # num_predict overrides exit the loop early instead of decoding
            # the full tier cap and trimming on host.
            cache = self._constrain_cache(cache, cache_len)
            b = first_token.shape[0]
            out = jnp.full((b, max_new), pad, jnp.int32)
            out = out.at[:, 0].set(first_token)
            done = first_token == eos

            def cond(state):
                step, _, _, done, _ = state
                return (step < token_budget) & ~jnp.all(done)

            def body(state):
                step, out, cache, done, rng = state
                cur = out[:, step - 1]
                pos = prompt_len + step - 1       # position of `cur`
                logits, cache = models.model_module(cfg).decode_step(
                    cfg, params, cur, pos, cache, **decode_kw)
                rng, sub = jax.random.split(rng)
                nxt = sample_token_dynamic(logits, sub, temperature)
                nxt = jnp.where(done, pad, nxt)
                out = out.at[:, step].set(nxt)
                done = done | (nxt == eos)
                return step + 1, out, cache, done, rng

            step, out, cache, done, rng = jax.lax.while_loop(
                cond, body, (jnp.int32(1), out, cache, done, rng))
            # The cache is returned (not dropped) so the host can park it
            # for session prefix reuse; donation still updates it in place.
            return out, step, cache

        # Donate the KV cache so the loop updates it in place in HBM.
        # (CPU can't donate these buffers and warns, so gate on backend.)
        donate = (1,) if jax.default_backend() != "cpu" else ()
        self._decode_fns[cache_len] = jax.jit(run, donate_argnums=donate)
        return self._decode_fns[cache_len]

    # -- host orchestration ------------------------------------------------

    def _prepare_and_prefill(self, history, max_new_tokens, temperature):
        """Shared front half of generate()/generate_stream(): tokenize,
        pick cache length, run (reuse-aware / chunked / bucketed) prefill.
        Returns (first token, cache, cache_len, ids, budget, rng, temp,
        ttft_ms, t0)."""
        t0 = time.perf_counter()
        with self.phases.phase("tokenize"):
            ids, bucket = prepare_prompt(self.tokenizer, history,
                                         self._buckets,
                                         self._max_seq,
                                         self.tier.max_new_tokens,
                                         allow_long=True)
        n = len(ids)
        # Chunked long prefill strides in largest-bucket steps; if the
        # strided span cannot fit max_seq (non-dividing bucket sizes),
        # keep the largest chunk-able tail (reference-style truncation,
        # but only of what the chunk loop genuinely cannot serve).
        cb = self._buckets[-1] if self._buckets else bucket
        span = -(-n // cb) * cb
        if n > cb and span > self._max_seq:
            limit = min((self._max_seq // cb) * cb,
                        self._max_seq - self.tier.max_new_tokens)
            ids = ids[-limit:]
            n = len(ids)
            span = -(-n // cb) * cb
        is_long = bool(self._buckets) and n > cb
        true_len = np.array([n], np.int32)

        self._rng, rng1, rng2 = jax.random.split(self._rng, 3)
        temp = jnp.float32(
            self.tier.temperature if temperature is None else temperature)
        budget = self.tier.max_new_tokens
        if max_new_tokens and max_new_tokens > 0:
            budget = min(budget, max_new_tokens)

        # Session prefix reuse: reclaim a parked KV cache covering a prefix
        # of this prompt and forward only the suffix (O(delta) prefill
        # instead of O(history) — the reference re-prefills everything
        # through Ollama every turn, SURVEY.md §3.1).
        from .prefix_cache import select_reuse
        sel = select_reuse(self.prefix_cache, ids, self._reuse_buckets,
                           self._max_seq, allow_long_suffix=True)
        reused = (sel[0].cache, sel[1], sel[2], sel[3]) if sel else None

        # Size the cache for this conversation, not the model maximum —
        # decode streams the whole cache per step.  Sized with the TIER's
        # decode cap (not the per-request override) so repeat prompt shapes
        # always reuse the warmed compiles.
        needed = max(n + self.tier.max_new_tokens, bucket)
        if is_long:
            needed = max(needed, span)
        if reused is not None:
            m, sb = reused[1], reused[3]
            if sb is None:     # bucket-exceeding suffix, chunked from m
                scb = self._suffix_buckets[-1]   # the chunk-stride size
                needed = max(needed, m + -(-(n - m) // scb) * scb)
            else:
                needed = max(needed, m + sb)
        cache_len = self._pick_cache_len(needed)

        from ..utils import roofline
        cb_s = self._suffix_buckets[-1] if self._suffix_buckets else bucket
        with self.phases.phase("prefill"):
            if reused is not None:
                cache0, m, suffix, sb = reused
                parked_len = int(cache0["k"].shape[2])
                if parked_len < cache_len:
                    cache0 = self._grow_fn(parked_len, cache_len)(cache0)
                else:
                    cache_len = parked_len    # bigger parked cache: keep it
                if sb is None:   # long new turn: chunk-stride from m
                    first, cache = self._long_prefill(
                        ids, cache_len, rng1, temp, cache=cache0, start0=m)
                    chunks = -(-(n - m) // cb_s)
                    pwork = roofline.prefill_work(
                        self.cfg, m + chunks * cb_s, m,
                        wbytes=chunks * self._wbytes)
                else:
                    tokens = np.full((1, sb), self.tokenizer.pad_id, np.int32)
                    tokens[0, :len(suffix)] = suffix
                    # The suffix attends over the WHOLE allocated cache
                    # (window == cache_len): a tighter bucketed window
                    # would save only one decode-step's worth of reads
                    # while multiplying the compiled-program count per
                    # (sb, window, cache_len) combination — mid-chat XLA
                    # compiles cost seconds (tens on chip), so suffix
                    # shapes are (sb, cache_len) and warmup can cover
                    # them all.
                    first, cache = self._suffix_prefill_fn(sb, cache_len)(
                        self.params, cache0, jnp.asarray(tokens),
                        jnp.asarray([m], np.int32), jnp.asarray(true_len),
                        rng1, temp)
                    # sb computed queries over the allocated span.
                    pwork = roofline.prefill_work(self.cfg, cache_len,
                                                  cache_len - sb,
                                                  wbytes=self._wbytes)
            elif is_long:        # beyond the largest bucket: chunked stride
                first, cache = self._long_prefill(ids, cache_len, rng1, temp)
                chunks = -(-n // cb_s)
                pwork = roofline.prefill_work(self.cfg, chunks * cb_s, 0,
                                              wbytes=chunks * self._wbytes)
            else:
                tokens = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
                tokens[0, :n] = ids
                first, cache = self._prefill_fn(bucket, cache_len)(
                    self.params, jnp.asarray(tokens), jnp.asarray(true_len),
                    rng1, temp)
                pwork = roofline.prefill_work(self.cfg, bucket, 0,
                                              wbytes=self._wbytes)
            first = jax.block_until_ready(first)
        self.phases.add_work("prefill", **pwork)
        ttft_ms = (time.perf_counter() - t0) * 1000.0

        # The decode cap must fit the sized cache (it always does when the
        # cache was sized fresh; a reclaimed shorter conversation's cache
        # was sized with the same tier cap).
        budget = min(budget, cache_len - n)
        return first, cache, cache_len, ids, budget, rng2, temp, ttft_ms, t0

    def generate(
        self,
        history: Union[str, Sequence[Dict[str, Any]]],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
    ) -> GenerationResult:
        """Synchronous generation from a prompt string or chat history.

        ``max_new_tokens`` may only shrink below the tier's compiled cap
        (the loop exits early), mirroring the reference's per-request
        ``num_predict`` override (src/devices/nano_api.py:62).
        ``temperature`` likewise overrides the tier default per request;
        both are runtime operands — no recompilation.
        """
        (first, cache, cache_len, ids, budget, rng2, temp, ttft_ms,
         t0) = self._prepare_and_prefill(history, max_new_tokens, temperature)
        n = len(ids)

        with self.phases.phase("decode"):
            out, steps, cache = self._decode_loop(cache_len)(
                self.params, cache, first, jnp.asarray([n], np.int32), rng2,
                temp, jnp.int32(budget))
            out = np.asarray(jax.block_until_ready(out))[0]
        from ..utils import roofline
        nsteps = max(0, int(steps) - 1)
        self.phases.add_work("decode", **roofline.decode_work(
            self.cfg, nsteps, cache_len,
            wbytes=self._wbytes, kv_quantize=self._kv_quantize))
        total_ms = (time.perf_counter() - t0) * 1000.0

        if self.prefix_cache is not None:
            # Park the post-decode cache: its first n positions hold this
            # prompt's KV (decode wrote past n; masks hide it until the next
            # suffix overwrites).  Next turn's history extends this prompt,
            # so it reclaims everything but the new turn.
            self.prefix_cache.put(ids, cache)

        with self.phases.phase("detokenize"):
            gen_ids = trim_at_eos(out.tolist()[:budget],
                                  self.tokenizer.eos_id,
                                  self.tokenizer.pad_id)
            text = self.tokenizer.decode(gen_ids)

        return GenerationResult(
            text=text,
            token_ids=gen_ids,
            prompt_tokens=n,
            gen_tokens=len(gen_ids),
            ttft_ms=ttft_ms,
            total_ms=total_ms,
        )

    def generate_stream(
        self,
        history: Union[str, Sequence[Dict[str, Any]]],
        max_new_tokens: Optional[int] = None,
        temperature: Optional[float] = None,
        segment: int = 8,
    ):
        """Token streaming for the sequential engine: same prefill as
        ``generate`` (TTFT = one device call), then the compiled decode
        loop runs in ``segment``-token slices — ``token_budget`` is a
        runtime operand, so slicing reuses the SAME compiled program, at
        one host round-trip per ``segment`` tokens.  Returns a
        StreamHandle (iterable of text deltas, ``.result`` once
        exhausted) with the same surface as the batching engine's."""
        from .batching import StreamHandle, _Request

        req = _Request(history=history, max_new_tokens=max_new_tokens,
                       temperature=temperature)

        def deltas():
            from .tokenizer import StreamDecoder
            decoder = StreamDecoder(self.tokenizer)
            eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id
            try:
                (first, cache, cache_len, ids, budget, rng, temp, ttft_ms,
                 t0) = self._prepare_and_prefill(history, max_new_tokens,
                                                 temperature)
            except BaseException as exc:
                req.error = exc
                req.done.set()
                raise
            n = len(ids)
            gen: List[int] = [int(np.asarray(first)[0])]
            decode = self._decode_loop(cache_len)

            try:
                if gen[-1] not in (eos, pad):
                    text = decoder.feed(gen[-1])
                    if text:
                        yield text
                while len(gen) < budget and gen[-1] not in (eos, pad):
                    # Continue from the last token at its absolute
                    # position: pos(gen[-1]) == n + len(gen) - 1.
                    seg = min(segment, budget - len(gen))
                    rng, sub = jax.random.split(rng)
                    with self.phases.phase("decode"):
                        out, steps, cache = decode(
                            self.params, cache,
                            jnp.asarray([gen[-1]], np.int32),
                            jnp.asarray([n + len(gen) - 1], np.int32),
                            sub, temp, jnp.int32(seg + 1))
                        out = np.asarray(jax.block_until_ready(out))[0]
                    from ..utils import roofline
                    nsteps = max(0, int(steps) - 1)
                    self.phases.add_work("decode", **roofline.decode_work(
                        self.cfg, nsteps, cache_len,
                        wbytes=self._wbytes,
                        kv_quantize=self._kv_quantize))
                    for tok in out[1:int(steps)].tolist():
                        gen.append(tok)
                        if tok in (eos, pad):
                            break
                        text = decoder.feed(tok)
                        if text:
                            yield text
                tail = decoder.flush()
                if tail:
                    yield tail

                if self.prefix_cache is not None:
                    self.prefix_cache.put(ids, cache)
                with self.phases.phase("detokenize"):
                    gen_ids = trim_at_eos(gen, eos, pad)
                    text_all = self.tokenizer.decode(gen_ids)
                req.result = GenerationResult(
                    text=text_all,
                    token_ids=gen_ids,
                    prompt_tokens=n,
                    gen_tokens=len(gen_ids),
                    ttft_ms=ttft_ms,
                    total_ms=(time.perf_counter() - t0) * 1000.0,
                )
            except BaseException as exc:
                req.error = exc
                raise
            finally:
                req.done.set()

        return StreamHandle(deltas(), req)

    def prefix_affinity(self, history) -> int:
        """Longest parked-prefix token match this engine could reuse for
        ``history`` — a NON-destructive probe for prefix-affinity routing
        (serving/router.py): the router prefers the tier already holding
        a conversation's KV over re-prefilling it cold elsewhere.  0 when
        reuse is off or nothing matches."""
        if self.prefix_cache is None or not self._reuse_buckets:
            return 0
        return self.prefix_affinity_tokens(self.affinity_token_ids(history))

    def affinity_token_ids(self, history):
        """Tokenize ``history`` as admission would — the shared half of
        the affinity probe (replica dispatch tokenizes once and peeks
        every replica with the same ids; serving/replicas.py)."""
        ids, _ = prepare_prompt(self.tokenizer, history, self._buckets,
                                self._max_seq, self.tier.max_new_tokens,
                                allow_long=True)
        return ids

    def prefix_affinity_tokens(self, ids) -> int:
        """Longest parked-prefix match for already-tokenized ``ids``
        (non-destructive peek; the per-replica half of the probe)."""
        if self.prefix_cache is None or not self._reuse_buckets:
            return 0
        # Same headroom cap as select_reuse's take() — the affinity score
        # must not promise tokens a real reclaim could not use.
        return self.prefix_cache.peek(
            ids, max_len=self._max_seq - self._reuse_buckets[0])

    def warmup(self, beat=None) -> None:
        """Compile EVERY prefill bucket + the decode loop, and (when prefix
        reuse is on) the suffix-prefill programs for the two smallest
        buckets — typical chat turns land there.  Compiling everything at
        startup keeps every request's TTFT free of XLA traces: lazy
        per-bucket compiles otherwise land inside whichever strategy run
        first crosses each prompt-length bucket (visible as a TTFT spike on
        the benchmark's first strategy).

        ``beat`` (liveness callback) fires after every compiled program:
        a full warmup is dozens of 20-40 s compiles on chip — far past
        a caller's wedge watchdog if warmup were silent."""
        beat = beat or (lambda: None)
        from ..utils.telemetry import PhaseTimer
        self.generate("warmup", max_new_tokens=1)
        beat()
        cap = self.tier.max_new_tokens
        # generate() sizes caches as pick(max(n + cap, bucket)) with
        # prev_bucket < n <= bucket, so each bucket can land on the ladder
        # rung of `bucket` or of `bucket + cap` — compile BOTH ends (the
        # range spans at most those rungs for any cap below the ladder
        # gap), plus each length's decode program.
        warm_caches = {}
        for bucket in self._buckets:
            for cache_len in {self._pick_cache_len(bucket),
                              self._pick_cache_len(bucket + cap)}:
                fresh = (bucket, cache_len) not in self._prefill_fns
                first, cache = self._prefill_fn(bucket, cache_len)(
                    self.params,
                    jnp.full((1, bucket), self.tokenizer.pad_id, jnp.int32),
                    jnp.asarray([1], np.int32), jax.random.PRNGKey(0),
                    jnp.float32(0.0))
                if fresh or cache_len not in self._decode_fns:
                    # NB the decode loop DONATES the cache: keep the one
                    # it returns, not the prefill's (now-deleted) buffers.
                    out, _, cache = self._decode_loop(cache_len)(
                        self.params, cache, jnp.asarray([0], np.int32),
                        jnp.asarray([1], np.int32), jax.random.PRNGKey(0),
                        jnp.float32(0.0), jnp.int32(1))
                    jax.block_until_ready(out)
                else:
                    jax.block_until_ready(first)
                beat()
                warm_caches.setdefault(cache_len, cache)
        if self.prefix_cache is not None:
            # Suffix programs are keyed (sb, cache_len) — window is always
            # the allocated span — so the two typical-chat-turn suffix
            # buckets × the cache rungs such conversations use cover the
            # multi-turn hot path completely (no mid-chat compiles).
            for sb in self._reuse_buckets:
                # Every rung a conversation with this suffix bucket can
                # grow into (≤3 on the shipped ladder) — a rung skipped
                # here is a mid-chat compile stall later.
                floor = self._pick_cache_len(sb + 1 + cap)
                for cache_len in [c for c in self._cache_lens
                                  if c >= floor]:
                    # Warm with a cache the ENGINE itself produced (the
                    # bucket loop's): serving always passes a parked
                    # jit-output cache — committed, placed on the tier's
                    # devices/mesh — and jit keys compilations on exactly
                    # that placement signature.  Warming with a
                    # hand-built cache compiles a signature serving never
                    # uses, and the real one then compiles mid-chat
                    # (seconds; tens of seconds on chip).
                    cache = warm_caches.get(cache_len)
                    if cache is None:
                        # Rung not minted by the bucket loop: produce one
                        # the same way serving does (placement signature
                        # must match — see above).
                        _, cache = self._prefill_fn(
                            self._buckets[0], cache_len)(
                            self.params,
                            jnp.full((1, self._buckets[0]),
                                     self.tokenizer.pad_id, jnp.int32),
                            jnp.asarray([1], np.int32),
                            jax.random.PRNGKey(0), jnp.float32(0.0))
                    # The suffix program donates its cache on TPU: keep
                    # the returned one so the next rung/bucket can reuse
                    # it.
                    first, cache = self._suffix_prefill_fn(sb, cache_len)(
                        self.params, cache,
                        jnp.full((1, sb), self.tokenizer.pad_id, jnp.int32),
                        jnp.asarray([0], jnp.int32),
                        jnp.asarray([1], jnp.int32),
                        jax.random.PRNGKey(0), jnp.float32(0.0))
                    warm_caches[cache_len] = cache
                    jax.block_until_ready(first)
                    beat()
        # Free the pinned rung caches before the chunked-long block
        # allocates its own max-rung cache (transient-HBM headroom).
        warm_caches.clear()
        if self._buckets and self._buckets[-1] < self._max_seq:
            # Chunked-long-prefill programs: the largest-bucket chunk at
            # every window rung a max-length prompt walks through, plus
            # the zero-cache init and that length's decode loop.
            cb = self._buckets[-1]
            limit = min((self._max_seq // cb) * cb, self._max_seq - cap)
            cache_len = self._pick_cache_len(
                max(limit + cap, -(-limit // cb) * cb))
            cache = self._init_cache_fn(cache_len)()
            for window in sorted({
                    min(self._suffix_window(s + cb), cache_len)
                    for s in range(0, limit, cb)}):
                first, cache = self._suffix_prefill_fn(cb, window)(
                    self.params, cache,
                    jnp.full((1, cb), self.tokenizer.pad_id, jnp.int32),
                    jnp.asarray([0], jnp.int32), jnp.asarray([1], jnp.int32),
                    jax.random.PRNGKey(0), jnp.float32(0.0))
                jax.block_until_ready(first)
                beat()
            if cache_len not in self._decode_fns:
                out, _, _ = self._decode_loop(cache_len)(
                    self.params, cache, jnp.asarray([0], np.int32),
                    jnp.asarray([1], np.int32), jax.random.PRNGKey(0),
                    jnp.float32(0.0), jnp.int32(1))
                jax.block_until_ready(out)
            else:
                jax.block_until_ready(first)
        # Compile time lands in the warmup call's phases; reset so /stats
        # attribution reflects steady-state serving only.
        self.phases = PhaseTimer()
