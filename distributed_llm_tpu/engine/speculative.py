"""Speculative decoding: the nano tier drafts, the orin tier verifies.

A natural extension of the reference's two-tier topology (SURVEY.md §1):
instead of routing a query to EITHER the weak or the strong model, the
weak model proposes ``gamma`` greedy tokens and the strong model checks
them in ONE chunked forward — decode throughput approaches
draft-speed × acceptance-rate while outputs remain token-identical to
greedy decoding with the strong model alone (the classic speculative
guarantee, trivially exact in the greedy case: accept while argmaxes
agree, then take the target's token).

TPU shape discipline: one jitted ``spec_step`` per engine — the γ-step
draft loop (lax.scan), the target's γ+1-position verify forward, and the
acceptance logic all run on device with static shapes; the host loop only
counts accepted tokens.  Verification uses a chunked decode
(multi-position query against the KV cache with a per-query position
mask), which is also what long-prefill chunking needs.

Both caches stay consistent without rollback machinery: rejected
positions' K/V are simply overwritten by later write-before-attend steps,
exactly like the right-padded prefill garbage (engine/inference.py).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import TierConfig
from ..models import transformer
from ..ops import quant
from .inference import (GenerationResult, prepare_prompt, trim_at_eos,
                        upgrade_attention_impl)
from .tokenizer import get_tokenizer


def decode_chunk(cfg, params, tokens: jax.Array, start_pos: jax.Array,
                 kv: transformer.KVCache
                 ) -> Tuple[jax.Array, transformer.KVCache]:
    """Multi-token decode: process ``tokens`` [B, G] at positions
    [start_pos, start_pos+G) against the cache.  Returns (logits [B, G, V]
    float32, updated cache).  Queries attend strictly to their own prefix
    (cache cols ≤ their position; write-before-attend)."""
    b, g = tokens.shape
    d = cfg.head_dim
    pos = start_pos[:, None] + jnp.arange(g)[None]            # [B, G]
    x = quant.embed_rows(params["embed"], tokens)             # [B, G, H]
    sin, cos = transformer.rope_sincos(pos, d, cfg.rope_theta)

    def layer(x, scanned):
        lp, k_cache, v_cache = scanned                        # [B, S, NKV, D]
        h_in = transformer.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = transformer.project_qkv(cfg, lp, h_in)
        q = transformer.apply_rope(q, sin, cos)
        k = transformer.apply_rope(k, sin, cos)

        def write(cache, new):                                # scatter G rows
            def one(c, n, p):
                return jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
            return jax.vmap(one)(cache, new, start_pos)
        k_cache = write(k_cache, k)
        v_cache = write(v_cache, v)

        # Per-query ragged attention (query g attends cols <= pos[b, g])
        # through the chunk op, as a prefix-reuse suffix prefill.
        from ..ops import attention as attention_ops
        attn = attention_ops.chunk(q, k_cache, v_cache, pos)

        x = x + quant.matmul(attn.reshape(b, g, cfg.num_heads * d), lp["wo"])
        x = x + transformer._swiglu(
            transformer.rms_norm(x, lp["ln2"], cfg.norm_eps),
            lp["w_gate"], lp["w_up"], lp["w_down"])
        return x, (k_cache, v_cache)

    x, (k_new, v_new) = jax.lax.scan(
        layer, x, (params["layers"], kv["k"], kv["v"]))
    hidden = transformer.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return transformer.logits_from_hidden(params, hidden), \
        {"k": k_new, "v": v_new}


class SpeculativeEngine:
    """Greedy speculative generation over a (target, draft) tier pair.

    Same ``generate()/warmup()`` surface as InferenceEngine; the result's
    text is token-identical to greedy decoding with the target alone.
    """

    def __init__(self, target: TierConfig, draft: TierConfig,
                 gamma: int = 4, seed: int = 0,
                 target_params: Optional[Dict[str, Any]] = None,
                 draft_params: Optional[Dict[str, Any]] = None):
        if target.model().vocab_size != draft.model().vocab_size:
            raise ValueError("speculative decoding needs a shared vocab")
        if target.temperature and target.temperature > 0:
            raise ValueError(
                "speculative engine is greedy-only; tier temperature "
                f"{target.temperature} would be silently ignored")
        self.target = target
        self.draft = draft
        self.cfg_t = upgrade_attention_impl(target.model(), None)
        self.cfg_d = upgrade_attention_impl(draft.model(), None)
        # InferenceEngine surface parity (the class contract): probes and
        # telemetry address any engine's .tier/.cfg — for a speculative
        # pair that means the TARGET (the model whose quality/context the
        # tier serves).
        self.tier = target
        self.cfg = self.cfg_t
        self.gamma = gamma
        self.tokenizer = get_tokenizer(self.cfg_t)
        self._max_seq = min(self.cfg_t.max_seq_len, self.cfg_d.max_seq_len)
        # Bucketed cache lengths (same coarse ladder as InferenceEngine):
        # the verify chunk and every draft step attend over the ALLOCATED
        # span, so sizing both caches to the conversation instead of
        # max_seq cuts verify compute and HBM traffic alike for short
        # chats (a flat _max_seq allocation would also make the
        # roofline charge severalfold too high).
        self._cache_lens = sorted(
            {c for c in (256, 1024) if c < self._max_seq} | {self._max_seq})

        def init(cfg, tier, params, salt):
            if params is not None:
                return params
            if tier.checkpoint_path:
                # Published tier weights win over random init (same rule
                # as InferenceEngine/ContinuousBatchingEngine) — drafting
                # against a trained target with a random draft would pin
                # acceptance near zero.
                from ..utils.checkpoint import load_params_for_tier
                return load_params_for_tier(tier.checkpoint_path, cfg)
            return jax.jit(lambda: transformer.init_params(cfg, seed + salt))()
        self.params_t = init(self.cfg_t, target, target_params, 0)
        self.params_d = init(self.cfg_d, draft, draft_params, 1)
        # The target tier's quantize mode applies to both models (the draft
        # gains the most: it runs gamma small decode steps per target step).
        self.params_t = quant.maybe_quantize(self.params_t, target, self.cfg_t)
        self.params_d = quant.maybe_quantize(self.params_d, target, self.cfg_d)

        self._prefill_fns: Dict[int, Any] = {}
        self._spec_fn = None
        self.accept_history: list = []
        # Phase wall-time + roofline work (GET /stats, bench MFU/HBM —
        # same surface as the other engines).  Draft and target work both
        # accumulate; the verify chunk is accounted as γ+1 decode queries
        # over the full cache span.
        from ..utils import roofline
        from ..utils.telemetry import PhaseTimer
        self.phases = PhaseTimer()
        self._wbytes_t = roofline.weight_bytes(self.cfg_t, target.quantize)
        self._wbytes_d = roofline.weight_bytes(self.cfg_d, target.quantize)

    @property
    def params(self):
        """InferenceEngine surface parity: the TARGET's weights — spec
        decoding is greedy-exact, so served answer quality IS the
        target model's (training/evaluate.py scores eng.cfg/eng.params
        for any engine)."""
        return self.params_t

    # -- compiled stages ---------------------------------------------------

    def _prefill_fn(self, bucket: int, cache_len: int):
        """Prefill BOTH models on the prompt; target picks the first token."""
        key = (bucket, cache_len)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        cfg_t, cfg_d = self.cfg_t, self.cfg_d

        def run(params_t, params_d, tokens, true_len):
            b, s = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

            def seed_cache(cfg, params):
                hidden, (k_all, v_all) = transformer.prefill(
                    cfg, params, tokens, positions)
                cache = transformer.init_kv_cache(cfg, b, cache_len)
                cache = {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k_all, (0, 0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v_all, (0, 0, 0, 0, 0)),
                }
                return hidden, cache

            hidden_t, cache_t = seed_cache(cfg_t, params_t)
            _, cache_d = seed_cache(cfg_d, params_d)
            last = hidden_t[jnp.arange(b), true_len - 1]
            first = jnp.argmax(
                transformer.logits_from_hidden(params_t, last), -1)
            return first, cache_t, cache_d

        fn = jax.jit(run)
        self._prefill_fns[key] = fn
        return fn

    def _round_body(self):
        """The traced speculative round shared by BOTH compiled paths
        (the streaming per-round jit and the fused whole-generation
        loop), so they cannot diverge."""
        cfg_t, cfg_d, gamma = self.cfg_t, self.cfg_d, self.gamma

        def run(params_t, params_d, cache_t, cache_d, cur, pos):
            # cur [B]: last accepted token; pos [B]: its position.
            def draft_one(carry, _):
                cache, tok, p = carry
                logits, cache = transformer.decode_step(
                    cfg_d, params_d, tok, p, cache)
                nxt = jnp.argmax(logits, -1)
                return (cache, nxt, p + 1), nxt

            # γ+1 steps, not γ: the extra step writes drafted[γ-1]'s K/V
            # into the draft cache at pos+γ.  Without it a fully-accepted
            # round advances past that slot and leaves a permanent zero
            # hole the overwrite-later invariant can never repair.
            (cache_d, _, _), drafted = jax.lax.scan(
                draft_one, (cache_d, cur, pos), None, length=gamma + 1)
            drafted = jnp.swapaxes(drafted, 0, 1)[:, :gamma]  # [B, γ]

            # Target verifies [cur, drafted[:-1]] + scores the bonus slot:
            # chunk = γ+1 tokens starting at pos.
            chunk = jnp.concatenate([cur[:, None], drafted], axis=1)
            logits, cache_t = decode_chunk(cfg_t, params_t, chunk, pos,
                                           cache_t)
            target_pick = jnp.argmax(logits, -1)              # [B, γ+1]

            # Greedy acceptance: drafted[i] survives iff it equals the
            # target's pick at slot i AND all earlier slots survived.
            agree = drafted == target_pick[:, :gamma]         # [B, γ]
            n_acc = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1),
                            axis=1)                           # [B] in [0, γ]
            # Output tokens: accepted draft prefix, then the target's pick
            # at the first disagreement (or the bonus token if all agreed).
            idx = jnp.arange(gamma + 1)[None]
            out = jnp.where(idx < n_acc[:, None],
                            jnp.pad(drafted, ((0, 0), (0, 1))),
                            jnp.take_along_axis(target_pick, jnp.minimum(
                                idx, n_acc[:, None]), axis=1))
            # Everything after slot n_acc is unused this round.
            new_cur = jnp.take_along_axis(out, n_acc[:, None], axis=1)[:, 0]
            new_pos = pos + n_acc + 1
            return out, n_acc, new_cur, new_pos, cache_t, cache_d

        return run

    def _spec_step(self):
        """One speculative round, fully on device:
        draft γ tokens → target verifies γ+1 positions → accept prefix.
        (The streaming path's unit of work — one host round trip per
        round, so accepted tokens can yield as text deltas.)"""
        if self._spec_fn is not None:
            return self._spec_fn
        self._spec_fn = jax.jit(self._round_body())
        return self._spec_fn

    def _spec_loop(self, cache_len: int):
        """The WHOLE speculative generation as one device call: a
        ``lax.while_loop`` over rounds with emit/EOS/budget logic on
        device.  The plain engine's decode is a single compiled loop —
        paying a host↔device round trip per γ accepted tokens instead
        was pure overhead (dozens of extra round trips per reply), and
        is the non-streaming path's whole disadvantage.
        ``token_budget`` is a runtime operand; compiled once per
        cache_len like the plain decode loop."""
        key = ("loop", cache_len)
        if key in self._prefill_fns:
            return self._prefill_fns[key]
        gamma = self.gamma
        eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id
        max_new = self.target.max_new_tokens
        round_fn = self._round_body()

        def run(params_t, params_d, cache_t, cache_d, first, prompt_len,
                token_budget):
            # out has γ+1 slack: a round writes its full window and only
            # the kept prefix advances n_out (later rounds overwrite).
            out = jnp.full((1, max_new + gamma + 1), pad, jnp.int32)
            out = out.at[0, 0].set(first[0])
            n_out = jnp.int32(1)
            done = (first[0] == eos) | (first[0] == pad)
            pos = prompt_len
            state = (out, n_out, first, pos, cache_t, cache_d, done,
                     jnp.int32(0), jnp.int32(0))

            def cond(s):
                _, n_out, _, pos, _, _, done, _, _ = s
                return (~done & (n_out < token_budget)
                        & (pos[0] + gamma + 1 < cache_len))

            def body(s):
                (out, n_out, cur, pos, cache_t, cache_d, done, rounds,
                 accepted) = s
                o, n_acc, cur, pos, cache_t, cache_d = round_fn(
                    params_t, params_d, cache_t, cache_d, cur, pos)
                emitted = o[0]                               # [γ+1]
                take = jnp.minimum(n_acc[0] + 1, token_budget - n_out)
                idx = jnp.arange(gamma + 1)
                stop = (emitted == eos) | (emitted == pad)
                in_take = idx < take
                stop_any = jnp.any(stop & in_take)
                stop_idx = jnp.min(jnp.where(stop & in_take, idx,
                                             gamma + 1))
                n_keep = jnp.minimum(take, stop_idx + 1)
                out = jax.lax.dynamic_update_slice(out, o, (0, n_out))
                n_out = n_out + n_keep
                done = stop_any | (n_out >= token_budget)
                return (out, n_out, cur, pos, cache_t, cache_d, done,
                        rounds + 1, accepted + n_acc[0])

            (out, n_out, _, _, _, _, _, rounds, accepted) = \
                jax.lax.while_loop(cond, body, state)
            return out, n_out, rounds, accepted

        fn = jax.jit(run)
        self._prefill_fns[key] = fn
        return fn

    # -- host orchestration ------------------------------------------------

    def _prepare_and_prefill(self, history, max_new_tokens):
        """Shared front half of generate()/generate_stream(): tokenize,
        clamp the budget, size both caches to the conversation (prompt +
        decode budget + one speculative round of headroom: a flat
        max_seq allocation makes every draft step and verify compute
        over the full span), prefill both models, account the
        roofline work.  Returns (first [1] device array, cache_t,
        cache_d, cache_len, n, budget, ttft_ms, t0)."""
        from ..utils import roofline
        t0 = time.perf_counter()
        ids, bucket = prepare_prompt(
            self.tokenizer, history, self.target.prefill_buckets,
            self._max_seq, self.target.max_new_tokens)
        n = len(ids)
        budget = self.target.max_new_tokens
        if max_new_tokens and max_new_tokens > 0:
            budget = min(budget, max_new_tokens)
        needed = max(bucket, n + budget + self.gamma + 2)
        cache_len = next(c for c in self._cache_lens
                         if c >= min(needed, self._max_seq))

        tokens = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
        tokens[0, :n] = ids
        with self.phases.phase("prefill"):
            first, cache_t, cache_d = self._prefill_fn(bucket, cache_len)(
                self.params_t, self.params_d, jnp.asarray(tokens),
                jnp.asarray([n], np.int32))
            first = jax.block_until_ready(first)
        self.phases.add_work("prefill", **roofline.prefill_work(
            self.cfg_t, bucket, 0, wbytes=self._wbytes_t))
        self.phases.add_work("prefill", **roofline.prefill_work(
            self.cfg_d, bucket, 0, wbytes=self._wbytes_d))
        ttft_ms = (time.perf_counter() - t0) * 1000.0
        return first, cache_t, cache_d, cache_len, n, budget, ttft_ms, t0

    def generate(self, history, max_new_tokens: Optional[int] = None,
                 temperature: Optional[float] = None) -> GenerationResult:
        """Non-streaming generation: prefill + ONE fused device call for
        the whole speculative loop (_spec_loop) — same tokens as the
        streaming path (both run _round_body), without its per-round
        host round trips."""
        if temperature:
            raise NotImplementedError(
                "speculative engine is greedy-only (reference default, "
                "src/devices/nano_api.py:21)")
        from ..utils import roofline
        eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id
        (first, cache_t, cache_d, cache_len, n, budget, ttft_ms,
         t0) = self._prepare_and_prefill(history, max_new_tokens)

        with self.phases.phase("decode"):
            out, n_out, rounds, accepted = self._spec_loop(cache_len)(
                self.params_t, self.params_d, cache_t, cache_d, first,
                jnp.asarray([n], np.int32), jnp.int32(budget))
            out = np.asarray(jax.block_until_ready(out))[0]
        rounds_i = int(rounds)
        accepted_i = int(accepted)
        self.phases.add_work("decode", **roofline.decode_work(
            self.cfg_d, (self.gamma + 1) * rounds_i, cache_len,
            wbytes=self._wbytes_d))
        self.phases.add_work("decode", **roofline.decode_work(
            self.cfg_t, rounds_i, cache_len, batch=self.gamma + 1,
            wbytes=self._wbytes_t, kv_batch=1))
        if rounds_i:
            # Preserve acceptance_rate's mean exactly (per-round detail
            # lives only on the streaming path).
            self.accept_history.extend([accepted_i / rounds_i] * rounds_i)

        gen_ids = trim_at_eos(out[:int(n_out)].tolist()[:budget], eos, pad)
        return GenerationResult(
            text=self.tokenizer.decode(gen_ids), token_ids=gen_ids,
            prompt_tokens=n, gen_tokens=len(gen_ids), ttft_ms=ttft_ms,
            total_ms=(time.perf_counter() - t0) * 1000.0)

    def generate_stream(self, history, max_new_tokens: Optional[int] = None,
                        temperature: Optional[float] = None):
        """Token streaming off the speculative loop: each accepted round's
        tokens yield as text deltas (same StreamHandle surface as the other
        engines; generate() is implemented on top, so the two paths cannot
        diverge)."""
        if temperature:
            raise NotImplementedError(
                "speculative engine is greedy-only (reference default, "
                "src/devices/nano_api.py:21)")
        from .batching import StreamHandle, _Request
        from .tokenizer import StreamDecoder

        req = _Request(history=history, max_new_tokens=max_new_tokens,
                       temperature=temperature)

        def deltas():
            decoder = StreamDecoder(self.tokenizer)
            eos, pad = self.tokenizer.eos_id, self.tokenizer.pad_id
            try:
                from ..utils import roofline
                (first_arr, cache_t, cache_d, cache_len, n, budget,
                 ttft_ms, t0) = self._prepare_and_prefill(history,
                                                          max_new_tokens)
                first = int(first_arr[0])

                out_tokens = [first]
                if first not in (eos, pad):
                    text = decoder.feed(first)
                    if text:
                        yield text
                cur = jnp.asarray([first], jnp.int32)
                pos = jnp.asarray([n], jnp.int32)
                step = self._spec_step()
                while (len(out_tokens) < budget
                       and out_tokens[-1] not in (eos, pad)
                       and int(pos[0]) + self.gamma + 1 < cache_len):
                    with self.phases.phase("decode"):
                        out, n_acc, cur, pos, cache_t, cache_d = step(
                            self.params_t, self.params_d, cache_t, cache_d,
                            cur, pos)
                        n_acc_i = int(n_acc[0])
                    # Draft: γ+1 sequential full-span decode steps.  Target
                    # verify: ONE chunked forward — γ+1 query tokens share
                    # a single read of the target cache (kv_batch=1), over
                    # the ALLOCATED (bucketed) span, not max_seq.
                    self.phases.add_work("decode", **roofline.decode_work(
                        self.cfg_d, self.gamma + 1, cache_len,
                        wbytes=self._wbytes_d))
                    self.phases.add_work("decode", **roofline.decode_work(
                        self.cfg_t, 1, cache_len, batch=self.gamma + 1,
                        wbytes=self._wbytes_t, kv_batch=1))
                    self.accept_history.append(n_acc_i)
                    for tok in np.asarray(out)[0][:n_acc_i + 1].tolist():
                        tok = int(tok)
                        out_tokens.append(tok)
                        # PAD ends the stream like EOS (trim_at_eos trims
                        # the result there, batching.py does the same).
                        if tok in (eos, pad) or len(out_tokens) > budget:
                            break
                        text = decoder.feed(tok)
                        if text:
                            yield text
                tail = decoder.flush()
                if tail:
                    yield tail

                gen_ids = trim_at_eos(out_tokens[:budget], eos, pad)
                req.result = GenerationResult(
                    text=self.tokenizer.decode(gen_ids), token_ids=gen_ids,
                    prompt_tokens=n, gen_tokens=len(gen_ids),
                    ttft_ms=ttft_ms,
                    total_ms=(time.perf_counter() - t0) * 1000.0)
            except BaseException as exc:
                req.error = exc
                raise
            finally:
                req.done.set()

        return StreamHandle(deltas(), req)

    @property
    def acceptance_rate(self) -> float:
        """Mean accepted draft tokens per round / γ."""
        if not self.accept_history:
            return 0.0
        return float(np.mean(self.accept_history)) / self.gamma

    def warmup(self, beat=None) -> None:
        # Compile BOTH compiled paths — the fused loop (generate) and the
        # per-round step (generate_stream) are separate jits, and real
        # traffic prefers streaming (serving/tiers.py process_stream) —
        # at EVERY cache rung a conversation can grow into, so no request
        # ever pays a mid-serve trace of the speculative graph.  ``beat``
        # fires per compiled program (a caller's watchdog liveness).
        beat = beat or (lambda: None)
        self.generate("warmup", max_new_tokens=self.gamma + 2)
        beat()
        for _ in self.generate_stream("warmup", max_new_tokens=self.gamma):
            pass
        beat()
        # Every (bucket, cache rung) pair _prepare_and_prefill can pick —
        # same two-rung-per-bucket coverage as InferenceEngine.warmup —
        # plus, once per rung, both speculative graphs (the fused loop and
        # the streaming round retrace per cache shape).  Nothing here
        # donates, so one prefill's outputs serve both graph warms.
        def pick(needed):
            return next(c for c in self._cache_lens
                        if c >= min(needed, self._max_seq))
        cap = self.target.max_new_tokens + self.gamma + 2
        buckets = sorted(set(b for b in self.target.prefill_buckets
                             if b <= self._max_seq))
        done_rungs = set()
        one = jnp.asarray([1], np.int32)
        for bucket in buckets:
            tokens = jnp.full((1, bucket), self.tokenizer.pad_id, jnp.int32)
            for cache_len in {pick(bucket), pick(bucket + cap)}:
                if cache_len < bucket:       # unreachable by serving
                    continue
                first, cache_t, cache_d = self._prefill_fn(
                    bucket, cache_len)(self.params_t, self.params_d,
                                       tokens, one)
                if cache_len in done_rungs:
                    jax.block_until_ready(first)
                    beat()
                    continue
                done_rungs.add(cache_len)
                out, *_ = self._spec_loop(cache_len)(
                    self.params_t, self.params_d, cache_t, cache_d, first,
                    one, jnp.int32(1))
                jax.block_until_ready(out)
                out, *_ = self._spec_step()(
                    self.params_t, self.params_d, cache_t, cache_d,
                    first, one)
                jax.block_until_ready(out)
                beat()
        self.accept_history.clear()   # don't skew acceptance_rate
