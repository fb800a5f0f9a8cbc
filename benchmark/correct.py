"""``correct``: the serving path's logits against the plain reference.

Decided before the timed window and from nothing the traffic did.  Per
tier, a fixed sample drawn whole from the seed (one sequence of ids per
length; the lengths and ``n_decode`` are the configuration's
``correct.sample``, or ``LENGTHS`` and ``N_DECODE`` where it states none)
goes

  * through the program's own serving functions, called as the engine
    calls them.  All but the last ``n_decode`` ids are prefilled chunk by
    chunk through ``chunk_prefill_paged`` — ``start`` the chunk's first
    position, ``true_len`` the whole prompt, the slot's full table row,
    ``window`` the smallest rung of the engine's chunk-window ladder that
    holds the chunk's end — so the sample runs 3 to 5 chunks a sequence,
    continuation chunks (``start > 0``, earlier blocks gathered) on every
    rung of the ladder.  The last ``n_decode`` ids go one at a time,
    teacher-forced, through ``decode_step_paged`` as one fixed batch on
    tables cut to the decode window rung, as the dense tick cuts them.
    The ENGINE'S weights, attention choice, block size, chunk size and
    mesh; a small paged pool of the engine's geometry made for the check
    (the engine's allocator and pool are never touched); and
  * through ``reference/<family>.py`` (found by the tier's ``family``):
    float32, full forward, its own weights from the seed.

The statistic is the relative Frobenius error of the logits over all kept
positions of all sequences together; beside it, the engine's weights and
pool may hold no array narrower than the configuration states
(``narrow_leaves``).  The engine's compiled programs
sample on the device and return tokens only, so the two serving functions
are jitted here (compiled in a cell's first run, from the cache after).
What this does NOT run: the engine's tick wrapper (sampling, table
upload), its scheduler and its allocator.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

import manifest as mf

# Where a configuration states no ``correct.sample``.  Prompts of
# 600..1272 ids at a chunk of 256: 3, 4, 5 and 5 chunks, ends on the 256,
# the 1024 and the full-span window rungs.
LENGTHS, N_DECODE = (616, 808, 1064, 1288), 16
TRASH_BLOCK = 0


def sample_sizes(config: Dict[str, Any]) -> Tuple[Tuple[int, ...], int]:
    """(lengths, n_decode) of a configuration's sample: its
    ``correct.sample``, so that a cell is judged at the cache lengths its
    traffic reaches.  The same for every seed."""
    stated = config.get("correct", {}).get("sample", {})
    unknown = sorted(set(stated) - {"lengths", "n_decode"})
    if unknown:
        raise mf.ManifestError(f"correct.sample has {unknown}; it may give "
                               f"'lengths' and 'n_decode'")
    lengths = tuple(int(n) for n in stated.get("lengths", LENGTHS))
    n_decode = int(stated.get("n_decode", N_DECODE))
    if not lengths or n_decode < 1 or min(lengths) <= n_decode:
        raise mf.ManifestError(f"correct.sample: lengths {lengths} have to "
                               f"be longer than n_decode {n_decode} >= 1")
    return lengths, n_decode


# The sweep's controls (``tools/sweep_correct.py``): the program's own
# paths one precision below the stated one, each switched on alone.
CONTROLS = ("int8_weights", "int8_kv")


def controls_of(config: Dict[str, Any]) -> Tuple[str, ...]:
    """Which of the sweep's controls a configuration runs: its
    ``correct.controls``, all of them where it states none.  A family
    whose program has neither path runs none, and then has to say in
    ``correct.control`` what else shows that a lower precision would be
    caught (``run.py`` judges ``narrow_leaves`` for every
    configuration)."""
    stated = config.get("correct", {})
    names = tuple(stated.get("controls", CONTROLS))
    unknown = sorted(set(names) - set(CONTROLS))
    if unknown:
        raise mf.ManifestError(f"correct.controls names {unknown}; the "
                               f"sweep has {list(CONTROLS)}")
    if not names and not str(stated.get("control", "")).strip():
        raise mf.ManifestError(
            "correct.controls is empty: correct.control has to say what "
            "else shows that a lower precision would be caught")
    return names


def draw_sample(seed: int, vocab_size: int,
                lengths: Sequence[int] = LENGTHS) -> List[np.ndarray]:
    """The fixed sample: a pure function of seed, vocabulary and
    ``lengths``; every seed draws the same lengths.  Ids avoid 256..258
    (PAD, BOS, EOS of the program's byte scheme)."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 0xC0221EC7])
    out = []
    for n in lengths:
        ids = rng.integers(0, vocab_size - 3, size=int(n))
        out.append(np.where(ids >= 256, ids + 3, ids).astype(np.int32))
    return out


def kept_positions(seqs: List[np.ndarray], n_decode: int = N_DECODE
                   ) -> np.ndarray:
    """[sequences, n_decode + 1]: the last prompt position and every
    decode step's position (logits at p predict the id at p + 1)."""
    return np.stack([np.arange(len(s) - n_decode - 1, len(s))
                     for s in seqs]).astype(np.int32)


def reference_logits(family: str, model: Dict[str, Any], seed: int,
                     seqs: List[np.ndarray], device=None,
                     n_decode: int = N_DECODE) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    ref = mf.load_module("reference", family)
    sharding = (jax.sharding.SingleDeviceSharding(device)
                if device is not None else None)
    weights = ref.init_weights(model, int(seed) % (2 ** 31), sharding)
    width = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    put = (lambda a: jax.device_put(a, sharding)) if sharding else jnp.asarray
    out = ref.logits(model, weights, put(tokens),
                     put(kept_positions(seqs, n_decode)))
    return np.asarray(out, np.float32)


def chunk_windows(span: int, block_size: int) -> List[int]:
    """The engine's chunk-window ladder (``ContinuousBatchingEngine``:
    block-aligned rungs at 256 and 1024 positions, then the slot's whole
    span), written out again for the sweep, which builds no engine."""
    return sorted({min(span, -(-c // block_size) * block_size)
                   for c in (256, 1024) if c < span} | {span})


_PROGRAMS: Dict[Any, Any] = {}


def _programs(cfg, block_size: int, kv_quantize: str, mesh, ragged: bool):
    """(prefill(window), decode) jitted once per setting and kept, so a
    sweep over seeds traces each program once."""
    key = (cfg, block_size, kv_quantize, id(mesh), ragged)
    if key in _PROGRAMS:
        return _PROGRAMS[key]
    import jax
    import jax.numpy as jnp

    from distributed_llm_tpu.engine.paged_kv import (chunk_prefill_paged,
                                                     decode_step_paged)
    from distributed_llm_tpu.models import transformer
    from distributed_llm_tpu.parallel.tp_attention import (
        tp_paged_decode_attn, tp_ragged_decode_attn)

    quantized = kv_quantize == "int8"
    pool_sh = repl = None
    if mesh is not None:
        from distributed_llm_tpu.parallel.sharding import (kv_pool_shardings,
                                                           replicated)
        pool_sh = kv_pool_shardings(mesh, quantized=quantized)
        repl = replicated(mesh)
    donate = (1,) if jax.default_backend() != "cpu" else ()
    prefills: Dict[int, Any] = {}

    def prefill(window: int):
        if window not in prefills:
            def run(params, pool, tokens, start, true_len, table):
                # The body of the engine's ``_chunk_prefill_fn`` up to its
                # sampler: the logits are returned in its place.
                hidden, pool = chunk_prefill_paged(
                    cfg, params, tokens, start, true_len, pool, table,
                    window)
                last = hidden[0, true_len[0] - start[0] - 1]
                return transformer.logits_from_hidden(params, last), pool
            kw = ({"out_shardings": (repl, pool_sh)}
                  if mesh is not None else {})
            prefills[window] = jax.jit(run, donate_argnums=donate, **kw)
        return prefills[window]

    def decode_run(params, pool, tables, ids, pos0):
        # As the engine's tick: the dense path's window is the width of
        # the tables it is given.
        attn = (tp_ragged_decode_attn(mesh, cfg, quantized=quantized)
                if ragged else
                tp_paged_decode_attn(mesh, cfg, tables.shape[1] * block_size,
                                     quantized=quantized))

        def step(pool, x):
            cur, t = x
            logits, pool = decode_step_paged(cfg, params, cur, pos0 + t,
                                             pool, tables, attn=attn,
                                             ragged=ragged)
            return pool, logits
        _, logits = jax.lax.scan(
            step, pool, (ids, jnp.arange(ids.shape[0], dtype=jnp.int32)))
        return logits                                     # [T, B, V]

    kw = {"out_shardings": repl} if mesh is not None else {}
    _PROGRAMS[key] = (prefill, jax.jit(decode_run, **kw), pool_sh)
    return _PROGRAMS[key]


def system_logits(cfg, params, seqs: List[np.ndarray], *, block_size: int,
                  chunk: int, span: int, decode_rungs: Sequence[int],
                  windows: Sequence[int] = (), kv_quantize: str = "none",
                  mesh=None, ragged: bool = False, device=None,
                  n_decode: int = N_DECODE) -> np.ndarray:
    """[sequences, n_decode + 1, V] float32 through the program's paged
    prefill and decode.  ``cfg`` is the engine's ModelConfig (attention
    choice included), ``params`` its weights as served, ``span`` the
    positions a slot's table row covers, ``windows`` the engine's
    chunk-window ladder, ``decode_rungs`` its decode window rungs (the
    tier's prefill buckets)."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_tpu.engine.paged_kv import PagedConfig, init_pool

    b = len(seqs)
    windows = sorted(windows) or chunk_windows(span, block_size)
    prefill, decode, pool_sh = _programs(cfg, block_size, kv_quantize, mesh,
                                         ragged)
    # Each sequence owns the blocks its ids need, handed out in order from
    # block 1; the rest of its row is the trash block, as in the engine.
    row_width = -(-span // block_size)
    need = [-(-len(s) // block_size) for s in seqs]
    pcfg = PagedConfig(block_size=block_size, max_slots=b, max_seq_len=span,
                       pool_blocks=sum(need))
    pool = init_pool(cfg, pcfg, kv_quantize)
    if mesh is not None:
        pool = jax.device_put(pool, pool_sh)
    elif device is not None:
        pool = jax.device_put(pool,
                              jax.sharding.SingleDeviceSharding(device))
    tables = np.full((b, row_width), TRASH_BLOCK, np.int32)
    first_block = 1
    for i, n in enumerate(need):
        tables[i, :n] = np.arange(first_block, first_block + n)
        first_block += n

    first = []
    for i, s in enumerate(seqs):
        total = len(s) - n_decode
        if total <= 2 * chunk:
            raise ValueError(f"sample prompt of {total} ids spans fewer "
                             f"than 3 chunks of {chunk}")
        row = jnp.asarray(tables[i])
        for start in range(0, total, chunk):
            end = start + chunk
            k = min(end, total) - start
            tokens = np.zeros((1, chunk), np.int32)
            tokens[0, :k] = s[start:start + k]
            window = next(w for w in windows if w >= end)
            lg, pool = prefill(window)(
                params, pool, jnp.asarray(tokens),
                jnp.asarray([start], np.int32),
                jnp.asarray([total], np.int32), row)
        first.append(np.asarray(lg, np.float32))
    ids = np.stack([s[len(s) - n_decode:] for s in seqs], axis=1)  # [T, B]
    pos0 = np.asarray([len(s) - n_decode for s in seqs], np.int32)
    longest = max(len(s) for s in seqs)
    rung = next((r for r in sorted(decode_rungs) if r >= longest), None)
    if rung is None:
        raise ValueError(f"no decode window rung of {sorted(decode_rungs)} "
                         f"holds the sample's {longest} positions")
    cut = tables if ragged else tables[:, :rung // block_size]
    steps = np.asarray(decode(params, pool, jnp.asarray(cut),
                              jnp.asarray(ids), jnp.asarray(pos0)),
                       np.float32)                        # [T, B, V]
    return np.concatenate([np.stack(first)[:, None],
                           np.swapaxes(steps, 0, 1)], axis=1)


def rel_frobenius(got: np.ndarray, want: np.ndarray) -> float:
    got = got.astype(np.float64)
    want = want.astype(np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def tier_settings(tier, cfg) -> Dict[str, Any]:
    """What ``system_logits`` takes from a TierConfig and its model."""
    bs = tier.kv_block_size
    return {"block_size": bs,
            "chunk": int(tier.prefill_chunk_tokens or 256),
            "span": -(-cfg.max_seq_len // bs) * bs,
            "decode_rungs": tuple(tier.prefill_buckets)}


def engine_statistic(engine, family: str, model: Dict[str, Any], seed: int,
                     ref_device=None,
                     sample: Tuple[Sequence[int], int] = (LENGTHS, N_DECODE)
                     ) -> Dict[str, Any]:
    """The statistic for one tier as it is served; ``sample`` is the
    configuration's ``sample_sizes``."""
    lengths, n_decode = sample
    seqs = draw_sample(seed, engine.cfg.vocab_size, lengths)
    dev = engine.devices[0] if (engine.mesh is None and engine.devices) \
        else None
    got = system_logits(
        engine.cfg, engine.params, seqs,
        **tier_settings(engine.tier, engine.cfg),
        windows=getattr(engine, "_chunk_windows", ()),
        kv_quantize=engine.tier.kv_quantize, mesh=engine.mesh,
        ragged=engine.ragged, device=dev, n_decode=n_decode)
    want = reference_logits(family, model, seed, seqs,
                            device=ref_device or dev, n_decode=n_decode)
    return {"rel_err": rel_frobenius(got, want),
            "finite": bool(np.isfinite(got).all()),
            "positions": int(got.shape[0] * got.shape[1]),
            "narrow": narrow_leaves({"params": engine.params,
                                     "pool": engine.pool})}


def narrow_leaves(tree, least_bits: int = 16) -> List[str]:
    """Paths of the arrays in ``tree`` stored in a type of fewer than
    ``least_bits`` bits (booleans aside): int8, fp8, int4.  The logits
    statistic reads an int8 K/V pool only 1.3-1.4 times the stated
    precision, so storage narrower than the configuration states is
    caught here, exactly, and not by the limit."""
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and dtype != np.bool_ \
                and np.dtype(dtype).itemsize * 8 < least_bits:
            out.append(f"{jax.tree_util.keystr(path)}:{np.dtype(dtype).name}")
    return out


def int8_weights(params, tier, cfg, mesh=None):
    """The program's own int8 weight path switched on
    (``quantize="int8"``), for the control."""
    from distributed_llm_tpu.ops.quant import maybe_quantize
    return maybe_quantize(params, dataclasses.replace(tier, quantize="int8"),
                          cfg, mesh=mesh)
