"""Property-based invariants for the host-side data structures.

Hypothesis drives random operation sequences against the pieces whose
bugs would be silent corruption rather than crashes: the paged-pool block
allocator (never lose or double-lend a block), the routing QueryCache
(capacity/TTL bookkeeping), and the prefix-cache matching policy (a
reclaimed prefix must actually be a prefix)."""

import jax  # noqa: F401  (conftest pins CPU before anything imports jax)

from hypothesis import given, settings, strategies as st

from distributed_llm_tpu.engine.paged_kv import TRASH_BLOCK, BlockAllocator
from distributed_llm_tpu.routing.cache import QueryCache


@settings(max_examples=200, deadline=None)
@given(st.lists(st.one_of(
    st.tuples(st.just("alloc"), st.integers(0, 12)),
    st.tuples(st.just("free"), st.integers(0, 5)),
), max_size=60))
def test_block_allocator_conserves_blocks(ops):
    """No block is ever lost, double-lent, or conjured; trash is never
    handed out and never re-enters the free list."""
    total = 33
    alloc = BlockAllocator(total)
    lent = []                                 # flat list of outstanding ids

    for op, n in ops:
        if op == "alloc":
            got = alloc.alloc(n)
            if got is not None:
                assert len(got) == n
                assert TRASH_BLOCK not in got
                assert not set(got) & set(lent), "double-lent block"
                lent.extend(got)
            else:
                # Refusal only under genuine pressure.
                assert alloc.available < n
        else:                                 # free a random slice
            back, lent = lent[:n], lent[n:]
            alloc.free(back)
        assert alloc.available + len(lent) == total - 1   # trash excluded

    alloc.free(lent)
    assert alloc.available == total - 1


@settings(max_examples=100, deadline=None)
@given(st.lists(st.tuples(st.text("abcdef", min_size=1, max_size=8),
                          st.sampled_from(["nano", "orin"])),
                min_size=1, max_size=50))
def test_query_cache_respects_capacity_and_counts(entries):
    """Size never exceeds max_size; hits+misses == lookups; every insert
    is immediately retrievable by exact key while capacity allows."""
    cache = QueryCache(max_size=8, ttl_seconds=3600, use_semantic=False)
    lookups = 0
    for query, device in entries:
        cache.insert(query, "ctx", device, confidence=0.9, method="test")
        res = cache.lookup(query, "ctx")
        lookups += 1
        assert res is not None, "fresh insert must hit exactly"
        assert res.entry.predict_device()[0] in ("nano", "orin")
        stats = cache.stats()
        assert stats["size"] <= 8
    stats = cache.stats()
    assert stats["attempts"] == lookups
    assert stats["hits"] <= stats["attempts"]


@settings(max_examples=100, deadline=None)
@given(st.data())
def test_prefix_cache_reclaims_only_true_prefixes(data):
    """select_reuse must only ever return (entry, m, suffix, sb) where the
    entry's ids are a true prefix of the prompt of length m and
    suffix == prompt[m:]."""
    from distributed_llm_tpu.engine.prefix_cache import (PrefixCache,
                                                         select_reuse)

    alphabet = st.integers(1, 5)
    prompt = data.draw(st.lists(alphabet, min_size=1, max_size=32))
    # Parked entries are DERIVED from the prompt (truncations, extensions,
    # and tail-perturbed variants) so the match/partial-match/mismatch
    # branches all actually fire — independent random lists almost never
    # share a usable prefix, which would make the property vacuous.
    parked = []
    for _ in range(data.draw(st.integers(0, 4))):
        cut = data.draw(st.integers(0, len(prompt)))
        tail = data.draw(st.lists(alphabet, max_size=8))
        parked.append(prompt[:cut] + tail)

    cache = PrefixCache(capacity=4, min_prefix=1)
    for ids in parked:
        if ids:
            cache.put(tuple(ids), {"cache": None, "tag": tuple(ids)})

    sel = select_reuse(cache, prompt, buckets=(8, 16, 32), max_seq=64)
    if sel is not None:
        entry, m, suffix, sb = sel
        assert 0 < m <= len(prompt)
        assert list(entry.cache["tag"])[:m] == prompt[:m]
        assert suffix == prompt[m:]
        if sb is not None:
            assert sb >= len(suffix)


@settings(max_examples=200, deadline=None)
@given(st.text(max_size=200))
def test_tokenizer_roundtrips_arbitrary_unicode(s):
    """decode(encode(s)) == s for any unicode (byte-level scheme)."""
    from distributed_llm_tpu.engine.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(s, add_bos=False)) == s


@settings(max_examples=100, deadline=None)
@given(st.text(max_size=64))
def test_stream_decoder_matches_batch_decode(s):
    """Feeding bytes one token at a time through StreamDecoder yields the
    same text as decoding the whole id list at once."""
    from distributed_llm_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder
    tok = ByteTokenizer()
    ids = tok.encode(s, add_bos=False)
    dec = StreamDecoder()
    out = "".join(dec.feed(t) for t in ids) + dec.flush()
    assert out == tok.decode(ids)


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 12), st.integers(0, 2**31 - 1))
def test_top_k_sampling_only_picks_top_k(k, seed):
    """With top_k set, sampled ids must come from the k highest logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distributed_llm_tpu.ops.sampling import sample_token

    logits = jax.random.normal(jax.random.PRNGKey(seed), (3, 32))
    tok = sample_token(logits, jax.random.PRNGKey(seed + 1),
                       temperature=1.0, top_k=k)
    top = np.argsort(np.asarray(logits), axis=-1)[:, -k:]
    for b in range(3):
        assert int(tok[b]) in top[b]
