"""Trained byte-level BPE subword tokenizer.

The reference's tiers serve real subword-vocab models through Ollama
(phi3-mini / llama3, /root/reference/src/devices/nano_api.py:15-16), and
its routing thresholds are tuned to BPE counts of ~4 characters/token
(/root/reference/src/token_counter.py:5-8).  Rounds 1-2 served a
byte-level vocab instead, paying ~4× the decode steps per word of text —
a first-order throughput gap no kernel can buy back.  Zero egress means
no pretrained vocabulary can be fetched, so this module trains one:
dependency-free byte-level BPE over the framework's own corpus
(training/data.py chat/synthetic generators + the bench query texts).

Id layout (deliberately compatible with ByteTokenizer so every consumer
of PAD/BOS/EOS stays tokenizer-agnostic):

    0-255      raw UTF-8 bytes (lossless fallback — no OOV possible)
    256/257/258  PAD / BOS / EOS
    259+       learned merges, in rank order
    ...        padded up to ``vocab_size`` (a 128-lane multiple for the
               MXU-friendly embedding table; padding ids decode to "")

Merges never cross pre-token boundaries (``\\s*\\S+`` chunks: a word plus
its leading whitespace), which keeps encode cacheable per chunk and the
vocabulary word-aligned like the llama/GPT families.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

from .tokenizer import BOS_ID, EOS_ID, PAD_ID

# A word and the whitespace that introduces it travel together, so the
# learned pieces look like " the"/" comp"/"iler" and decode re-inserts
# spacing for free.
_CHUNK_RE = re.compile(r"\s*\S+|\s+$")

_FIRST_MERGE_ID = 259
DEFAULT_VOCAB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "bpe_vocab.json")


def train_bpe(texts: Iterable[str], vocab_size: int = 4096,
              ) -> List[Tuple[int, int]]:
    """Learn BPE merges over ``texts`` until the id space [259, vocab_size)
    is full (or no pair repeats).  Deterministic: ties on count break
    toward the lexicographically smallest pair.

    Classic word-frequency BPE with incremental pair-count maintenance —
    the corpus is compressed to distinct chunks first, so training the
    full 4k vocabulary over the framework corpus takes seconds."""
    if vocab_size <= _FIRST_MERGE_ID:
        raise ValueError(f"vocab_size {vocab_size} leaves no room for merges")
    from collections import Counter, defaultdict

    # Distinct chunk -> frequency, each chunk a list of ids.
    freq: Counter = Counter()
    for text in texts:
        for m in _CHUNK_RE.finditer(text):
            freq[m.group()] += 1
    words: List[List[int]] = []
    counts: List[int] = []
    for chunk, c in sorted(freq.items()):
        words.append(list(chunk.encode("utf-8")))
        counts.append(c)

    pair_counts: Counter = Counter()
    pair_words: defaultdict = defaultdict(set)   # pair -> word indices
    for wi, w in enumerate(words):
        c = counts[wi]
        for pair in zip(w, w[1:]):
            pair_counts[pair] += c
            pair_words[pair].add(wi)

    merges: List[Tuple[int, int]] = []
    max_merges = vocab_size - _FIRST_MERGE_ID
    while len(merges) < max_merges and pair_counts:
        best = min(pair_counts.items(), key=lambda kv: (-kv[1], kv[0]))[0]
        if pair_counts[best] < 2:      # nothing repeats: stop, don't memorize
            break
        new_id = _FIRST_MERGE_ID + len(merges)
        merges.append(best)
        for wi in list(pair_words.pop(best, ())):
            w = words[wi]
            c = counts[wi]
            # Remove the word's old pair contributions...
            for pair in zip(w, w[1:]):
                pair_counts[pair] -= c
                if pair_counts[pair] <= 0:
                    del pair_counts[pair]
                if pair != best:
                    pair_words[pair].discard(wi)
            # ...rewrite it with the merge applied...
            out: List[int] = []
            i = 0
            while i < len(w):
                if i + 1 < len(w) and (w[i], w[i + 1]) == best:
                    out.append(new_id)
                    i += 2
                else:
                    out.append(w[i])
                    i += 1
            words[wi] = out
            # ...and add the new contributions.
            for pair in zip(out, out[1:]):
                pair_counts[pair] += c
                pair_words[pair].add(wi)
    return merges


@dataclasses.dataclass(frozen=True)
class BPETokenizer:
    """Same surface as ByteTokenizer (engine code is tokenizer-agnostic),
    backed by learned merges.  ``token_bytes[id]`` is the exact UTF-8 byte
    expansion of every id (b"" for specials/padding) — the StreamDecoder
    uses it to emit text deltas mid-multibyte-sequence safely."""

    merges: Tuple[Tuple[int, int], ...]
    vocab_size: int = 4096
    pad_id: int = PAD_ID
    bos_id: int = BOS_ID
    eos_id: int = EOS_ID

    def __post_init__(self):
        if _FIRST_MERGE_ID + len(self.merges) > self.vocab_size:
            raise ValueError(
                f"{len(self.merges)} merges overflow vocab {self.vocab_size}")
        ranks = {tuple(p): i for i, p in enumerate(self.merges)}
        table: List[bytes] = [bytes([i]) for i in range(256)]
        table += [b""] * (self.vocab_size - 256)       # specials + padding
        for i, (a, b) in enumerate(self.merges):
            table[_FIRST_MERGE_ID + i] = table[a] + table[b]
        object.__setattr__(self, "_ranks", ranks)
        object.__setattr__(self, "token_bytes", tuple(table))
        object.__setattr__(self, "_cache", {})

    # -- encode ------------------------------------------------------------

    def _encode_chunk(self, chunk: str) -> List[int]:
        hit = self._cache.get(chunk)
        if hit is not None:
            return hit
        ids = list(chunk.encode("utf-8"))
        ranks = self._ranks
        while len(ids) > 1:
            best_rank, best_i = None, -1
            for i in range(len(ids) - 1):
                r = ranks.get((ids[i], ids[i + 1]))
                if r is not None and (best_rank is None or r < best_rank):
                    best_rank, best_i = r, i
            if best_rank is None:
                break
            new_id = _FIRST_MERGE_ID + best_rank
            pair = (ids[best_i], ids[best_i + 1])
            out: List[int] = []
            i = 0
            while i < len(ids):
                if i + 1 < len(ids) and (ids[i], ids[i + 1]) == pair:
                    out.append(new_id)
                    i += 2
                else:
                    out.append(ids[i])
                    i += 1
            ids = out
        if len(self._cache) < 65536:       # bound the per-process cache
            self._cache[chunk] = ids
        return ids

    def _native_encode(self, text: str) -> Optional[List[int]]:
        """C++ merge loop (native/bpe_encoder.cc) for ASCII text — on
        ASCII, C's byte-wise isspace and Python's \\s agree, so the two
        paths are bit-identical (pinned by tests/test_native.py).  Returns
        None whenever native is unavailable; the Python path is the
        reference semantics and the non-ASCII path."""
        handle = self.__dict__.get("_native_handle")
        if handle is None:
            from .. import native
            handle = native.bpe_load(self.merges)
            object.__setattr__(self, "_native_handle",
                               handle if handle is not None else -1)
        if handle == -1 or handle is None:
            return None
        from .. import native
        return native.bpe_encode(handle, text)

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids: List[int] = [self.bos_id] if add_bos else []
        # Long ASCII prompts take the native merge loop; short texts stay
        # on the Python path where the per-chunk cache usually hits.
        if len(text) >= 256 and text.isascii():
            native_ids = self._native_encode(text)
            if native_ids is not None:
                ids.extend(native_ids)
                return ids
        for m in _CHUNK_RE.finditer(text):
            ids.extend(self._encode_chunk(m.group()))
        return ids

    # -- decode ------------------------------------------------------------

    def decode(self, ids: Iterable[int]) -> str:
        table = self.token_bytes
        data = b"".join(table[int(i)] for i in ids
                        if 0 <= int(i) < len(table))
        return data.decode("utf-8", errors="replace")

    # -- history formatting (shared contract with ByteTokenizer) -----------

    def format_history(self,
                       history: Union[str, Sequence[Dict[str, Any]]]) -> str:
        from .tokenizer import format_history
        return format_history(history)

    def encode_history(self,
                       history: Union[str, Sequence[Dict[str, Any]]]
                       ) -> List[int]:
        return self.encode(self.format_history(history))

    # -- persistence -------------------------------------------------------

    def save(self, path: str) -> None:
        payload = {"format": "dllm-bpe-v1", "vocab_size": self.vocab_size,
                   "merges": [list(p) for p in self.merges]}
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "BPETokenizer":
        with open(path) as f:
            payload = json.load(f)
        if payload.get("format") != "dllm-bpe-v1":
            raise ValueError(f"{path}: not a dllm-bpe-v1 vocabulary")
        return cls(merges=tuple(tuple(p) for p in payload["merges"]),
                   vocab_size=int(payload["vocab_size"]))

    @classmethod
    def train(cls, texts: Iterable[str],
              vocab_size: int = 4096) -> "BPETokenizer":
        return cls(merges=tuple(train_bpe(texts, vocab_size)),
                   vocab_size=vocab_size)


_DEFAULT: Optional[BPETokenizer] = None


def load_default() -> BPETokenizer:
    """The committed vocabulary artifact (bpe_vocab.json), cached so every
    engine in the process shares one encode cache."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = BPETokenizer.load(DEFAULT_VOCAB_PATH)
    return _DEFAULT


def main(argv=None) -> None:
    """Train and publish the vocabulary artifact:

        python -m distributed_llm_tpu.engine.bpe [--vocab-size 4096]
            [--out .../bpe_vocab.json]

    Prints compression stats (chars/token) on the bench query texts — the
    number the routing thresholds care about (~4 chars/token in the
    reference's tokenizer, /root/reference/src/token_counter.py:5-8)."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--vocab-size", type=int, default=4096)
    ap.add_argument("--out", default=DEFAULT_VOCAB_PATH)
    args = ap.parse_args(argv)

    from ..training.data import bpe_corpus
    texts = bpe_corpus()
    tok = BPETokenizer.train(texts, args.vocab_size)
    tok.save(args.out)

    from ..bench.query_sets import query_sets
    qtexts = [item["query"] for qs in query_sets.values() for item in qs]
    chars = sum(len(t) for t in qtexts)
    toks = sum(len(tok.encode(t, add_bos=False)) for t in qtexts)
    byte_ratio = chars / max(toks, 1)
    print(json.dumps({
        "vocab_size": tok.vocab_size,
        "merges": len(tok.merges),
        "corpus_texts": len(texts),
        "bench_query_chars_per_token": round(byte_ratio, 2),
        "decode_step_reduction_vs_byte": round(byte_ratio, 2),
        "out": args.out,
    }))


if __name__ == "__main__":
    main()

