"""Tokenizers: trained subword BPE (default for serving presets) and the
byte-level fallback.

The reference gets tokenization for free from Ollama/llama.cpp; in this
zero-egress environment no pretrained BPE vocabulary can be fetched, so the
framework trains its own byte-level BPE over its corpus (engine/bpe.py)
and keeps this self-contained byte-level scheme as the
fallback: ids 0-255 are raw UTF-8 bytes, followed by PAD/BOS/EOS specials,
padded to a 512 vocab so the embedding table tiles the MXU's 128-lane
layout cleanly.  Both tokenizers share the special ids and the
``token_bytes``/encode/decode surface, so engines are tokenizer-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Sequence, Union

PAD_ID = 256
BOS_ID = 257
EOS_ID = 258
VOCAB_SIZE = 512


def format_history(history: Union[str, Sequence[Dict[str, Any]]]) -> str:
    """Conversation history -> prompt string, matching the reference's
    device-server formatting: one "role: content" line per message
    (src/devices/nano_api.py:49-56)."""
    if isinstance(history, str):
        return history.strip()
    lines = [
        f"{m.get('role', 'user')}: {m.get('content', '')}"
        for m in history
    ]
    return "\n".join(lines).strip()


@dataclasses.dataclass(frozen=True)
class ByteTokenizer:
    pad_id: int = PAD_ID
    bos_id: int = BOS_ID
    eos_id: int = EOS_ID
    vocab_size: int = VOCAB_SIZE

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = list(text.encode("utf-8"))
        return [self.bos_id] + ids if add_bos else ids

    def decode(self, ids: Iterable[int]) -> str:
        data = bytes(i for i in ids if 0 <= int(i) < 256)
        return data.decode("utf-8", errors="replace")

    def format_history(self, history: Union[str, Sequence[Dict[str, Any]]]) -> str:
        return format_history(history)

    def encode_history(self, history: Union[str, Sequence[Dict[str, Any]]]) -> List[int]:
        return self.encode(self.format_history(history))


def get_tokenizer(cfg):
    """Tokenizer for a model config: the committed BPE artifact for
    ``cfg.tokenizer == "bpe"`` presets (engine/bpe.py), byte-level
    otherwise.  The vocabulary sizes must agree — a mismatch means the
    checkpoint/preset and the tokenizer artifact drifted apart."""
    if getattr(cfg, "tokenizer", "byte") == "bpe":
        from .bpe import load_default
        tok = load_default()
        if tok.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"model {cfg.name}: vocab_size {cfg.vocab_size} != BPE "
                f"artifact vocab {tok.vocab_size} (re-train the vocabulary "
                "or fix the preset)")
        return tok
    return ByteTokenizer()


class StreamDecoder:
    """Incremental token→text-delta decoder for streaming engines.

    Multi-byte UTF-8 sequences are held back until complete; special and
    padding ids produce no text.  Subword tokenizers expose
    ``token_bytes`` (exact UTF-8 expansion per id); without it the
    byte-level scheme applies.  One shared implementation so the
    sequential and batching engines' SSE output can never diverge."""

    def __init__(self, tokenizer=None):
        import codecs
        self._decoder = codecs.getincrementaldecoder("utf-8")("replace")
        self._table = getattr(tokenizer, "token_bytes", None)

    def feed(self, token: int) -> str:
        if self._table is not None:
            data = (self._table[token]
                    if 0 <= token < len(self._table) else b"")
            return self._decoder.decode(data) if data else ""
        if 0 <= token < 256:
            return self._decoder.decode(bytes([token]))
        return ""

    def flush(self) -> str:
        return self._decoder.decode(b"", final=True)
