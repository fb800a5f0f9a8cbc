"""Trained subword BPE tokenizer (engine/bpe.py).

The engine serves subword ids end-to-end since round 3; these tests pin
the training algorithm (deterministic, word-bounded merges), the encode/
decode contract (lossless on arbitrary text via the byte fallback), the
committed vocabulary artifact, streaming decode, and the exact routing
token counter built on top.
"""

import json

import pytest

from distributed_llm_tpu.engine.bpe import (BPETokenizer, DEFAULT_VOCAB_PATH,
                                            load_default, train_bpe)
from distributed_llm_tpu.engine.tokenizer import (ByteTokenizer,
                                                  StreamDecoder,
                                                  get_tokenizer)

CORPUS = ["the chip routes tokens across the mesh " * 8,
          "user: what is the capital of japan?\nassistant: tokyo " * 4,
          "compile the kernel and fuse the matmul " * 6]


def test_training_is_deterministic_and_word_bounded():
    m1 = train_bpe(CORPUS, vocab_size=400)
    m2 = train_bpe(list(CORPUS), vocab_size=400)
    assert m1 == m2 and len(m1) > 10
    tok = BPETokenizer(merges=tuple(m1), vocab_size=400)
    # No learned piece spans a word boundary: whitespace may only LEAD a
    # piece (" the"), never sit between two words.
    for i in range(259, 259 + len(m1)):
        piece = tok.token_bytes[i].decode("utf-8", errors="replace")
        assert " " not in piece.strip(), repr(piece)


def test_roundtrip_arbitrary_text_including_oov():
    tok = BPETokenizer.train(CORPUS, vocab_size=400)
    for text in ("the chip routes tokens",
                 "completely unseen wörds — ünïcode ☃ and bytes\x00\x7f",
                 "", "   spaces   and\nnewlines\t\ttabs"):
        ids = tok.encode(text, add_bos=False)
        assert tok.decode(ids) == text
        # BOS variant decodes identically (specials emit no text).
        assert tok.decode(tok.encode(text)) == text


def test_special_ids_match_byte_tokenizer():
    tok = BPETokenizer.train(CORPUS, vocab_size=400)
    byte_tok = ByteTokenizer()
    assert (tok.pad_id, tok.bos_id, tok.eos_id) == (
        byte_tok.pad_id, byte_tok.bos_id, byte_tok.eos_id)


def test_compression_beats_bytes_on_corpus_text():
    tok = BPETokenizer.train(CORPUS, vocab_size=512)
    text = "the chip routes tokens across the mesh"
    assert len(tok.encode(text, add_bos=False)) < len(text) / 2


def test_save_load_roundtrip(tmp_path):
    tok = BPETokenizer.train(CORPUS, vocab_size=400)
    path = str(tmp_path / "vocab.json")
    tok.save(path)
    back = BPETokenizer.load(path)
    assert back.merges == tok.merges and back.vocab_size == tok.vocab_size
    text = "routes tokens across"
    assert back.encode(text) == tok.encode(text)


def test_committed_artifact_serves_the_presets():
    """The committed bpe_vocab.json must agree with every 'bpe' preset and
    hit the subword compression regime on the bench queries (~3-5
    chars/token like the reference's tokenizer, src/token_counter.py:5-8)."""
    from distributed_llm_tpu.bench.query_sets import query_sets
    from distributed_llm_tpu.config import MODEL_PRESETS

    tok = load_default()
    with open(DEFAULT_VOCAB_PATH) as f:
        assert json.load(f)["format"] == "dllm-bpe-v1"
    for preset in MODEL_PRESETS.values():
        if preset.tokenizer == "bpe":
            assert get_tokenizer(preset).vocab_size == preset.vocab_size
    # Compression regime is asserted on the CONVERSATIONAL sets the
    # vocab was sized for; long_context's pasted pseudo-reports are
    # deliberately figure-dense (numerals split to bytes) and sit below
    # the chat regime — they still must roundtrip exactly (below).
    chat_sets = ("general_knowledge", "technical_coding",
                 "personal_health")
    chat_texts = [i["query"] for name in chat_sets
                  for i in query_sets[name]]
    chars = sum(len(t) for t in chat_texts)
    toks = sum(len(tok.encode(t, add_bos=False)) for t in chat_texts)
    assert 2.5 <= chars / toks <= 6.0, chars / toks
    for t in (i["query"] for qs in query_sets.values() for i in qs):
        assert tok.decode(tok.encode(t, add_bos=False)) == t


def test_get_tokenizer_rejects_vocab_mismatch():
    import dataclasses

    from distributed_llm_tpu.config import MODEL_PRESETS
    bad = dataclasses.replace(MODEL_PRESETS["nano_test"], vocab_size=512)
    with pytest.raises(ValueError, match="vocab"):
        get_tokenizer(bad)


def test_stream_decoder_handles_multibyte_subwords():
    tok = load_default()
    text = "user: naïve café — ☃ snowman?"
    ids = tok.encode(text, add_bos=False)
    sd = StreamDecoder(tok)
    out = "".join(sd.feed(i) for i in ids) + sd.flush()
    assert out == text
    # Specials stream as nothing.
    sd2 = StreamDecoder(tok)
    assert sd2.feed(tok.eos_id) == "" and sd2.feed(tok.pad_id) == ""


def test_token_counter_is_exact_against_serving_tokenizer():
    from distributed_llm_tpu.routing.token_counter import TokenCounter
    tok = load_default()
    tc = TokenCounter()
    msg = {"role": "user", "content": "Explain how plate tectonics works."}
    assert tc.count_tokens(msg) == len(
        tok.encode(msg["content"], add_bos=False))
    assert tc.count_tokens({"content": ""}) == 1
