"""Turn clipping (serving/turns.py): served replies stop at the model's
own turn instead of continuing the transcript — the single-turn semantic
the reference gets for free from Ollama's instruction-tuned models."""

import pytest

from distributed_llm_tpu.serving.turns import (ClippedStream, clip_turn,
                                               _marker_pos)


def test_clip_turn_cuts_at_first_role_marker():
    assert clip_turn("The capital is Tokyo.\nuser: And France?\n"
                     "assistant: Paris.") == "The capital is Tokyo."
    # Leading echoed label is dropped, then the next marker clips.
    assert clip_turn("assistant: Tokyo.\nuser: next") == "Tokyo."
    # Markers mid-line are quoted text, not turns.
    assert clip_turn("Type 'user: hi' to begin.") == "Type 'user: hi' to begin."
    # No marker: stripped passthrough.
    assert clip_turn("  plain reply  ") == "plain reply"


def test_clip_turn_degenerate_keeps_something():
    # A reply that IS a transcript from token one must not become "".
    text = "user: echo\nassistant: echo"
    assert clip_turn(text) != ""
    assert clip_turn("") == ""


def test_marker_pos_line_start_only():
    assert _marker_pos("abc\nuser: x") == 4
    assert _marker_pos("abc user: x") is None
    assert _marker_pos("user: x") == 0


class _FakeHandle:
    def __init__(self, deltas, text=None):
        self._deltas = deltas
        self.result = type("R", (), {"text": text if text is not None
                                     else "".join(deltas),
                                     "gen_tokens": 5})()

    def __iter__(self):
        return iter(self._deltas)


@pytest.mark.parametrize("deltas", [
    ["The capital ", "is Tokyo.", "\nuse", "r: And France?", " more"],
    ["The capital is Tokyo.\nuser: And France? more"],
    list("The capital is Tokyo.\nuser: And France?"),
])
def test_clipped_stream_stops_at_marker(deltas):
    out = "".join(ClippedStream(_FakeHandle(deltas)))
    assert out == "The capital is Tokyo."


def test_clipped_stream_no_marker_passthrough():
    deltas = ["Hello ", "there, ", "rivers are long."]
    assert "".join(ClippedStream(_FakeHandle(deltas))) == \
        "Hello there, rivers are long."


def test_clipped_stream_drops_leading_label_and_keeps_result():
    h = _FakeHandle(["assist", "ant: Tok", "yo rules.", "\nuser: hi"])
    s = ClippedStream(h)
    assert "".join(s) == "Tokyo rules."
    assert s.result.gen_tokens == 5


def test_clipped_stream_degenerate_falls_back_to_result_text():
    # A transcript-shaped reply clips to its first turn's content, same
    # as the sync clip_turn.
    h = _FakeHandle(["user: echo\nassistant: echo"])
    assert "".join(ClippedStream(h)) == "echo"
    assert clip_turn("user: echo\nassistant: echo") == "echo"
    # Nothing BUT a label: stream emits the raw-text fallback rather
    # than nothing at all.
    h = _FakeHandle(["user:"])
    assert "".join(ClippedStream(h)) == "user:"
    assert clip_turn("user:") == "user:"


def test_clipped_stream_quoted_marker_on_cut_boundary_not_clipped():
    """A quoted mid-line 'user:' whose position coincides with a
    hold-back cut must NOT read as a turn marker (code review r5: after
    a cut, buffer position 0 is mid-line, not a line start)."""
    deltas = ["Say user:abcdef", " now etc"]
    assert "".join(ClippedStream(_FakeHandle(deltas))) == \
        "Say user:abcdef now etc"
    # Same text through the sync path agrees.
    assert clip_turn("Say user:abcdef now etc") == "Say user:abcdef now etc"
    # A REAL marker right after a cut (preceded by newline) still clips.
    deltas = ["First line okay\n", "user: next turn"]
    assert "".join(ClippedStream(_FakeHandle(deltas))) == "First line okay"


def test_clipped_stream_prime_drain_cap_releases_early():
    """A marker from token one makes the clipped
    drain consume the WHOLE generation inside a single next() — with
    ``prime_drain_chars`` the stream yields one empty delta once that
    many chars have drained, so an eager primer returns early; the rest
    drains lazily and the degenerate fallback still lands."""
    # An echoed label then a transcript from token one: nothing ever
    # emits, so the whole stream would drain inside the first next().
    deltas = (["assistant:\n", "user: filler question?\n"]
              + ["assistant: filler words. "] * 20)
    s = ClippedStream(_FakeHandle(deltas, text="assistant: only labels"),
                      prime_drain_chars=30)
    it = iter(s)
    first = next(it)
    assert first == ""                       # prime released, not blocked
    rest = list(it)
    assert rest == ["assistant: only labels"]  # degenerate fallback at end


def test_clipped_stream_prime_cap_noop_for_normal_streams():
    """The cap must not inject empty deltas into streams that emit real
    text (the primer sentinel only fires on fully-clipped streams)."""
    deltas = ["Hello ", "there, ", "rivers are long."]
    out = list(ClippedStream(_FakeHandle(deltas), prime_drain_chars=4))
    assert "" not in out
    assert "".join(out) == "Hello there, rivers are long."


def test_primed_stream_swallows_prime_sentinel():
    """Through TierClient's primer: the empty release delta never
    reaches the consumer, and the stream still ends with the fallback."""
    from distributed_llm_tpu.serving.tiers import _PrimedStream

    deltas = (["assistant:\n", "user: filler question?\n"]
              + ["assistant: more filler text. "] * 20)
    clipped = ClippedStream(_FakeHandle(deltas, text="assistant: labels"),
                            prime_drain_chars=30)
    released = []
    primed = _PrimedStream(clipped, release=lambda: released.append(1))
    out = list(primed)
    assert "" not in out and out == ["assistant: labels"]
    assert released == [1]                   # release fired exactly once


def test_tier_process_clips_served_reply():
    """End-to-end through TierClient.process: a transcript-continuing
    generation serves only its own turn."""
    from distributed_llm_tpu.config import TierConfig
    from distributed_llm_tpu.serving.tiers import TierClient

    class FakeResult:
        text = "It is Tokyo.\nuser: and Peru?\nassistant: Lima."
        gen_tokens = 12
        ttft_ms = 1.0
        total_ms = 2.0
        prompt_tokens = 4

    class FakeEngine:
        concurrent_safe = False

        def generate(self, history, **kw):
            return FakeResult()

    class FakeManager:
        def is_server_running(self):
            return True

        def engine(self):
            return FakeEngine()

    tier = TierClient(TierConfig(name="nano", model_preset="nano_test",
                                 request_timeout_s=None), FakeManager())
    resp = tier.process([{"role": "user", "content": "capital of Japan?"}])
    assert resp["response"] == "It is Tokyo."
    # Per-request timing rides in the raw dict (additive keys so
    # concurrent bench clients get race-free TTFT; serving/tiers.py).
    assert resp["ttft_ms"] == 1.0 and resp["gen_tokens"] == 12
