"""Turn clipping for served replies.

The reference's device servers return Ollama chat-model output, and an
instruction-tuned model stops at its turn boundary on its own
(src/devices/nano_api.py:76 just forwards the text).  This framework's
tiers serve LMs pretrained on the raw ``role: content`` chat corpus
(training/data.py), so an un-clipped generation happily continues the
TRANSCRIPT — emitting ``user:`` / ``assistant:`` turns after its own
reply.  The serving layer owns restoring the single-turn semantic: clip
the reply at the first role marker the model hallucinates, both on the
sync path and (with a hold-back buffer) on the token stream.
"""

from __future__ import annotations

from typing import Iterator, Optional

# Role labels as they appear in the training corpus / prompt format
# (engine/tokenizer.py format_history): "role: content" lines.
_ROLES = ("user:", "assistant:", "system:")
# Longest text a marker can span, for the streaming hold-back (11 chars:
# "assistant:" + newline).  WORST CASE of the hold-back: nothing is
# emitted until >HOLDBACK chars accumulate, and a stream whose model emits a role marker from token one NEVER
# emits — ClippedStream then silently drains the rest of the generation
# for its result/lock, so an eager first-delta primer
# (serving/tiers.py _PrimedStream) would block a serving thread for the
# whole decode budget.  ClippedStream's ``prime_drain_chars`` bounds
# that drain (the primer is released with one "" sentinel after at most
# PRIME_DRAIN_CHARS drained chars ≈ 74 BPE tokens at ~3.5 chars/token —
# see the constant's definition in serving/tiers.py).
HOLDBACK = max(len(r) for r in _ROLES) + 1          # +1 for the newline


def _marker_pos(text: str, at_line_start: bool = True) -> Optional[int]:
    """Position of the earliest role marker at a line start (markers
    mid-line are quoted text, not turns), or None.  ``at_line_start``
    says whether position 0 of ``text`` begins a line — False when the
    caller holds a buffer whose origin is mid-line (the streaming
    hold-back cut)."""
    best: Optional[int] = None
    for role in _ROLES:
        start = 0
        while True:
            i = text.find(role, start)
            if i < 0:
                break
            if (i == 0 and at_line_start) or (i > 0 and text[i - 1] == "\n"):
                best = i if best is None else min(best, i)
                break
            start = i + 1
    return best


def clip_turn(text: str) -> str:
    """The reply's own turn: drop a leading ``assistant:`` label if the
    model echoed one, then cut at the first subsequent role marker.  A
    clip that would leave nothing returns the stripped original (a
    degenerate transcript beats an empty reply)."""
    stripped = text.lstrip()
    for role in _ROLES:
        if stripped.startswith(role):
            stripped = stripped[len(role):].lstrip()
            break
    pos = _marker_pos(stripped)
    clipped = stripped[:pos] if pos is not None else stripped
    clipped = clipped.rstrip()
    return clipped if clipped else text.strip()


class ClippedStream:
    """Delta-stream wrapper applying ``clip_turn`` semantics on the fly.

    Holds back the last ``HOLDBACK`` characters so a role marker split
    across deltas is still caught before it is emitted.  Once a marker
    is confirmed, remaining deltas are DRAINED silently rather than the
    stream closed: closing mid-stream would leave ``handle.result``
    None (no token counts for the done event, no perf-strategy
    feedback) and skip the engine's end-of-stream prefix-cache parking,
    so the next turn would lose its KV reuse.  The drain's dead air is
    bounded by the tier's ``max_new_tokens`` decode cap (48-128 across
    the shipped clusters) — the same budget the sync path always
    spends, since it clips after the fact.

    WORST CASE (and the ``prime_drain_chars`` cap): when the model emits
    a role marker from token one, nothing is ever emitted and a single
    ``next()`` on this stream blocks for the ENTIRE drain — up to
    max_new_tokens of decoding.  A caller that eagerly primes the first
    delta before handing the stream out (serving/tiers.py
    ``_PrimedStream``, which holds the sequential engine lock while
    priming) would stall its serving thread for a full generation before
    the handle is even returned.  ``prime_drain_chars`` caps that: once
    a fully-clipped stream has silently drained that many characters, an
    EMPTY delta is yielded once so the primer's ``next()`` returns; the
    remaining drain then happens lazily as the consumer iterates.
    Consumers must tolerate one "" delta (``_PrimedStream`` swallows
    it).  None keeps the uncapped r5 behavior.
    """

    def __init__(self, handle, prime_drain_chars: Optional[int] = None):
        self._handle = handle
        self._prime_drain_chars = prime_drain_chars
        self._emitted_any = False

    def __iter__(self) -> Iterator[str]:
        buf = ""                  # text received but not yet emitted
        # Whether position 0 of buf begins a line: True until a
        # hold-back cut leaves a mid-line origin (a quoted "user:" that
        # lands exactly on a cut boundary must not read as a turn).
        buf_line_start = True
        label_checked = False
        clipped = False
        drained = 0               # chars silently drained after a clip
        prime_released = False
        for delta in self._handle:
            if clipped:
                # Drain for result/lock, emit nothing — but release an
                # eager primer once (see class docstring worst case).
                drained += len(delta)
                if (self._prime_drain_chars is not None
                        and not self._emitted_any and not prime_released
                        and drained >= self._prime_drain_chars):
                    prime_released = True
                    yield ""
                continue
            buf += delta
            if not label_checked:
                # Wait until the buffer can't be a partial leading label.
                probe = buf.lstrip()
                if (len(probe) < HOLDBACK
                        and any(r.startswith(probe) or probe.startswith(r)
                                for r in _ROLES)):
                    continue
                for role in _ROLES:
                    if probe.startswith(role):
                        buf = probe[len(role):].lstrip()
                        break
                label_checked = True
            pos = _marker_pos(buf, at_line_start=buf_line_start)
            if pos is not None:
                out = buf[:pos].rstrip()
                if out:
                    self._emitted_any = True
                    yield out
                buf = ""
                clipped = True
                continue
            if len(buf) > HOLDBACK:
                out, buf = buf[:-HOLDBACK], buf[-HOLDBACK:]
                buf_line_start = out.endswith("\n")
                if out:
                    self._emitted_any = True
                    yield out
        if not clipped:
            tail = buf.rstrip() if self._emitted_any else clip_turn(buf)
            if tail:
                self._emitted_any = True
                yield tail
        # A fully-clipped stream (marker from token one) still owes the
        # caller SOMETHING; mirror clip_turn's degenerate fallback.
        if not self._emitted_any:
            result = getattr(self._handle, "result", None)
            text = getattr(result, "text", "") or ""
            fallback = text.strip()
            if fallback:
                yield fallback

    def close(self) -> None:
        close = getattr(self._handle, "close", None)
        if close is not None:
            close()

    @property
    def result(self):
        return getattr(self._handle, "result", None)
