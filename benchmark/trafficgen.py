"""One general traffic generator; a mix is a data file of parameters.

A mix (``benchmark/traffic/<mix>.json``) gives:

    loop        "closed": ``clients`` clients, each sends its next request
                when the last ends (the one kind the generator drives)
    clients     how many
    ramp_s      traffic starts this long before t0; the ramp is in
                neither the window nor any latency
    stagger_s   client k sends its first request k x this long after
                client 0
    drain_s     after the window nothing new is sent; what has not
                finished after this long counts as failed
    strategy    the routing strategy each request names
    classes     [{"name", "share", "lengths": [...]}]: the prompt lengths
                are a FIXED grid of token counts; the seed shuffles their
                order and draws the text (a distinct tag, then seeded
                filler words), so two seeds ask for the same work
    reports     the end-to-end metrics the mix's cells report

Prompt lengths count tokens as the tier sees them: BOS + "user: " + the
characters of the message (the program's byte scheme, one token a byte).
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

CHAT_OVERHEAD_TOKENS = 7          # BOS + "user: "
WORDS = ("river delta stone copper winter signal harbor lantern meadow "
         "orbit canyon ember willow tundra quartz falcon prairie summit "
         "glacier timber cobalt marsh beacon thicket ridge anchor plume "
         "basin garnet hollow").split()


def _rng(seed: int, salt: str) -> random.Random:
    # str seeds hash with sha512 inside random.Random: stable across
    # processes, whatever PYTHONHASHSEED says.
    return random.Random(f"{salt}:{int(seed)}")


def make_text(rng: random.Random, n_tokens: int, tag: str) -> str:
    """A message of exactly ``n_tokens - CHAT_OVERHEAD_TOKENS``
    characters: a tag that makes it distinct, then seeded filler words."""
    n_chars = n_tokens - CHAT_OVERHEAD_TOKENS
    parts = [tag]
    size = len(tag) + 1
    while size < n_chars + 1:
        w = rng.choice(WORDS)
        parts.append(w)
        size += len(w) + 1
    text = " ".join(parts)[:n_chars].rstrip()
    # Cutting can leave a trailing space; the edge strips nothing inside,
    # but a message may not END in whitespace (format_history strips).
    return text + "x" * (n_chars - len(text))


def expand_grid(mix: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The mix's fixed multiset of (class, length) items, the same for
    every seed: each class contributes its length grid, repeated so that
    the classes stand in the ratio of their shares."""
    classes = mix["classes"]
    base = min(c["share"] / len(c["lengths"]) for c in classes)
    items = []
    for c in classes:
        reps = max(1, round(c["share"] / len(c["lengths"]) / base))
        for _ in range(reps):
            items.extend({"class": c["name"], "tokens": int(n)}
                         for n in c["lengths"])
    return items


def iter_requests(mix: Dict[str, Any], seed: int, lane: int):
    """The endless request sequence of one lane (a closed-loop client):
    whole shuffles of the grid laid end to end, so every run of
    ``len(grid)`` requests holds each item once."""
    grid = expand_grid(mix)
    rng = _rng(seed, f"{mix['name']}:lane{lane}")
    sent = cycle = 0
    while True:
        order = list(range(len(grid)))
        rng.shuffle(order)
        for j in order:
            item = dict(grid[j])
            tag = f"[{int(seed) % 100000}-{lane}-{cycle}-{j}]"
            item["message"] = make_text(rng, item["tokens"], tag)
            item["session"] = f"s{lane}-{sent}"
            sent += 1
            yield item
        cycle += 1
