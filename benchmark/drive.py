"""Offer a mix's traffic to ``/chat/stream`` and stamp what comes back.

Client threads of the benchmark's own process (a chip belongs to one
process) post to the app's test client and read the SSE frames as the
server yields them.  Every character of a ``delta`` is one generated
token (the configuration's ``token_bytes`` table); each is stamped with
the arrival of its delta, by ``time.perf_counter``.  The serving edge's
turn clipper (serving/turns.py) holds back the last 11 characters, so the
first delta a client sees is the reply's 12th token: the stamps are what
a user sees, hold-back included.  Nothing here computes a metric.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from trafficgen import iter_requests

clock = time.perf_counter


def stream_request(client, item: Dict[str, Any], strategy: str,
                   due: float) -> Dict[str, Any]:
    """One request to its end.  Returns its record: when it was due and
    sent, the arrival stamp of every token, the tier that served it, and
    the server's own ``done`` figures for the cross-check."""
    rec: Dict[str, Any] = {
        "class": item["class"], "prompt_tokens": item["tokens"],
        "session": item["session"], "due": due, "sent": clock(),
        "stamps": [], "device": None, "ok": False, "error": None,
        "server": None, "end": None}
    try:
        resp = client.post("/chat/stream", json={
            "message": item["message"], "strategy": strategy,
            "session_id": item["session"]})
        if resp.status_code != 200:
            body = resp.get_json() or {}
            rec["error"] = f"HTTP {resp.status_code}: {body.get('error')}"
            return rec
        for chunk in resp.iter_encoded():
            now = clock()
            for frame in chunk.decode("utf-8").split("\n\n"):
                if not frame.startswith("data: "):
                    continue
                ev = json.loads(frame[len("data: "):])
                if "delta" in ev:
                    # One character a token (the token_bytes table); the
                    # edge's turn clipper holds text back and may hand
                    # over several tokens in one delta.
                    rec["stamps"].extend([now] * len(ev["delta"]))
                elif ev.get("meta"):
                    rec["device"] = ev.get("device")
                elif ev.get("done"):
                    rec["server"] = {k: ev.get(k) for k in
                                     ("tokens", "ttft_ms", "total_ms")}
                    rec["ok"] = True
                elif "error" in ev:
                    rec["error"] = str(ev["error"])[:200]
    except Exception as exc:      # a failed request is a record, not a crash
        rec["error"] = f"{type(exc).__name__}: {exc}"[:200]
        rec["ok"] = False
    finally:
        rec["end"] = clock()
    return rec


class Run:
    """The traffic of one run: started ``ramp_s`` before t0, stopped at
    ``t0 + seconds``, drained for at most ``drain_s``."""

    def __init__(self, client, mix: Dict[str, Any], seed: int,
                 seconds: float):
        self.client = client
        self.mix = mix
        self.seed = seed
        self.seconds = float(seconds)
        self.records: List[Dict[str, Any]] = []
        self.unfinished = 0
        self._lock = threading.Lock()
        self.t0: Optional[float] = None

    def _note(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self.records.append(rec)

    def _closed_client(self, lane: int, stop_at: float) -> None:
        # Clients start ``stagger_s`` apart and, with replies of one
        # length, stay apart: admissions then fall among the others'
        # decode ticks, as they do for callers that do not know of each
        # other, and one reply that ends early moves one client's phase
        # instead of breaking a lockstep.
        time.sleep(lane * float(self.mix.get("stagger_s", 0.0)))
        for item in iter_requests(self.mix, self.seed, lane):
            now = clock()
            if now >= stop_at:
                return
            self._note(stream_request(self.client, item,
                                      self.mix["strategy"], due=now))

    def run(self, in_window: Optional[Callable[[float], None]] = None
            ) -> None:
        """Blocks until the drain ends.  ``in_window(t0)`` is called on
        this thread once the window has opened (the traced run starts
        and stops the profiler from it)."""
        start = clock()
        self.t0 = t0 = start + float(self.mix["ramp_s"])
        stop_at = t0 + self.seconds
        if self.mix["loop"] != "closed":
            raise ValueError(f"mix {self.mix['name']}: loop "
                             f"{self.mix['loop']!r}; the generator drives "
                             f"closed loops only")
        workers = [threading.Thread(target=self._closed_client,
                                    args=(lane, stop_at), daemon=True)
                   for lane in range(int(self.mix["clients"]))]
        for w in workers:
            w.start()
        if in_window is not None:
            time.sleep(max(0.0, t0 - clock()))
            in_window(t0)
        time.sleep(max(0.0, stop_at - clock()))
        deadline = stop_at + float(self.mix["drain_s"])
        for w in workers:
            w.join(timeout=max(0.0, deadline - clock()))
        self.unfinished = sum(1 for w in workers if w.is_alive())
