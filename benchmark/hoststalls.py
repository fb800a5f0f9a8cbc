"""What stalls the host inside the window: printed, never judged.

Three recorders.  The first two are of the benchmark's own process (the
engine's scheduler thread lives in it, and every end-to-end number is
taken on its clock):

``GcPauses``   the cyclic garbage collector's collections, by
               ``gc.callbacks``: when each began, how long it took, of
               which generation.  A collection holds the GIL, so every
               thread stands still for its length.
``Heartbeat``  a thread that asks to sleep ``PERIOD_S`` and notes by how
               much it woke late.  Late beside a collection: the
               collector.  Late with none: some other thread held the
               GIL, or the process did not get a core (the one-chip
               machine shares its host's cores).
``MachineBeat`` the same heartbeat in a process of its own that never
               touches jax.  Late at the same moment as the thread: the
               machine stood still, not the program.  That is what the
               chip's machine does a few times a minute for about
               100 ms, and now and then for seconds (PERF.md section 6,
               PR 28).

A run whose tails read far from the others' can then be put down to a
cause from its own output, without a second run.
"""

from __future__ import annotations

import contextlib
import gc
import subprocess
import sys
import threading
import time
from typing import Dict, List, Tuple

clock = time.perf_counter

PERIOD_S = 0.010
REPORT_OVER_MS = 20.0       # pauses and late wake-ups listed one by one


class GcPauses:
    def __init__(self):
        self.events: List[Tuple[float, float, int]] = []   # begin, ms, gen
        self._begin = 0.0

    def _on(self, phase, info):
        if phase == "start":
            self._begin = clock()
        else:
            self.events.append((self._begin,
                                (clock() - self._begin) * 1000.0,
                                int(info.get("generation", -1))))

    def start(self):
        gc.callbacks.append(self._on)

    def stop(self):
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)


class Heartbeat:
    def __init__(self):
        self.late: List[Tuple[float, float]] = []          # woke at, ms late
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-heartbeat")

    def _run(self):
        while not self._stop.is_set():
            asked = clock()
            time.sleep(PERIOD_S)
            now = clock()
            ms = (now - asked - PERIOD_S) * 1000.0
            if ms >= REPORT_OVER_MS:
                self.late.append((now, ms))

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2.0)


CHILD = r"""
import os, sys, time
period, over, parent = float(sys.argv[1]), float(sys.argv[2]), os.getppid()
while os.getppid() == parent:
    asked = time.time()
    time.sleep(period)
    now = time.time()
    ms = (now - asked - period) * 1000.0
    if ms >= over:
        print(repr(now), repr(ms), flush=True)
"""


class MachineBeat:
    """The same heartbeat in a process of its own, which never touches
    jax and shares nothing with this one but the machine: where both are
    late at once, the machine stood still and not this process."""

    def __init__(self):
        self.late: List[Tuple[float, float]] = []   # woke at (time.time), ms
        self._proc = None

    def start(self):
        self._proc = subprocess.Popen(
            [sys.executable, "-c", CHILD, str(PERIOD_S), str(REPORT_OVER_MS)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            stdin=subprocess.DEVNULL, text=True)

    def stop(self):
        if self._proc is None:
            return
        self._proc.terminate()
        try:
            out, _ = self._proc.communicate(timeout=5.0)
        except Exception:
            self._proc.kill()
            out, _ = self._proc.communicate()
        for line in (out or "").splitlines():
            at, ms = line.split()
            self.late.append((float(at), float(ms)))
        self._proc = None


@contextlib.contextmanager
def recording():
    """All three recorders around the traffic of a run; each is stopped,
    and the other process waited for, on every way out."""
    recorders = (GcPauses(), Heartbeat(), MachineBeat())
    started = []
    try:
        for r in recorders:
            r.start()
            started.append(r)
        yield recorders
    finally:
        for r in reversed(started):
            r.stop()


def summary(recorders, t0: float, seconds: float, wall_offset: float
            ) -> Dict[str, str]:
    """Three lines of text about [t0, t0 + seconds): times are seconds
    after t0.  ``wall_offset`` is ``time.time()`` minus this module's
    clock, which puts the other process's stamps on it."""
    pauses, beat, machine = recorders
    end = t0 + seconds
    ev = [e for e in pauses.events if t0 <= e[0] < end]
    by_gen: Dict[int, List[float]] = {}
    for _, ms, gen in ev:
        by_gen.setdefault(gen, []).append(ms)
    gens = ", ".join(f"gen {g}: {len(v)} taking {sum(v):.0f} ms, longest "
                     f"{max(v):.1f}" for g, v in sorted(by_gen.items()))
    long_gc = " ".join(f"{b - t0:.2f}s:{ms:.0f}ms:gen{g}"
                       for b, ms, g in ev if ms >= REPORT_OVER_MS)

    def lates(late):
        inside = [x for x in late if t0 <= x[0] < end]
        return (f"{len(inside)} times in the window, "
                f"{sum(ms for _, ms in inside):.0f} ms together (woke "
                f"at:late by): "
                + (" ".join(f"{w - t0:.2f}s:{ms:.0f}ms"
                            for w, ms in inside[:40]) or "none"))

    asked = (f"asking to sleep {PERIOD_S * 1000:.0f} ms woke "
             f"{REPORT_OVER_MS:.0f} ms late or more")
    return {
        "gc": f"collections in the window: {gens or 'none'}; over "
              f"{REPORT_OVER_MS:.0f} ms (begin:length:generation): "
              f"{long_gc or 'none'}",
        "heartbeat": f"a thread of this process {asked} "
                     + lates(beat.late),
        "machine": f"a process of its own {asked} "
                   + lates([(w - wall_offset, ms)
                            for w, ms in machine.late])}
