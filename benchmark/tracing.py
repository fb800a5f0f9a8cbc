"""From the profiler's trace to device numbers.

``capture`` wraps ``jax.profiler`` around a few seconds of the steady
window; ``load`` turns the ``.xplane.pb`` it leaves into plain lists;
the functions below reduce those lists.  The same functions run on the
small recorded trace under ``tests/data`` (``tests/test_tracing.py``), so
every PR computes the same number in the same way.

Shape of a loaded trace::

    {"devices": {<device id>: {"ops": [[name, start_ns, dur_ns], ...],
                               "modules": [[name, start_ns, dur_ns], ...]}},
     "t_lo": <ns>, "t_hi": <ns>}

``ops`` are the events of a device plane's "XLA Ops" line (one per HLO
operation run; a ``while`` encloses its body's ops), ``modules`` those of
its "XLA Modules" line (one per program execution).
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Sequence[Any]          # [name, start_ns, dur_ns]

# Operations that only ENCLOSE others on the ops line: their time is their
# children's, so a per-operation table leaves them out.
WRAPPERS = ("while", "conditional", "call")


@contextlib.contextmanager
def capture():
    """Trace what runs inside the block; yields the directory the trace
    is written to (under TMPDIR, removed by ``discard``)."""
    import jax
    out = tempfile.mkdtemp(prefix="bench-trace-")
    opts = None
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host Python frames: not read
        opts.host_tracer_level = 1
    except Exception:
        opts = None
    if opts is not None:
        jax.profiler.start_trace(out, profiler_options=opts)
    else:
        jax.profiler.start_trace(out)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()


def discard(path: str) -> None:
    shutil.rmtree(path, ignore_errors=True)


def load(trace_dir: str) -> Dict[str, Any]:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: Dict[int, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if not m:
            continue
        dev: Dict[str, List[Event]] = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key is None:
                continue
            dev[key] = [[short_name(ev.name) if key == "ops" else ev.name,
                         int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
        devices[int(m.group(1))] = dev
    starts = [e[1] for d in devices.values() for k in d for e in d[k]]
    ends = [e[1] + e[2] for d in devices.values() for k in d for e in d[k]]
    return {"devices": devices,
            "t_lo": min(starts) if starts else 0,
            "t_hi": max(ends) if ends else 0}


def short_name(hlo: str) -> str:
    """``%fusion.16 = (...) fusion(...), kind=kLoop`` -> ``fusion.16``; a
    custom call keeps its target: ``custom-call.3@tpu_custom_call``."""
    name = hlo.split(" = ")[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name}@{m.group(1)}" if m else name


# -- reductions ----------------------------------------------------------------

def union_ns(events: Iterable[Event], lo: Optional[int] = None,
             hi: Optional[int] = None) -> int:
    """Length of the union of the events' intervals, clipped to
    [lo, hi)."""
    spans = []
    for _, start, dur in events:
        a, b = start, start + dur
        if lo is not None:
            a = max(a, lo)
        if hi is not None:
            b = min(b, hi)
        if b > a:
            spans.append((a, b))
    spans.sort()
    total, cur_a, cur_b = 0, None, None
    for a, b in spans:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps_ns(events: Iterable[Event], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    """The idle intervals (start, length) inside [lo, hi): what the union
    of ``events`` leaves uncovered, longest first."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi)
    out, cursor = [], lo
    for a, b in spans:
        if a > cursor:
            out.append((cursor, a - cursor))
        cursor = max(cursor, b)
    if hi > cursor:
        out.append((cursor, hi - cursor))
    return sorted(out, key=lambda g: -g[1])


def is_wrapper(name: str) -> bool:
    base = name.lstrip("%")
    return base.startswith(WRAPPERS)


def module_kind(module: Event, whiles: Sequence[Event]) -> str:
    """What a program execution was, from the loops inside it: the decode
    tick scans its steps and, inside each, the layers (two nested
    ``while``s); a prefill program scans the layers once; anything
    without a loop (block copies, writers, samplers) is ``other``."""
    _, start, dur = module
    mine = [w for w in whiles if w[1] >= start and w[1] + w[2] <= start + dur]
    depth = 0
    for w in mine:
        depth = max(depth, sum(1 for o in mine
                               if o[1] <= w[1] and o[1] + o[2] >= w[1] + w[2]))
    return {0: "other", 1: "prefill"}.get(depth, "decode")


def classify(dev: Dict[str, List[Event]], min_ns: int = 100_000
             ) -> List[Tuple[str, int, int]]:
    """(kind, start_ns, dur_ns) of every program execution of at least
    ``min_ns`` on one device."""
    whiles = [e for e in dev["ops"] if e[0].startswith("while")]
    return [(module_kind(m, whiles), m[1], m[2]) for m in dev["modules"]
            if m[2] >= min_ns]
