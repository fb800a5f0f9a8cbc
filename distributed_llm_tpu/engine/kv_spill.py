"""Hierarchical KV: a host-RAM spill tier under the device prefix cache.

The effective KV universe used to end at ``kv_pool_blocks`` of device
memory: at a session population larger than the pool, parked prefixes
were evicted long before they were re-hit, so the shared-prefix dedup
(PR 10) and prefix-affinity routing (PR 12) decayed to cold prefills
exactly when traffic got production-shaped.  This module adds the tier
below: when the device prefix cache evicts an unpinned, sole-owner
entry, the engine DEMOTES it here — blocks are snapshot off the pool
with an async device gather (engine/paged_kv.py ``gather_blocks``) and
freed immediately (the functional snapshot owns its data); the
device→host pull then drains on the COPIER WORKER below, off the tick
path, into host buffers bounded by a ``host_kv_bytes`` budget with its
own LRU.  A later prompt that extends a demoted prefix PROMOTES it: the
admission becomes an in-flight chunked prefill whose leading blocks are
satisfied by host→device copies instead of compute, granted per tick
under the same budget as chunk grants (engine/batching.py
``_advance_promotion``), and if promotion loses the race — entry
invalidated, copier never landed, blocks starved, engine draining — the
request falls back to a cold prefill with byte-identical greedy output.

Copy correctness is layout-exact: demote gathers the pool's own
``[L, N_kv, nb, bs, D]`` tiles (int8 scales included) and promote
scatters them back bit-identically, so a promoted prefix serves decode
exactly like one that never left the pool.

Concurrency model (mirrors the engine's single-writer discipline):

- the SCHEDULER thread calls ``accepts``/``offer`` (demote),
  ``claim``/``release``/``entry_state`` (promote) and ``peek`` —
  list/state mutations take the store lock;
- the COPIER thread (daemon, lazily started) performs the only
  device→host syncs (``jax.device_get`` of demote snapshots) — the
  ``transfer-sync-spill`` lint rule makes this the ONLY sanctioned
  pool-data crossing; serving threads read ``stats``/``peek`` under the
  same lock;
- host-LRU eviction NEVER drops an entry with a promotion in flight
  (``pins > 0``), and invalidation marks entries DEAD in place so an
  in-flight promotion observes the race instead of reading freed
  buffers.

``pause``/``resume`` are test/bench hooks that hold the copier before
its next job — the deterministic way to pin the hit-during-demotion and
promotion-race fallbacks.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

COPYING = "copying"      # demote snapshot queued/draining to host
RESIDENT = "resident"    # host tiles landed; promotable
DEAD = "dead"            # invalidated/evicted; promotions must abort


class HostEntry:
    """One demoted prefix: token ids + host K/V tiles for ``nb`` blocks.

    ``tiles`` is None until the copier lands the snapshot (state
    COPYING); ``pins`` counts promotions in flight — a pinned entry is
    exempt from host-LRU eviction (dropping buffers a promotion is
    mid-copy from would hand the slot garbage KV)."""

    __slots__ = ("ids", "nb", "nbytes", "state", "pins", "tiles")

    def __init__(self, ids: Tuple[int, ...], nb: int, nbytes: int):
        self.ids = ids
        self.nb = nb
        self.nbytes = nbytes
        self.state = COPYING
        self.pins = 0
        # Host tiles in pool layout; promote grants slice [:, lo:hi]
        # views off a LOCAL reference (a concurrent invalidation nulls
        # this field — engine/batching.py snapshots it with the state
        # check).
        self.tiles: Optional[Dict[str, np.ndarray]] = None


class HostKVSpill:
    """Budgeted host-RAM LRU of demoted prefix KV for ONE engine."""

    def __init__(self, budget_bytes: int, block_bytes: int,
                 copier_depth: int = 8, min_prefix: int = 4,
                 tier: str = ""):
        self.budget_bytes = max(0, int(budget_bytes))
        self.block_bytes = max(1, int(block_bytes))
        self.min_prefix = min_prefix
        self.tier = tier
        self._lock = threading.Lock()
        self._entries: List[HostEntry] = []     # LRU order: oldest first
        self._bytes = 0
        self._jobs: "queue.Queue" = queue.Queue(
            maxsize=max(1, int(copier_depth)))
        self._copier: Optional[threading.Thread] = None
        self._stopping = threading.Event()
        self._paused = threading.Event()        # test/bench hook
        # Counters (store lock): the kv_stats / metrics source of truth.
        self.demotions_total = 0                # host copies LANDED
        self.demotions_dropped = 0              # offer rejected / died mid-copy
        self.promotions_total = 0               # promotions completed
        self.promotion_races_total = 0          # promotions lost the race
        self.evictions_total = 0                # host-LRU drops
        self.host_hits = 0
        self.host_misses = 0

    # -- demote (scheduler thread) -----------------------------------------

    def accepts(self, nbytes: int) -> bool:
        """Whether ``offer`` could hold an ``nbytes`` entry right now
        (evicting unpinned LRU entries counts as room).  Advisory — the
        engine checks BEFORE paying for the device gather."""
        if self._stopping.is_set() or nbytes > self.budget_bytes:
            return False
        with self._lock:
            reclaimable = sum(e.nbytes for e in self._entries
                              if e.pins == 0)
            return self._bytes - reclaimable + nbytes <= self.budget_bytes

    def _reserve(self, entry: "HostEntry", nbytes: int) -> bool:
        """Make room for ``entry`` and register it — all or nothing.

        Two kill sets, PLANNED before anything is touched: entries the
        new one extends (or duplicates) — the device cache's put() rule,
        without which the promote → re-park → evict → demote cycle would
        accumulate a stale shorter copy per session and halve the
        budget's reach — and unpinned LRU victims evicted to fit.
        Entries with a promotion in flight stay (the promotion reads
        their buffers).  When even evicting every unpinned entry cannot
        fit the newcomer, NOTHING is destroyed: returning False with a
        dead twin would trade a promotable resident entry for nothing
        (the destroy-then-fail bug this helper exists to prevent)."""
        with self._lock:
            ids_t = entry.ids
            twins = [e for e in self._entries
                     if (e.pins == 0 and e.state is not DEAD
                         and ids_t[:len(e.ids)] == e.ids)]
            avail = self._bytes - sum(e.nbytes for e in twins)
            victims = []
            if avail + nbytes > self.budget_bytes:
                for e in self._entries:
                    if e.pins != 0 or e in twins:
                        continue
                    victims.append(e)
                    avail -= e.nbytes
                    if avail + nbytes <= self.budget_bytes:
                        break
                if avail + nbytes > self.budget_bytes:
                    return False          # everything pinned: no room
            for e in twins:
                e.state = DEAD
                e.tiles = None
                self._entries.remove(e)
                self._bytes -= e.nbytes
            for e in victims:
                e.state = DEAD
                e.tiles = None
                self._entries.remove(e)
                self._bytes -= e.nbytes
                self.evictions_total += 1
            self._bytes += nbytes
            self._entries.append(entry)
            return True

    def offer(self, ids: Sequence[int], dev_tiles: Any, nbytes: int,
              nb: int) -> bool:
        """Register a demotion: reserve budget (evicting unpinned LRU
        entries to fit — never one with a promotion in flight) and queue
        the device snapshot for the copier.  False = could not take it
        (budget/queue pressure); the caller loses nothing — the blocks
        were freed at gather time and the snapshot is garbage-collected."""
        if self._stopping.is_set() or nbytes > self.budget_bytes:
            return False
        entry = HostEntry(tuple(ids), nb, int(nbytes))
        if not self._reserve(entry, int(nbytes)):
            with self._lock:
                self.demotions_dropped += 1
            return False                  # everything pinned: no room
        try:
            self._jobs.put_nowait((entry, dev_tiles))
        except queue.Full:
            with self._lock:
                entry.state = DEAD
                if entry in self._entries:
                    self._entries.remove(entry)
                self._bytes -= nbytes
                self.demotions_dropped += 1
            return False
        self._ensure_copier()
        return True

    # -- scale-down handoff (serving/replicas.py scale_to) ------------------

    def export_resident(self) -> List[Tuple[Tuple[int, ...],
                                            Dict[str, np.ndarray], int, int]]:
        """Snapshot of RESIDENT, unpinned entries as ``(ids, tiles,
        nbytes, nb)`` tuples — the read side of the scale-down handoff:
        a retiring replica's landed spill entries move WHOLE into a
        survivor's store via ``admit_resident``.  Tiles are host arrays
        already in pool layout, identical across same-config replicas,
        so adoption is a reference move, not a copy.  Pinned or
        still-COPYING entries stay behind (the caller flushes first, so
        COPYING here means the copy failed or never ran)."""
        with self._lock:
            return [(e.ids, e.tiles, e.nbytes, e.nb)
                    for e in self._entries
                    if e.state is RESIDENT and e.pins == 0
                    and e.tiles is not None]

    def admit_resident(self, ids: Sequence[int],
                       tiles: Dict[str, np.ndarray], nbytes: int,
                       nb: int) -> bool:
        """Register an ALREADY-host-resident entry (the write side of
        the scale-down handoff): same extend-replacement and
        LRU-evict-to-fit rules as ``offer``, but no copier job — the
        entry is promotable the moment this returns.  False = no room
        (budget smaller than the entry, or everything pinned)."""
        nbytes = int(nbytes)
        if (self._stopping.is_set() or tiles is None
                or nbytes > self.budget_bytes):
            return False
        entry = HostEntry(tuple(ids), int(nb), nbytes)
        # RESIDENT before publication: _reserve appends under the lock,
        # and the entry must never be observable in a COPYING limbo a
        # concurrent offer()'s twin-kill could reap.
        entry.tiles = dict(tiles)
        entry.state = RESIDENT
        if not self._reserve(entry, nbytes):
            return False
        with self._lock:
            self.demotions_total += 1
        self._mirror_counter("kv_demotions")
        return True

    # -- copier worker (the one sanctioned device→host crossing) -----------

    def _ensure_copier(self) -> None:
        t = self._copier
        if t is not None and t.is_alive():
            return
        with self._lock:
            t = self._copier
            if t is not None and t.is_alive():
                return
            self._copier = threading.Thread(
                target=self._copier_loop, daemon=True,
                name=f"kv-spill-copier-{self.tier}")
            self._copier.start()

    def _copier_loop(self) -> None:
        import jax
        while True:
            job = self._jobs.get()
            if job is None:                     # stop sentinel
                return
            while self._paused.is_set() and not self._stopping.is_set():
                time.sleep(0.002)               # test hook: hold the copy
            entry, dev_tiles = job
            try:
                host = {name: np.asarray(jax.device_get(arr))
                        for name, arr in dev_tiles.items()}
            except Exception:
                logger.exception("kv-spill copier: demote copy failed")
                host = None
            with self._lock:
                if entry.state is DEAD:
                    # Invalidated mid-copy (clear/eviction): budget was
                    # already released at invalidation time.
                    self.demotions_dropped += 1
                    continue
                if host is None:
                    # Copy failed: the entry must not sit in COPYING
                    # holding budget forever (flush/drain wait on it,
                    # promotions would stall against it).
                    entry.state = DEAD
                    if entry in self._entries:
                        self._entries.remove(entry)
                    self._bytes -= entry.nbytes
                    self.demotions_dropped += 1
                    continue
                entry.tiles = host
                entry.state = RESIDENT
                self.demotions_total += 1
            self._mirror_counter("kv_demotions")

    # -- promote / probe ----------------------------------------------------

    def _best(self, ids: Sequence[int],
              max_len: Optional[int]) -> Tuple[int, int]:
        """(entry index, matched length) of the longest non-DEAD common
        prefix — the SAME longest-common-prefix policy as the device
        cache's ``_best_match`` (lock held by the caller)."""
        ids = tuple(ids)
        cap = len(ids) - 1
        if max_len is not None:
            cap = min(cap, max_len)
        best_i, best_len = -1, 0
        for i, e in enumerate(self._entries):
            if e.state is DEAD:
                continue
            bound = min(len(e.ids), cap)
            if bound < max(self.min_prefix, best_len + 1):
                continue
            if e.ids[:bound] == ids[:bound]:
                m = bound
            else:
                m = 0
                for x, y in zip(e.ids[:bound], ids[:bound]):
                    if x != y:
                        break
                    m += 1
            if m >= max(self.min_prefix, best_len + 1):
                best_i, best_len = i, m
        return best_i, best_len

    def claim(self, ids: Sequence[int],
              max_len: Optional[int] = None
              ) -> Optional[Tuple[HostEntry, int]]:
        """Longest demoted prefix of ``ids``, PINNED for a promotion
        (LRU-touched; COPYING entries are claimable — the promotion
        waits the copier out, the hit-during-demotion race).  The caller
        pairs every claim with exactly one ``release``."""
        with self._lock:
            best_i, m = self._best(ids, max_len)
            if best_i < 0:
                self.host_misses += 1
                return None
            entry = self._entries.pop(best_i)
            self._entries.append(entry)
            entry.pins += 1
            self.host_hits += 1
            return entry, m

    def release(self, entry: HostEntry, promoted: bool,
                race: bool = False) -> None:
        """End of a promotion attempt: unpin; account the outcome
        (``promoted`` = the blocks landed and the slot went live on
        them; ``race`` = the fallback-to-cold contract fired)."""
        with self._lock:
            entry.pins = max(0, entry.pins - 1)
            if promoted:
                self.promotions_total += 1
            elif race:
                self.promotion_races_total += 1
        if promoted:
            self._mirror_counter("kv_promotions")
        elif race:
            self._mirror_counter("kv_promotion_races")

    def entry_state(self, entry: HostEntry) -> str:
        return entry.state                       # single-word GIL read

    def peek(self, ids: Sequence[int],
             max_len: Optional[int] = None) -> int:
        """Longest demoted-prefix match with NO pin, NO LRU touch and NO
        hit/miss accounting — the affinity probe (serving/replicas.py
        treats a replica's demoted entries as affinity-eligible so a
        session follows its spilled prefix home)."""
        with self._lock:
            _, m = self._best(ids, max_len)
        return m

    # -- invalidation / lifecycle -------------------------------------------

    def clear(self) -> None:
        """Invalidate everything: entries go DEAD in place (an in-flight
        promotion observes the race through ``entry_state``), buffers
        drop, budget zeroes."""
        with self._lock:
            for e in self._entries:
                e.state = DEAD
                e.tiles = None
            self._entries = []
            self._bytes = 0

    def pending(self) -> int:
        """Demote copies not yet landed — what drain/stop wait out.
        (COPYING covers queued jobs too: an entry leaves the state only
        when its copy lands or it dies.)"""
        with self._lock:
            return sum(1 for e in self._entries if e.state is COPYING)

    def flush(self, timeout_s: float = 5.0) -> bool:
        """Wait (bounded) for every queued demote copy to land."""
        deadline = time.monotonic() + timeout_s
        while self.pending() > 0:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.002)
        return True

    def stop(self, timeout_s: float = 5.0) -> None:
        """Drain in-flight copies (bounded — drain waits out the
        copier), then stop the worker.  Idempotent."""
        self.flush(timeout_s)
        self._stopping.set()
        t = self._copier
        if t is not None and t.is_alive():
            try:
                self._jobs.put_nowait(None)
            except queue.Full:
                pass
            t.join(timeout=timeout_s)

    # -- test/bench hooks ---------------------------------------------------

    def pause(self) -> None:
        """Hold the copier before its next job (deterministic
        hit-during-demotion / race-fallback tests)."""
        self._paused.set()

    def resume(self) -> None:
        self._paused.clear()

    # -- observability ------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        with self._lock:
            resident = sum(1 for e in self._entries
                           if e.state is RESIDENT)
            copying = sum(1 for e in self._entries
                          if e.state is COPYING)
            blocks = sum(e.nb for e in self._entries)
            return {
                "entries": len(self._entries),
                "resident_entries": resident,
                "copying_entries": copying,
                "blocks": blocks,
                "bytes": self._bytes,
                "budget_bytes": self.budget_bytes,
                "pinned_entries": sum(1 for e in self._entries
                                      if e.pins > 0),
                "demotions_total": self.demotions_total,
                "demotions_dropped": self.demotions_dropped,
                "promotions_total": self.promotions_total,
                "promotion_races_total": self.promotion_races_total,
                "evictions_total": self.evictions_total,
                "host_hits": self.host_hits,
                "host_misses": self.host_misses,
                "copy_queue_depth": self._jobs.qsize(),
            }

    def _mirror_counter(self, name: str) -> None:
        """Mirror one event to the process-global metric registry (same
        no-injection pattern as the engine's preemption counter)."""
        try:
            from ..obs import get_observability
            getattr(get_observability().m, name).labels(self.tier).inc()
        except Exception:
            pass
