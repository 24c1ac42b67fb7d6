"""Outback store: extendible hashing directory + the resize protocol (§4.4).

The directory is the paper's additional hash layer (Fig. 7): ``2^global_depth``
entries, each pointing at one DMPH table (an ``OutbackShard``) with a local
depth.  A key routes by the low ``global_depth`` bits of a dedicated directory
hash.  When a table's overflow cache crosses ``s_slow`` the store *splits* it:

  1. PRE_RESIZE is broadcast to the shard's compute nodes (we count the
     messages and the one-sided RC setup exactly as §4.4 describes);
  2. a new pair of DMPH tables is rebuilt host-side from the live pairs —
     Get/Update keep being served from the stale table during the rebuild,
     Insert/Delete get FALSE'd and buffered (replayed after the swap);
  3. compute nodes fetch the new locator via simulated one-sided reads of the
     registered area ``(N_cNode, len, GlobalD, seeds, A, B)`` — we account the
     exact byte volume — and decrement ``N_cNode`` (FAA);
  4. the stale table is dropped and buffered mutations are replayed.

Wall-clock of step 2 is recorded so the Fig.-17 benchmark can report the
throughput dip during resizing.
"""

from __future__ import annotations

import copy
import dataclasses
import time

import numpy as np

from repro.core.cn_cache import CNKeyCache
from repro.core.hashing import hash64_32, split_u64, splitmix64
from repro.core.meter import CommMeter, MSG_BYTES
from repro.core.outback import (OutbackShard, cached_get, meter_cache_batch)

_DIR_SEED = 0xD14EC7


@dataclasses.dataclass
class ResizeEvent:
    step: int  # op index at which the resize happened
    table_keys: int
    rebuild_seconds: float
    locator_bytes: int  # one-sided fetch volume per compute node
    buffered_mutations: int


class OutbackStore:
    """Directory of Outback DMPH tables with runtime resizing."""

    def __init__(self, keys: np.ndarray, values: np.ndarray, *,
                 load_factor: float = 0.85, initial_depth: int = 0,
                 num_compute_nodes: int = 2, rng_seed: int = 0,
                 cn_cache_budget_bytes: int = 0, transport=None):
        self.load_factor = load_factor
        self.num_compute_nodes = num_compute_nodes
        self.global_depth = initial_depth
        self.rng_seed = rng_seed
        self.transport = transport  # optional repro.net.Transport, shared by
        self.meter = CommMeter()    # the directory meter and every table's
        self.meter.sink = transport
        self.resize_events: list[ResizeEvent] = []
        self._op_count = 0
        # Every compute node gets the same fixed cache budget; the store
        # models one CN's view (tables are shared, so one cache suffices).
        self.cn_cache = (CNKeyCache(cn_cache_budget_bytes)
                         if cn_cache_budget_bytes else None)
        # Externally-owned CN caches (repro.api middleware) that must see
        # the same split-time invalidation the internal cache gets.
        self._coherence_caches: list[CNKeyCache] = []

        keys = np.asarray(keys, dtype=np.uint64)
        values = np.asarray(values, dtype=np.uint64)
        dir_idx = self._dir_hash(keys) & np.uint64((1 << initial_depth) - 1)
        self.local_depth: list[int] = []
        tables: list[OutbackShard] = []
        for e in range(1 << initial_depth):
            m = dir_idx == e
            tables.append(OutbackShard(keys[m], values[m],
                                       load_factor=load_factor,
                                       rng_seed=rng_seed + e,
                                       transport=transport))
            self.local_depth.append(initial_depth)
        # directory[i] -> table index (tables may be shared across entries)
        self.directory = list(range(1 << initial_depth))
        self.tables = tables
        self._buffer: list = []
        self._open_split = None
        self._lease = None  # optional lease guard, pushed to every table
        # optional telemetry wire-sink factory (repro.obs): index -> sink,
        # re-applied to split successors and resynced tables so per-table
        # wire stats survive §4.4 splits and replica re-installs
        self._sink_factory = None

    # ------------------------------------------------------------- routing
    def _dir_hash(self, keys: np.ndarray) -> np.ndarray:
        lo, hi = split_u64(np.asarray(keys, dtype=np.uint64))
        return hash64_32(lo, hi, _DIR_SEED).astype(np.uint64)

    def _entry(self, key: int) -> int:
        h = int(self._dir_hash(np.uint64([key]))[0])
        return h & ((1 << self.global_depth) - 1)

    def _table(self, key: int) -> OutbackShard:
        return self.tables[self.directory[self._entry(key)]]

    # ------------------------------------------------------------ data ops
    def get(self, key: int):
        self._op_count += 1
        if self.cn_cache is None:
            return self._table(key).get(key)
        return cached_get(self.cn_cache, self.meter, key,
                          lambda k: self._table(k).get(k))

    def update(self, key: int, value: int) -> bool:
        self._op_count += 1
        ok = self._table(key).update(key, value)
        if ok and self.cn_cache is not None:
            self.cn_cache.note_update(key, value)
        return ok

    def delete(self, key: int) -> bool:
        self._op_count += 1
        t = self._table(key)
        if t.frozen:
            self._buffer.append(("delete", key, 0))
            return False
        ok = t.delete(key)
        if ok and self.cn_cache is not None:
            self.cn_cache.note_delete(key)
        return ok

    def insert(self, key: int, value: int) -> str:
        self._op_count += 1
        t = self._table(key)
        if t.frozen:
            # Paper: FALSE status; MN buffers and replays post-resize.
            self._buffer.append(("insert", key, value))
            self.meter.add(rts=1, req=MSG_BYTES, resp=8)
            return "frozen"
        case = t.insert(key, value)
        if self.cn_cache is not None:
            self.cn_cache.note_insert(key, value)
        if t.needs_resize() and self._open_split is None:
            self._split(self.directory[self._entry(key)])
        return case

    # ------------------------------------------------- batched write path
    # Mirrors the scalar ops lane-for-lane: vectorised directory routing,
    # per-table sub-batches served by the shard's batched protocol, frozen
    # tables buffering (with the same FALSE'd accounting), and the §4.4
    # split trigger evaluated between chunks (the scalar stream checks
    # after every insert; the chunk is the granularity a doorbell-batched
    # CN naturally observes).  The chunk never exceeds a third of the
    # table's overflow capacity, so a batch cannot sail from below the
    # ``s_slow`` trigger past the ``s_stop`` hard limit between two
    # checks.  After a split the remaining lanes re-route through the new
    # directory.

    SPLIT_CHECK_CHUNK = 256

    def _insert_chunk_len(self, table: OutbackShard) -> int:
        return max(1, min(self.SPLIT_CHECK_CHUNK,
                          int(0.35 * table.overflow.cap)))

    def _route_tables(self, keys: np.ndarray) -> np.ndarray:
        """Vectorised directory routing: key -> owning table index."""
        e = (self._dir_hash(keys)
             & np.uint64((1 << self.global_depth) - 1)).astype(np.int64)
        return np.asarray(self.directory, dtype=np.int64)[e]

    def insert_batch(self, keys: np.ndarray, values: np.ndarray) -> list[str]:
        keys = np.asarray(keys, dtype=np.uint64)
        values = np.asarray(values, dtype=np.uint64)
        n = int(keys.shape[0])
        self._op_count += n
        statuses: list[str | None] = [None] * n
        done = np.zeros(n, dtype=bool)
        while not bool(done.all()):
            remaining = np.nonzero(~done)[0]
            tbl = self._route_tables(keys[remaining])
            resized = False
            for t in np.unique(tbl):
                lanes = remaining[tbl == t]
                table = self.tables[int(t)]
                if table.frozen:
                    # Paper: FALSE status; MN buffers and replays post-resize.
                    for i in lanes:
                        self._buffer.append(("insert", int(keys[i]),
                                             int(values[i])))
                        statuses[i] = "frozen"
                    self.meter.add(int(lanes.size), rts=1, req=MSG_BYTES,
                                   resp=8)
                    done[lanes] = True
                    continue
                if table.needs_resize() and self._open_split is None:
                    self._split(int(t))
                    resized = True
                    break
                step = self._insert_chunk_len(table)
                for c0 in range(0, int(lanes.size), step):
                    chunk = lanes[c0:c0 + step]
                    cases = table.insert_batch(keys[chunk], values[chunk])
                    for i, case in zip(chunk, cases):
                        statuses[i] = case
                    done[chunk] = True
                    if self.cn_cache is not None:
                        for i in chunk:
                            self.cn_cache.note_insert(int(keys[i]),
                                                      int(values[i]))
                    if table.needs_resize() and self._open_split is None:
                        self._split(int(t))
                        resized = True
                        break
                if resized:
                    break  # directory changed: re-route the rest
        return statuses

    def update_batch(self, keys: np.ndarray, values: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.uint64)
        values = np.asarray(values, dtype=np.uint64)
        n = int(keys.shape[0])
        self._op_count += n
        ok = np.zeros(n, dtype=bool)
        tbl = self._route_tables(keys)
        for t in np.unique(tbl):
            m = tbl == t
            ok[m] = self.tables[int(t)].update_batch(keys[m], values[m])
        if self.cn_cache is not None:
            for i in np.nonzero(ok)[0]:
                self.cn_cache.note_update(int(keys[i]), int(values[i]))
        return ok

    def delete_batch(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.uint64)
        n = int(keys.shape[0])
        self._op_count += n
        ok = np.zeros(n, dtype=bool)
        tbl = self._route_tables(keys)
        for t in np.unique(tbl):
            m = tbl == t
            table = self.tables[int(t)]
            if table.frozen:
                for i in np.nonzero(m)[0]:
                    self._buffer.append(("delete", int(keys[i]), 0))
                continue
            ok[m] = table.delete_batch(keys[m])
        if self.cn_cache is not None:
            for i in np.nonzero(ok)[0]:
                self.cn_cache.note_delete(int(keys[i]))
        return ok

    def get_batch(self, keys: np.ndarray, xp=np, *,
                  resolve_makeup: bool | None = None):
        """Vectorised Get across the directory (single-table fast path).

        With a CN cache, hit lanes are answered locally and only misses are
        dispatched to the tables.  ``resolve_makeup`` mirrors
        ``OutbackShard.get_batch``: the default (``None``) resolves
        mismatched lanes through the host Makeup-Get only when a cache is
        attached (so the cache learns resolved truths); pass ``True`` to
        force the full §4.3.1 protocol on the cache-less path too (the
        ``repro.api`` adapters do when fronted by middleware)."""
        self._op_count += len(keys)
        if self.cn_cache is None:
            return self._get_batch_tables(np.asarray(keys, np.uint64), xp,
                                          resolve_makeup=bool(resolve_makeup))
        keys = np.asarray(keys, dtype=np.uint64)
        h_lo, h_hi = split_u64(keys)
        hit, neg, c_vlo, c_vhi = self.cn_cache.probe_batch(h_lo, h_hi)
        meter_cache_batch(self.meter, int(hit.sum()), int(neg.sum()))
        v_lo = np.asarray(c_vlo).copy()
        v_hi = np.asarray(c_vhi).copy()
        match = np.asarray(hit).copy()
        miss = ~hit & ~neg
        if miss.any():
            m_lo, m_hi, m_match = self._get_batch_tables(keys[miss], xp,
                                                         resolve_makeup=True)
            v_lo[miss] = np.asarray(m_lo)
            v_hi[miss] = np.asarray(m_hi)
            match[miss] = np.asarray(m_match)
        # full-batch observation: hit lanes keep their sketch counts and
        # CLOCK ref bits fresh, or the hot set would decay and churn
        self.cn_cache.observe_batch(h_lo, h_hi, v_lo, v_hi, match, hit, neg)
        return v_lo, v_hi, match

    def _get_batch_tables(self, keys: np.ndarray, xp=np,
                          resolve_makeup: bool = False):
        """Dispatch a key batch to the owning DMPH tables (the MN path)."""
        if len(self.tables) == 1:
            return self.tables[0].get_batch(keys, xp,
                                            resolve_makeup=resolve_makeup)
        idx = (self._dir_hash(keys) & np.uint64((1 << self.global_depth) - 1)).astype(np.int64)
        v_lo = np.zeros(keys.shape[0], np.uint32)
        v_hi = np.zeros(keys.shape[0], np.uint32)
        match = np.zeros(keys.shape[0], bool)
        tbl = np.asarray([self.directory[i] for i in idx], dtype=np.int64)
        for t in np.unique(tbl):
            m = tbl == t
            lo, hi, mt = self.tables[int(t)].get_batch(
                keys[m], xp, resolve_makeup=resolve_makeup)
            v_lo[m], v_hi[m], match[m] = np.asarray(lo), np.asarray(hi), np.asarray(mt)
        return v_lo, v_hi, match

    # -------------------------------------------------------------- resize
    def _split(self, t_idx: int) -> None:
        h = self.begin_split(t_idx)
        h.build()
        h.finish()

    def begin_split(self, t_idx: int) -> "SplitHandle":
        """Freeze the table and open a resize window (PRE_RESIZE phase).

        Benchmarks interleave data ops between ``begin_split`` and
        ``finish`` to reproduce the paper's throughput-during-resize study
        (Fig. 17): Gets/Updates keep hitting the stale table, Inserts/Deletes
        are FALSE'd and buffered.
        """
        if getattr(self, "_open_split", None) is not None:
            raise RuntimeError("a resize is already in flight")
        depth = self.local_depth[t_idx]
        if depth == self.global_depth:
            # Double the directory (paper Fig. 7, GlobalD += 1).
            self.directory = self.directory + list(self.directory)
            self.global_depth += 1
        # PRE_RESIZE broadcast + RC setup with every compute node.
        self.meter.add(self.num_compute_nodes, rts=1, req=MSG_BYTES, resp=8)
        if self.transport is not None:
            # the rebuild steals MN CPU share for its duration (§4.4) —
            # the simulator turns this into a throughput-dip window
            self.transport.mark_resize(self.tables[t_idx].n_keys)
        self.tables[t_idx].frozen = True
        self._buffer = []
        h = SplitHandle(self, t_idx, depth)
        self._open_split = h
        return h

    def _finish_split(self, h: "SplitHandle") -> None:
        t_idx, depth = h.t_idx, h.depth
        # One-sided locator fetch by every compute node (§4.4): polls of
        # (N_cNode, len), the bulk read, and the FAA decrement — RDMA READ
        # payloads, not RPC messages, so no message padding applies.
        per_cn = 0
        for t in (h.t_lo, h.t_hi):
            oth = t.cn.othello
            per_cn += (8 + 8 + 8 + t.cn.seeds.nbytes
                       + oth.words_a.nbytes + oth.words_b.nbytes)
        self.meter.add(self.num_compute_nodes, rts=3, req=16, resp=per_cn,
                       one_sided=True)

        # Swap directory pointers (successors inherit the lease guard
        # and, when telemetry is on, per-table wire sinks at their new
        # directory indices).
        h.t_lo.lease = h.t_hi.lease = self._lease
        self.tables.append(h.t_hi)
        hi_idx = len(self.tables) - 1
        self.tables[t_idx] = h.t_lo
        if self._sink_factory is not None:
            h.t_lo.meter.add_sink(self._sink_factory(t_idx))
            h.t_hi.meter.add_sink(self._sink_factory(hi_idx))
        self.local_depth[t_idx] = depth + 1
        self.local_depth.append(depth + 1)
        for e in range(len(self.directory)):
            if self.directory[e] == t_idx and (e >> depth) & 1:
                self.directory[e] = hi_idx

        # CN-cache coherence: entries filled from the stale table during the
        # resize window may be newer than the rebuilt tables (a §4.4 Update
        # races the snapshot), so drop everything now routed to either
        # successor — the same sync point at which CNs fetch the new locator.
        # Externally-bound caches (repro.api middleware) join the same sync.
        caches = [c for c in (self.cn_cache, *self._coherence_caches)
                  if c is not None]
        if caches:
            dir_mask = np.uint32((1 << self.global_depth) - 1)
            directory = np.asarray(self.directory, np.int64)

            def routed_to_successors(k_lo, k_hi):
                e = hash64_32(k_lo, k_hi, _DIR_SEED) & dir_mask
                t = directory[e.astype(np.int64)]
                return (t == t_idx) | (t == hi_idx)

            for c in caches:
                c.invalidate_where(routed_to_successors)

        buffered, self._buffer = self._buffer, []
        self._open_split = None
        self.resize_events.append(ResizeEvent(
            self._op_count, h.n_live, h.rebuild_seconds, per_cn, len(buffered)))
        for op, k, v in buffered:  # replay on the fresh tables
            if op == "insert":
                self.insert(k, v)
            else:
                self.delete(k)

    def bind_coherence_cache(self, cache: CNKeyCache) -> None:
        """Register an externally-owned CN cache (the ``repro.api`` stack's)
        for split-time invalidation, without routing any data path through
        it — the middleware owns probe/fill, the store owns the sync point."""
        self._coherence_caches.append(cache)

    # --------------------------------------------------------- replication
    def set_lease(self, lease) -> None:
        """Install a lease guard on every table, present and future.

        The guard's ``on_seed_refresh`` fires before any Makeup-Get seed
        refresh (``repro.core.outback``); split successors inherit it in
        ``_finish_split``.  ``None`` detaches."""
        self._lease = lease
        for t in self.tables:
            t.lease = lease

    # ----------------------------------------------------------- telemetry
    def bind_table_sinks(self, factory) -> None:
        """Attach a per-table telemetry wire sink, present and future.

        ``factory(table_index)`` must return an object implementing the
        meter-sink protocol (``on_meter_add``); it is applied to every
        current table's meter and — like :meth:`set_lease` — re-applied
        to §4.4 split successors (at the directory index they take) and
        to tables rebuilt by a replica resync.  Sinks are observers: the
        meters' accounting and the transport trace are byte-identical
        with or without them."""
        self._sink_factory = factory
        if factory is None:
            return
        seen = set()
        for i, t in enumerate(self.tables):
            if id(t) not in seen:  # a table may sit at several indices
                seen.add(id(t))
                t.meter.add_sink(factory(i))

    def mn_state(self) -> dict:
        """Deep-copied image of the whole directory store's MN half.

        Per-table ``OutbackShard.mn_state`` images plus the extendible-
        hashing directory, and a private locator copy per table so a
        restarted replica that slept through a §4.4 split can
        re-materialise the successor tables it never built.  Locator
        copies are CN-side bookkeeping: after a real split every CN
        refetches locators anyway (the one-sided fetch ``_finish_split``
        meters), so the resync wire cost — :meth:`mn_state_bytes` —
        charges only the memory-heavy MN half.
        """
        return {"global_depth": self.global_depth,
                "local_depth": list(self.local_depth),
                "directory": list(self.directory),
                "tables": [{"cn": copy.deepcopy(t.cn),
                            "mn": t.mn_state(),
                            "load_factor": t.load_factor}
                           for t in self.tables]}

    def install_mn_state(self, state: dict) -> None:
        """Overwrite this replica with another's :meth:`mn_state`.

        Matching table layouts install in place (the common crash-without-
        split case); a layout mismatch rebuilds the tables list from the
        shipped images.  Coherence-cache registrations and the lease guard
        survive either way."""
        same_layout = (
            len(state["tables"]) == len(self.tables)
            and state["global_depth"] == self.global_depth
            and all(st["mn"]["slots_lo"].shape == t.slots_lo.shape
                    for st, t in zip(state["tables"], self.tables)))
        if same_layout:
            for st, t in zip(state["tables"], self.tables):
                t.install_mn_state(st["mn"])
        else:
            self.tables = [
                OutbackShard._from_state(copy.deepcopy(st["cn"]), st["mn"],
                                         load_factor=st["load_factor"],
                                         transport=self.transport)
                for st in state["tables"]]
            for i, t in enumerate(self.tables):
                t.lease = self._lease
                if self._sink_factory is not None:
                    t.meter.add_sink(self._sink_factory(i))
        self.global_depth = int(state["global_depth"])
        self.local_depth = list(state["local_depth"])
        self.directory = list(state["directory"])
        self._open_split = None
        self._buffer = []

    def drop_device_copies(self) -> None:
        """Free every table's device copies (see ``OutbackShard``)."""
        for t in self.tables:
            t.drop_device_copies()

    def mn_state_bytes(self) -> int:
        """On-wire size of one replica resync (MN half only)."""
        seen, total = set(), 0
        for t in self.tables:
            if id(t) not in seen:
                seen.add(id(t))
                total += t.mn_state_bytes()
        return total

    # --------------------------------------------------------- accounting
    @property
    def n_keys(self) -> int:
        seen, total = set(), 0
        for t in self.tables:
            if id(t) not in seen:
                seen.add(id(t))
                total += t.n_keys
        return total

    def cn_memory_bytes(self) -> int:
        """Per-compute-node memory: every CN caches all live locators plus
        its (fixed-budget) hot-key cache."""
        seen, total = set(), 0
        for t in self.tables:
            if id(t) not in seen:
                seen.add(id(t))
                total += t.cn_memory_bytes()
        if self.cn_cache is not None:
            total += self.cn_cache.memory_bytes()
        return total

    def meter_total(self) -> CommMeter:
        m = CommMeter()
        m.merge(self.meter)
        seen = set()
        for t in self.tables:
            if id(t) not in seen:
                seen.add(id(t))
                m.merge(t.meter)
        return m


class SplitHandle:
    """An in-flight table split: freeze -> build -> finish (swap + replay)."""

    def __init__(self, store: OutbackStore, t_idx: int, depth: int):
        self.store, self.t_idx, self.depth = store, t_idx, depth
        self.t_lo = self.t_hi = None
        self.n_live = 0
        self.rebuild_seconds = 0.0

    def build(self) -> None:
        """Rebuild the two successor DMPH tables (the slow, host-side part —
        the paper measures ~3 s for 20M keys on a single MN thread)."""
        store, depth = self.store, self.depth
        table = store.tables[self.t_idx]
        t0 = time.perf_counter()
        keys, vals = table.live_pairs()
        side = (store._dir_hash(keys) >> np.uint64(depth)) & np.uint64(1) != 0
        # Extendible hashing (Fig. 7): each successor inherits the PARENT's
        # table geometry, so a split genuinely halves the load and buys real
        # insert headroom (content-sized successors re-trigger immediately).
        nb = table.cn.num_buckets
        self.t_lo = OutbackShard(keys[~side], vals[~side],
                                 load_factor=store.load_factor,
                                 num_buckets=nb,
                                 rng_seed=store.rng_seed + 101 * len(store.tables),
                                 transport=store.transport)
        self.t_hi = OutbackShard(keys[side], vals[side],
                                 load_factor=store.load_factor,
                                 num_buckets=nb,
                                 rng_seed=store.rng_seed + 101 * len(store.tables) + 1,
                                 transport=store.transport)
        self.n_live = int(keys.shape[0])
        self.rebuild_seconds = time.perf_counter() - t0

    def finish(self) -> None:
        self.store._finish_split(self)


def make_uniform_keys(n: int, seed: int = 1) -> np.ndarray:
    """Deterministic unique 64-bit key set (FB/OSM-style random IDs)."""
    keys = splitmix64(np.arange(1, int(n * 1.05) + 16, dtype=np.uint64) + np.uint64(seed << 32))
    keys = np.unique(keys)[:n]
    assert keys.shape[0] == n
    return keys
