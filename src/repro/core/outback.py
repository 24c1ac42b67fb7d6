"""Outback's decoupled DMPH index — the paper's core contribution (§4).

One ``OutbackShard`` is the paper's (compute-shard, memory-node) pair:

* **CN component** (compute-heavy, memory-light): ``LudoCN`` — Othello bucket
  locator + per-bucket seeds.  All Get-path compute happens here: 2 Othello
  hashes + 2 candidate-bucket hashes + 1 seeded slot hash.
* **MN component** (memory-heavy, compute-light): the DMPH slot table
  (packed 64-bit slots: cache/fp/len/addr — Fig. 5), the latest seeds array,
  the overflow cache, and the KV heap.  On the Get fast path the MN performs
  *zero* hash/compare work: one slot read + one heap read, both pure
  dereferences — this is the property the whole paper is built on.

Protocols implemented exactly as §4.3:
  Get (1 RT; CN full-key check; Makeup-Get with ind_slot = -1 on mismatch),
  Insert (3 cases: free slot / MN re-seed + seed propagation / overflow
  cache + cache bit), Update/Delete (fingerprint short-circuit + full-key
  verify, cache-bit redirect to the overflow cache), and the s_slow/s_stop
  thresholds that arm index resizing (``repro.core.resize``).

Batched device paths (`get_batch`, `update_batch`, `insert_batch` fast case)
are jit-compatible: CN math is vectorised; MN work is pure gathers — the
communication seam between the two is where the sharded engine
(``repro.core.sharded_kvs``) places its single all_to_all pair.

An optional CN-side hot-key cache (``repro.core.cn_cache``) sits in front
of the round trip: pass ``cn_cache=CNKeyCache(budget)`` and Gets consult it
first (answering skewed-workload hits locally), while Update/Delete/Insert
keep it coherent.  ``cn_cache=None`` (default) is byte-for-byte the plain
protocol.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import ludo, slots
from repro.core.cn_cache import CNKeyCache
from repro.core.hashing import (fingerprint6, fingerprint6_int, slot_hash,
                                slot_hash_int, split_u64)
from repro.core.meter import MSG_BYTES, CommMeter
from repro.core.overflow import OverflowCache
from repro.obs import wall

GET_REQ_BYTES = 8  # ind_bucket + ind_slot, packed (padded to MSG_BYTES on wire)
KV_BLOCK_BYTES = 32  # klen(8)+vlen(8)+key(8)+value(8) — the paper's workloads


class ShardFullError(RuntimeError):
    pass


# What one CN-cache answer saves on the wire: a positive hit skips the 1-RT
# Get; a negative hit skips the full 2-RT miss-plus-makeup route.  Shared by
# every cache front (shard, store) so the accounting cannot diverge.
# Both directions of an RPC message are padded to MSG_BYTES (paper §5.1),
# so the saved response is the padded message, not the raw KV block.
CACHE_HIT_SAVINGS = dict(saved_rts=1, saved_req=MSG_BYTES,
                         saved_resp=MSG_BYTES)
CACHE_NEG_SAVINGS = dict(saved_rts=2, saved_req=2 * MSG_BYTES,
                         saved_resp=2 * MSG_BYTES)


def cached_get(cache, meter, key: int, mn_get):
    """Front a scalar Get with a CN cache: probe, account, fall through to
    ``mn_get(key)`` on a miss and offer the result for admission."""
    state, val = cache.lookup(key)
    if state == "hit":
        meter.add_cache_hit(1, **CACHE_HIT_SAVINGS)
        return GetResult(val, 0, False)
    if state == "neg":
        meter.add_cache_hit(1, neg=True, **CACHE_NEG_SAVINGS)
        return GetResult(None, 0, False)
    res = mn_get(key)
    cache.fill(key, res.value)
    return res


def meter_cache_batch(meter, n_hit: int, n_neg: int) -> None:
    """Account a batched probe's hit/neg lanes (same savings as scalar)."""
    meter.add_cache_hit(n_hit, **CACHE_HIT_SAVINGS)
    meter.add_cache_hit(n_neg, neg=True, **CACHE_NEG_SAVINGS)


@dataclasses.dataclass
class GetResult:
    value: int | None
    round_trips: int
    makeup: bool


class OutbackShard:
    """One shard: CN view + MN state + the RDMA-RPC protocol between them."""

    def __init__(self, keys: np.ndarray, values: np.ndarray, *,
                 load_factor: float = 0.95, heap_slack: float = 1.30,
                 overflow_frac: float = 0.08, rng_seed: int = 0,
                 num_buckets: int | None = None, oth_ma: int | None = None,
                 oth_mb: int | None = None, heap_cap: int | None = None,
                 cn_cache: CNKeyCache | None = None, transport=None):
        keys = np.asarray(keys, dtype=np.uint64)
        values = np.asarray(values, dtype=np.uint64)
        n = keys.shape[0]
        lo, hi = split_u64(keys)
        build = ludo.build(lo, hi, load_factor=load_factor, rng_seed=rng_seed,
                           num_buckets=num_buckets, oth_ma=oth_ma, oth_mb=oth_mb)
        self.load_factor = load_factor
        self._dev: dict[str, tuple] = {}  # device copies, see _device_copy
        self.cn = build.cn  # CN-cached locator+seeds (the decoupled half)
        nb = build.cn.num_buckets

        # ---- memory node state ----
        self.slots_lo = np.zeros((nb, 4), dtype=np.uint32)
        self.slots_hi = np.zeros((nb, 4), dtype=np.uint32)
        self.seeds_mn = build.cn.seeds.copy()  # MN keeps the latest seeds
        if heap_cap is None:
            heap_cap = max(16, int(np.ceil(n * heap_slack)) + 64)
        self.heap_klo = np.zeros(heap_cap, dtype=np.uint32)
        self.heap_khi = np.zeros(heap_cap, dtype=np.uint32)
        self.heap_vlo = np.zeros(heap_cap, dtype=np.uint32)
        self.heap_vhi = np.zeros(heap_cap, dtype=np.uint32)
        self.heap_top = 0
        self.overflow = OverflowCache(max(64, int(n * overflow_frac)))
        self.meter = CommMeter()
        # optional repro.net.Transport: meter events double as timed-op trace
        self.meter.sink = transport
        self.frozen = False  # resize in progress: inserts/deletes rejected
        self.cn_cache = cn_cache  # optional CN-side hot-key cache
        # optional lease guard (repro.api.replication.ShardLease): consulted
        # before a Makeup-Get refreshes CN-cached seeds from MN state — the
        # CN may only trust fresh MN state under a live lease.  None (the
        # default) leaves every path byte-identical.
        self.lease = None

        # Bulk-populate from the build assignment.
        vlo, vhi = split_u64(values)
        addrs = self._heap_alloc_bulk(lo, hi, vlo, vhi)
        fp = fingerprint6(lo, hi)
        s_lo, s_hi = slots.pack(0, fp, KV_BLOCK_BYTES, addrs, 0)
        # Fallback keys carry a sentinel bucket (uint32 -1): mask them out of
        # the scatter — at tiny n (post-split tables) they are NOT rare.
        ok = np.ones(n, dtype=bool)
        ok[build.fallback] = False
        placed = build.bucket[ok].astype(np.int64)
        self.slots_lo[placed, build.slot[ok]] = s_lo[ok]
        self.slots_hi[placed, build.slot[ok]] = s_hi[ok]
        for i in build.fallback:
            self.overflow.insert(int(lo[i]), int(hi[i]), int(addrs[i]))
        self.n_keys = n

    # ------------------------------------------------------------------ heap
    def _heap_alloc_bulk(self, klo, khi, vlo, vhi) -> np.ndarray:
        n = klo.shape[0]
        if self.heap_top + n > self.heap_klo.shape[0]:
            self._heap_grow(self.heap_top + n)
        a = np.arange(self.heap_top, self.heap_top + n, dtype=np.uint32)
        self.heap_klo[a] = klo
        self.heap_khi[a] = khi
        self.heap_vlo[a] = vlo
        self.heap_vhi[a] = vhi
        self.heap_top += n
        return a

    def _heap_grow(self, need: int) -> None:
        cap = max(need, int(self.heap_klo.shape[0] * 1.5) + 64)
        for name in ("heap_klo", "heap_khi", "heap_vlo", "heap_vhi"):
            old = getattr(self, name)
            new = np.zeros(cap, dtype=np.uint32)
            new[: old.shape[0]] = old
            setattr(self, name, new)

    def _heap_write(self, lo, hi, vlo, vhi) -> int:
        if self.heap_top >= self.heap_klo.shape[0]:
            self._heap_grow(self.heap_top + 1)
        a = self.heap_top
        self.heap_klo[a], self.heap_khi[a] = lo, hi
        self.heap_vlo[a], self.heap_vhi[a] = vlo, vhi
        self.heap_top += 1
        return a

    # ------------------------------------------------------------- protocols
    def get(self, key: int) -> GetResult:
        """Get: CN cache first (0 RT on a hit), else the §4.3 protocol."""
        if self.cn_cache is None:
            return self._get_mn(key)
        return cached_get(self.cn_cache, self.meter, key, self._get_mn)

    def _get_mn(self, key: int) -> GetResult:
        """Single-op Get, exactly the paper's Fig. 6(a) message sequence."""
        lo, hi = int(key) & 0xFFFFFFFF, (int(key) >> 32) & 0xFFFFFFFF
        # CN: locator math (5 hashes), then ONE round trip carrying 8 bytes.
        b, s = self.cn.locate(np.uint32([lo]), np.uint32([hi]))
        b, s = int(b[0]), int(s[0])
        # The CN always inspects the returned block (one compare) — counted
        # up front so the scalar walk and ``get_batch`` meter identically.
        self.meter.add(rts=1, req=GET_REQ_BYTES, resp=KV_BLOCK_BYTES,
                       cn_hash=5, cn_cmp=1, mn_reads=2)
        # MN: pure dereference — slot, then heap block. No compute.
        f = slots.unpack(self.slots_lo[b, s], self.slots_hi[b, s])
        if int(f["len"]) != 0:
            addr = int(f["addr_lo"])
            k_lo, k_hi = int(self.heap_klo[addr]), int(self.heap_khi[addr])
            if (k_lo, k_hi) == (lo, hi):
                val = (int(self.heap_vhi[addr]) << 32) | int(self.heap_vlo[addr])
                return GetResult(val, 1, False)
        if int(f["cache"]) == 0 and int(f["len"]) != 0:
            # Mismatch without cache bit: key may still sit in another slot
            # after an MN re-seed the CN hasn't learned yet -> makeup.
            pass
        return self._makeup_get(lo, hi, b)

    def _makeup_get(self, lo: int, hi: int, bucket: int) -> GetResult:
        """Makeup Get (ind_slot = -1): MN searches overflow cache, then the
        bucket's (<=4) blocks; returns the fresh seed if it re-seeded."""
        addr, probes = self.overflow.lookup(lo, hi)
        self.meter.add(rts=1, req=GET_REQ_BYTES + 8, resp=KV_BLOCK_BYTES,
                       mn_hash=1, mn_cmp=probes, mn_reads=probes, cont=True)
        if addr is not None:
            val = (int(self.heap_vhi[addr]) << 32) | int(self.heap_vlo[addr])
            return GetResult(val, 2, True)
        for s in range(4):
            f = slots.unpack(self.slots_lo[bucket, s], self.slots_hi[bucket, s])
            if int(f["len"]) == 0:
                continue
            a = int(f["addr_lo"])
            self.meter.add(0, mn_cmp=1, mn_reads=2, attach=True)
            if (int(self.heap_klo[a]), int(self.heap_khi[a])) == (lo, hi):
                # Seed changed MN-side; CN refreshes its copy (paper §4.3.1)
                # — trusted only under a live MN lease (docs/FAILURE_MODEL.md).
                if self.lease is not None:
                    self.lease.on_seed_refresh(self)
                self._cn_written()
                self.cn.seeds[bucket] = self.seeds_mn[bucket]
                val = (int(self.heap_vhi[a]) << 32) | int(self.heap_vlo[a])
                return GetResult(val, 2, True)
        return GetResult(None, 2, True)

    def insert(self, key: int, value: int) -> str:
        """Insert; afterwards the key exists, so any negative-cache entry
        for it is cleared (and a resolved in-place update refreshed)."""
        case = self._insert_mn(key, value)
        if case != "frozen" and self.cn_cache is not None:
            self.cn_cache.note_insert(key, value)
        return case

    def _insert_mn(self, key: int, value: int) -> str:
        """Insert per §4.3.2. Returns the resolution case for accounting:
        'slot' | 'reseed' | 'overflow' | 'update' | 'frozen'."""
        if self.frozen:
            return "frozen"
        lo, hi = int(key) & 0xFFFFFFFF, (int(key) >> 32) & 0xFFFFFFFF
        # CN sends ind_bucket + full KV (not ind_slot: MN owns latest seeds).
        b_arr, _ = self.cn.locate(np.uint32([lo]), np.uint32([hi]))
        return self._insert_located(lo, hi, value, int(b_arr[0]))

    def _insert_located(self, lo: int, hi: int, value: int, b: int,
                        s: int | None = None, fp: int | None = None) -> str:
        """The MN half of Insert, after the CN locate.  ``insert_batch``
        precomputes ``s``/``fp`` vectorised; the scalar path derives them
        here — either way the protocol walk and accounting are this one
        code path."""
        self._mn_written()  # every outcome writes the heap (or grows it)
        self.meter.add(rts=1, req=8 + KV_BLOCK_BYTES, resp=8,
                       cn_hash=4, mn_hash=1, mn_writes=1)
        # MN: seeded slot with the *latest* seed.
        if s is None:
            s = slot_hash_int(lo, hi, int(self.seeds_mn[b]))
        f = slots.unpack(self.slots_lo[b, s], self.slots_hi[b, s])
        if fp is None:
            fp = fingerprint6_int(lo, hi)

        if int(f["len"]) != 0:
            # Occupied: fingerprint short-circuit, then full-key compare.
            self.meter.add(0, mn_cmp=1, attach=True)
            if int(f["fp"]) == fp:
                a = int(f["addr_lo"])
                self.meter.add(0, mn_cmp=1, mn_reads=1, attach=True)
                if (int(self.heap_klo[a]), int(self.heap_khi[a])) == (lo, hi):
                    # Resolves to Update (in place: fixed-size values).
                    self.heap_vlo[a] = value & 0xFFFFFFFF
                    self.heap_vhi[a] = (value >> 32) & 0xFFFFFFFF
                    return "update"

        # The key may already live in the overflow cache (spilled by an
        # earlier insert, possibly under a since-rotated seed): resolve to
        # Update there, or a re-insert would duplicate it — n_keys drifts
        # and Delete of the slot copy resurrects the overflow copy.
        addr0, probes = self.overflow.lookup(lo, hi)
        self.meter.add(0, mn_hash=1, mn_cmp=probes, mn_reads=probes, attach=True)
        if addr0 is not None:
            self.heap_vlo[addr0] = value & 0xFFFFFFFF
            self.heap_vhi[addr0] = (value >> 32) & 0xFFFFFFFF
            self.meter.add(0, mn_writes=1, attach=True)
            return "update"

        addr = self._heap_write(lo, hi, value & 0xFFFFFFFF, (value >> 32) & 0xFFFFFFFF)

        if int(f["len"]) == 0:  # case 1: free slot
            s_lo, s_hi = slots.pack(0, fp, KV_BLOCK_BYTES, addr, 0)
            self.slots_lo[b, s], self.slots_hi[b, s] = s_lo, s_hi
            self.n_keys += 1
            return "slot"

        # case 2: bucket has a free slot somewhere -> MN brute-forces a new
        # seed over existing keys + the new one, rewrites the bucket layout,
        # and returns the seed to the CN (which propagates it shard-wide).
        occ = [t for t in range(4)
               if int(slots.unpack_len(self.slots_hi[b, t])) != 0]
        if len(occ) < 4:
            addrs = [int(self.slots_lo[b, t]) for t in occ]
            k_lo = np.array([int(self.heap_klo[a]) for a in addrs] + [lo], np.uint32)
            k_hi = np.array([int(self.heap_khi[a]) for a in addrs] + [hi], np.uint32)
            self.meter.add(0, mn_reads=len(occ), attach=True)
            new_seed = ludo.find_bucket_seed(k_lo, k_hi)
            # Account the brute force: ~(tries x keys) hashes on the MN.
            self.meter.add(0, mn_hash=(new_seed + 1 if new_seed is not None
                                       else ludo.MAX_SEED) * len(k_lo), attach=True)
            if new_seed is not None:
                old_lo = self.slots_lo[b].copy()
                old_hi = self.slots_hi[b].copy()
                self.slots_lo[b] = 0
                self.slots_hi[b] = 0
                new_slots = slot_hash(k_lo, k_hi, np.uint32(new_seed))
                for i, t in enumerate(occ):  # move surviving slots
                    self.slots_lo[b, int(new_slots[i])] = old_lo[t]
                    self.slots_hi[b, int(new_slots[i])] = old_hi[t]
                s_lo, s_hi = slots.pack(0, fp, KV_BLOCK_BYTES, addr, 0)
                self.slots_lo[b, int(new_slots[-1])] = s_lo
                self.slots_hi[b, int(new_slots[-1])] = s_hi
                self.seeds_mn[b] = new_seed
                self._cn_written()
                self.cn.seeds[b] = new_seed  # returned in the RPC response
                self.n_keys += 1
                return "reseed"

        # case 3: all four slots taken -> overflow cache + cache bit.
        ok, probes = self.overflow.insert(lo, hi, addr)
        self.meter.add(0, mn_hash=1, mn_cmp=probes, mn_writes=1, attach=True)
        if not ok:
            raise ShardFullError("overflow cache full: s_stop breached")
        self.slots_hi[b, s] |= np.uint32(1 << slots.CACHE_SHIFT)
        self.n_keys += 1
        return "overflow"

    def update(self, key: int, value: int) -> bool:
        """Update; on success the CN cache entry is refreshed (coherence)."""
        ok = self._update_mn(key, value)
        if ok and self.cn_cache is not None:
            self.cn_cache.note_update(key, value)
        return ok

    def _update_mn(self, key: int, value: int) -> bool:
        """Update per §4.3.3 (1 RT; fp + full-key verify on the MN)."""
        lo, hi = int(key) & 0xFFFFFFFF, (int(key) >> 32) & 0xFFFFFFFF
        b_arr, s_arr = self.cn.locate(np.uint32([lo]), np.uint32([hi]))
        b, s = int(b_arr[0]), int(s_arr[0])
        self.meter.add(rts=1, req=8 + KV_BLOCK_BYTES, resp=8,
                       cn_hash=5, mn_reads=2, mn_cmp=1)
        f = slots.unpack(self.slots_lo[b, s], self.slots_hi[b, s])
        if int(f["len"]) != 0:
            a = int(f["addr_lo"])
            if (int(self.heap_klo[a]), int(self.heap_khi[a])) == (lo, hi):
                self._mn_written()
                self.heap_vlo[a] = value & 0xFFFFFFFF
                self.heap_vhi[a] = (value >> 32) & 0xFFFFFFFF
                self.meter.add(0, mn_writes=1, attach=True)
                return True
        if int(f["cache"]) == 1:  # redirect to overflow cache
            addr, probes = self.overflow.lookup(lo, hi)
            self.meter.add(0, mn_hash=1, mn_cmp=probes, mn_reads=probes, attach=True)
            if addr is not None:
                self._mn_written()
                self.heap_vlo[addr] = value & 0xFFFFFFFF
                self.heap_vhi[addr] = (value >> 32) & 0xFFFFFFFF
                self.meter.add(0, mn_writes=1, attach=True)
                return True
        # Stale CN seed: retry against every slot of the bucket (MN-side).
        for t in range(4):
            ft = slots.unpack(self.slots_lo[b, t], self.slots_hi[b, t])
            if int(ft["len"]) == 0 or t == s:
                continue
            a = int(ft["addr_lo"])
            self.meter.add(0, mn_cmp=1, mn_reads=1, attach=True)
            if (int(self.heap_klo[a]), int(self.heap_khi[a])) == (lo, hi):
                self._mn_written()
                self._cn_written()
                self.heap_vlo[a] = value & 0xFFFFFFFF
                self.heap_vhi[a] = (value >> 32) & 0xFFFFFFFF
                self.meter.add(0, mn_writes=1, attach=True)
                self.cn.seeds[b] = self.seeds_mn[b]
                return True
        return False

    def delete(self, key: int) -> bool:
        """Delete; on success the CN cache entry is dropped (coherence)."""
        ok = self._delete_mn(key)
        if ok and self.cn_cache is not None:
            self.cn_cache.note_delete(key)
        return ok

    def _delete_mn(self, key: int) -> bool:
        """Delete per §4.3.3: mark the slot length zero."""
        if self.frozen:
            return False
        lo, hi = int(key) & 0xFFFFFFFF, (int(key) >> 32) & 0xFFFFFFFF
        b_arr, s_arr = self.cn.locate(np.uint32([lo]), np.uint32([hi]))
        b, s = int(b_arr[0]), int(s_arr[0])
        self.meter.add(rts=1, req=8 + 8, resp=8, cn_hash=5,
                       mn_reads=2, mn_cmp=1)
        f = slots.unpack(self.slots_lo[b, s], self.slots_hi[b, s])
        if int(f["len"]) != 0:
            a = int(f["addr_lo"])
            if (int(self.heap_klo[a]), int(self.heap_khi[a])) == (lo, hi):
                cache_bit = np.uint32(int(f["cache"]) << slots.CACHE_SHIFT)
                self._mn_written()
                self.slots_lo[b, s] = 0
                self.slots_hi[b, s] = cache_bit  # keep cache hint
                self.meter.add(0, mn_writes=1, attach=True)
                self.n_keys -= 1
                return True
        ok, probes = self.overflow.delete(lo, hi)
        self.meter.add(0, mn_hash=1, mn_cmp=probes, mn_writes=1 if ok else 0, attach=True)
        if ok:
            self.n_keys -= 1
        return ok

    # --------------------------------------------------- batched write path
    # The batched mutations are *exact* vectorisations of the scalar §4.3
    # walks: the CN locate and the MN fast-path classification run as array
    # ops over the whole batch, lanes the fast path fully resolves are
    # applied with scatters, and every remaining lane falls through to the
    # scalar protocol walk (which meters itself).  Results, MN state, meter
    # totals and CN-cache state are identical to the scalar loop — tested
    # property-style in tests/test_write_batch_parity.py.  The transport
    # sink sees one doorbell-batched event per fast wave instead of one
    # event per op (same totals; that is the point of doorbell batching).

    def _locate_batch(self, keys: np.ndarray):
        keys = np.asarray(keys, dtype=np.uint64)
        lo, hi = split_u64(keys)
        b, s = self.cn.locate(lo, hi)
        return keys, lo, hi, b.astype(np.int64), s.astype(np.int64)

    def insert_batch(self, keys: np.ndarray, values: np.ndarray) -> list[str]:
        """Batched Insert: one status string per lane (§4.3.2 cases).

        The CN locate, MN slot hash and fingerprints are vectorised over
        the batch; the MN state machine itself (free slot / re-seed /
        overflow) runs per lane against live state, so intra-batch
        interactions — two lanes landing in one bucket, a re-seed moving a
        later lane's slot — resolve exactly as the scalar stream would.
        """
        keys = np.asarray(keys, dtype=np.uint64)
        values = np.asarray(values, dtype=np.uint64)
        n = int(keys.shape[0])
        if n == 0:
            return []
        if self.frozen:
            return ["frozen"] * n
        lo, hi = split_u64(keys)
        b_vec, _ = self.cn.locate(lo, hi)
        b_vec = b_vec.astype(np.int64)
        s_vec = slot_hash(lo, hi, self.seeds_mn[b_vec])
        fp_vec = fingerprint6(lo, hi)
        reseeded: set[int] = set()
        statuses: list[str] = []
        for i in range(n):
            b = int(b_vec[i])
            # a re-seed earlier in the batch rotated this bucket's seed:
            # the precomputed slot is stale, recompute against seeds_mn
            s = None if b in reseeded else int(s_vec[i])
            case = self._insert_located(int(lo[i]), int(hi[i]),
                                        int(values[i]), b, s=s,
                                        fp=int(fp_vec[i]))
            if case == "reseed":
                reseeded.add(b)
            statuses.append(case)
            if self.cn_cache is not None:
                self.cn_cache.note_insert(int(keys[i]), int(values[i]))
        return statuses

    def update_batch(self, keys: np.ndarray, values: np.ndarray) -> np.ndarray:
        """Batched Update (§4.3.3): returns the per-lane success mask.

        Fast lanes (full-key match at the located slot) are one gather +
        one scatter for the whole wave; mismatched lanes (overflow
        residents, stale CN seeds) take the scalar walk unchanged.
        """
        keys, lo, hi, b, s = self._locate_batch(keys)
        values = np.asarray(values, dtype=np.uint64)
        vlo, vhi = split_u64(values)
        s_hi = self.slots_hi[b, s]
        length = slots.unpack_len(s_hi)
        addr = slots.unpack_addr32(self.slots_lo[b, s], s_hi).astype(np.int64)
        fast = ((length != 0) & (self.heap_klo[addr] == lo)
                & (self.heap_khi[addr] == hi))
        ok = fast.copy()
        n_fast = int(fast.sum())
        if n_fast:
            self._mn_written()
            a = addr[fast]  # duplicate keys: last lane wins, as in order
            self.heap_vlo[a] = vlo[fast]
            self.heap_vhi[a] = vhi[fast]
            self.meter.add(n_fast, rts=1, req=8 + KV_BLOCK_BYTES, resp=8,
                           cn_hash=5, mn_reads=2, mn_cmp=1, mn_writes=1)
        for i in np.nonzero(~fast)[0]:
            ok[i] = self._update_mn(int(keys[i]), int(values[i]))
        if self.cn_cache is not None:
            for i in np.nonzero(ok)[0]:
                self.cn_cache.note_update(int(keys[i]), int(values[i]))
        return ok

    def delete_batch(self, keys: np.ndarray) -> np.ndarray:
        """Batched Delete (§4.3.3): returns the per-lane success mask.

        Fast lanes (first occurrence of a slot-resident key) clear their
        slots in one scatter, preserving the cache-hint bit; duplicates
        and non-residents take the scalar walk so repeat-deletes miss and
        overflow residents are removed exactly as the scalar stream does.
        """
        if self.frozen:
            return np.zeros(int(np.asarray(keys).shape[0]), dtype=bool)
        keys, lo, hi, b, s = self._locate_batch(keys)
        n = int(keys.shape[0])
        s_hi = self.slots_hi[b, s]
        length = slots.unpack_len(s_hi)
        addr = slots.unpack_addr32(self.slots_lo[b, s], s_hi).astype(np.int64)
        first = np.zeros(n, dtype=bool)
        first[np.unique(keys, return_index=True)[1]] = True
        fast = (first & (length != 0) & (self.heap_klo[addr] == lo)
                & (self.heap_khi[addr] == hi))
        ok = fast.copy()
        n_fast = int(fast.sum())
        if n_fast:
            self._mn_written()
            bf, sf = b[fast], s[fast]
            cache_bits = self.slots_hi[bf, sf] & np.uint32(1 << slots.CACHE_SHIFT)
            self.slots_lo[bf, sf] = 0
            self.slots_hi[bf, sf] = cache_bits  # keep cache hint
            self.meter.add(n_fast, rts=1, req=8 + 8, resp=8, cn_hash=5,
                           mn_reads=2, mn_cmp=1, mn_writes=1)
            self.n_keys -= n_fast
        for i in np.nonzero(~fast)[0]:
            ok[i] = self._delete_mn(int(keys[i]))
        if self.cn_cache is not None:
            for i in np.nonzero(ok)[0]:
                self.cn_cache.note_delete(int(keys[i]))
        return ok

    # ------------------------------------------------- batched (device) path
    def _cn_host(self) -> tuple:
        oth = self.cn.othello
        return oth.words_a, oth.words_b, self.cn.seeds

    def _mn_host(self) -> tuple:
        return (self.slots_lo, self.slots_hi, self.heap_klo, self.heap_khi,
                self.heap_vlo, self.heap_vhi)

    # The device copies of the two halves stay in HBM between device Gets
    # until a write changes their host arrays: each writer calls
    # _cn_written or _mn_written first, which drops the copy, so the next
    # device call uploads afresh and at most one copy of a half is kept.
    # Only cn.seeds changes the CN half (the Othello words are fixed at
    # build).  The heap helpers are reached only from the constructor
    # (before any copy) and _insert_located (which marks); the overflow
    # cache lives on the host and is no part of either half.
    def _cn_written(self) -> None:
        self._dev.pop("cn", None)

    def _mn_written(self) -> None:
        self._dev.pop("mn", None)

    def drop_device_copies(self) -> None:
        """Free both halves' device copies (a memory node that crashed
        lost its memory); the next device Get uploads them afresh."""
        self._dev.clear()

    def _device_copy(self, half: str, xp) -> tuple[tuple, int]:
        """(the ``half`` ("cn" or "mn") arrays in ``xp``, bytes handed to
        the device for them on this call)."""
        host = self._cn_host() if half == "cn" else self._mn_host()
        if xp is np:
            return host, 0
        if half in self._dev:
            return self._dev[half], 0
        kept = self._dev[half] = tuple(xp.asarray(a) for a in host)
        return kept, sum(a.nbytes for a in host)

    def cn_arrays(self, xp=np):
        """The CN-cached arrays, converted for the target namespace; on a
        device, the copy kept since the last write to them."""
        return self._device_copy("cn", xp)[0]

    def mn_arrays(self, xp=np):
        return self._device_copy("mn", xp)[0]

    def get_batch(self, keys: np.ndarray, xp=np, cn=None, mn=None,
                  resolve_makeup: bool | None = None):
        """Vectorised Get over a key batch.

        Returns (v_lo, v_hi, match).  Pure function of (cn, mn) arrays — pass
        device arrays + xp=jnp to run it jitted.  Mismatched lanes (stale
        seeds / overflow residents) are resolved by the host Makeup-Get when
        ``resolve_makeup`` is true — the default whenever a CN cache is
        attached, so the cache only ever learns resolved truths; pass
        ``resolve_makeup=False``/``True`` to override.

        With a CN cache attached, the batch is probed first: hit lanes are
        answered from the cache (no round trip is accounted for them) and
        the cache adapts from the observed miss results.
        """
        keys = np.asarray(keys, dtype=np.uint64)
        h_lo, h_hi = split_u64(keys)
        cn_sent = mn_sent = 0
        with wall.span(wall.GET_UPLOAD):
            d_lo, d_hi = xp.asarray(h_lo), xp.asarray(h_hi)
            if cn is None:
                cn, cn_sent = self._device_copy("cn", xp)
            if mn is None:
                mn, mn_sent = self._device_copy("mn", xp)
        if xp is not np:
            wall.count(wall.H2D_BYTES,
                       h_lo.nbytes + h_hi.nbytes + cn_sent + mn_sent)
            if mn_sent:
                wall.count(wall.MN_UPLOADS, 1)
        n = int(keys.shape[0])
        if resolve_makeup is None:
            resolve_makeup = self.cn_cache is not None
        if self.cn_cache is None:
            with wall.span(wall.GET_DISPATCH):
                out = outback_get_batch(d_lo, d_hi, cn, mn, self.cn.othello,
                                        self.cn.num_buckets, xp)
            self.meter.add(n, rts=1, req=GET_REQ_BYTES, resp=KV_BLOCK_BYTES,
                           cn_hash=5, cn_cmp=1, mn_reads=2)
            if resolve_makeup:
                out = self._resolve_makeups(keys, *out, xp=xp)
            return out
        # ---- CN-cache stage: hits never cross the wire -------------------
        hit, neg, c_vlo, c_vhi = self.cn_cache.probe_batch(h_lo, h_hi)
        n_hit, n_neg = int(hit.sum()), int(neg.sum())
        self.meter.add(n - n_hit - n_neg, rts=1, req=GET_REQ_BYTES,
                       resp=KV_BLOCK_BYTES, cn_hash=5, cn_cmp=1, mn_reads=2)
        meter_cache_batch(self.meter, n_hit, n_neg)
        miss = ~hit & ~neg
        if xp is np:
            # host path: only the misses touch the MN arrays
            v_lo, v_hi = c_vlo.copy(), c_vhi.copy()
            match = hit.copy()
            if miss.any():
                m_out = outback_get_batch(h_lo[miss], h_hi[miss], cn, mn,
                                          self.cn.othello,
                                          self.cn.num_buckets, np)
                if resolve_makeup:
                    m_out = self._resolve_makeups(keys[miss], *m_out, xp=np)
                v_lo[miss], v_hi[miss], match[miss] = m_out
            self.cn_cache.observe_batch(h_lo, h_hi, v_lo, v_hi, match,
                                        hit, neg)
            return v_lo, v_hi, match
        # device path: full-batch kernel keeps shapes static for jit; hit
        # lanes are merged on the host over the (discarded) MN result,
        # which the cache has to observe there anyway
        with wall.span(wall.GET_DISPATCH):
            v_lo, v_hi, match = outback_get_batch(
                d_lo, d_hi, cn, mn, self.cn.othello, self.cn.num_buckets, xp)
        if resolve_makeup:
            # only true misses take the makeup trip: cached and known-absent
            # lanes already have their answer
            v_lo, v_hi, match = self._resolve_makeups(
                keys, v_lo, v_hi, match, xp=xp, skip=hit | neg)
        with wall.device_span(wall.GET_FETCH, v_lo, v_hi, match):
            v_lo, v_hi, match = (np.asarray(v_lo), np.asarray(v_hi),
                                 np.asarray(match))
        self.cn_cache.observe_batch(h_lo, h_hi, v_lo, v_hi, match, hit, neg)
        return (np.where(hit, c_vlo, v_lo), np.where(hit, c_vhi, v_hi),
                hit | match)

    def _resolve_makeups(self, keys: np.ndarray, v_lo, v_hi, match, *,
                         xp=np, skip=None):
        """Host Makeup-Get for mismatched lanes of a batched Get (overflow
        residents / stale CN seeds) — the §4.3.1 ind_slot=-1 path.

        Vectorised end-to-end: one CN locate over all mismatched lanes,
        one batched overflow probe (``OverflowCache.lookup_batch``), and
        one (m, 4) bucket-slot scan replace the per-lane Python walks, so
        heavy overflow pressure (post-``s_slow``, pre-split) no longer
        drags the miss path through the interpreter.  The *accounting*
        stays a per-lane loop emitting exactly the meter events the scalar
        ``_makeup_get`` emits — same totals, same transport-trace
        continuation attachment — proven lane-identical against
        ``_resolve_makeups_reference`` in ``tests/test_makeup_batch.py``.
        """
        # one readback of the whole answer: the caller reads it on the host
        # anyway, so with no lane to resolve it gets the host arrays back
        with wall.device_span(wall.GET_FETCH, v_lo, v_hi, match):
            v_lo, v_hi, match = (np.asarray(v_lo), np.asarray(v_hi),
                                 np.asarray(match))
        pending = ~match
        if skip is not None:
            pending &= ~np.asarray(skip)
        idx = np.nonzero(pending)[0]
        wall.count(wall.MAKEUP_LANES, idx.size)
        if idx.size == 0:
            return v_lo, v_hi, match
        with wall.span(wall.GET_MAKEUP):
            v_lo, v_hi, match = self._makeup_lanes(keys, idx, v_lo.copy(),
                                                   v_hi.copy(), match.copy())
        with wall.span(wall.GET_UPLOAD):
            out = xp.asarray(v_lo), xp.asarray(v_hi), xp.asarray(match)
        if xp is not np:
            wall.count(wall.H2D_BYTES,
                       v_lo.nbytes + v_hi.nbytes + match.nbytes)
        return out

    def _makeup_lanes(self, keys: np.ndarray, idx: np.ndarray, v_lo, v_hi,
                      match):
        """Resolve lanes ``idx`` of a host answer in place and return it."""
        lo, hi = split_u64(np.asarray(keys, np.uint64)[idx])
        b, _ = self.cn.locate(lo, hi)
        b = b.astype(np.int64)
        o_addr, o_probes = self.overflow.lookup_batch(lo, hi)
        o_hit = o_addr >= 0
        # the bucket's (<=4) blocks, scanned only where the overflow missed
        s_hi = self.slots_hi[b]
        s_addr = slots.unpack_addr32(self.slots_lo[b], s_hi).astype(np.int64)
        nonempty = slots.unpack_len(s_hi) != 0
        s_match = (nonempty & (self.heap_klo[s_addr] == lo[:, None])
                   & (self.heap_khi[s_addr] == hi[:, None]))
        any_s = s_match.any(axis=1) & ~o_hit
        first = np.where(s_match.any(axis=1), np.argmax(s_match, axis=1), 4)
        # the scalar walk skips empty slots silently and stops at the
        # match, so it examines every non-empty slot up to (and incl.) it
        n_exam = (nonempty & (np.arange(4)[None, :] <= first[:, None])).sum(1)
        lanes = np.arange(idx.shape[0])
        res_addr = np.where(o_hit, o_addr,
                            s_addr[lanes, np.minimum(first, 3)])
        ok = o_hit | any_s
        for t in range(idx.shape[0]):
            self.meter.add(rts=1, req=GET_REQ_BYTES + 8, resp=KV_BLOCK_BYTES,
                           mn_hash=1, mn_cmp=int(o_probes[t]),
                           mn_reads=int(o_probes[t]), cont=True)
            if not o_hit[t]:
                for _ in range(int(n_exam[t])):
                    self.meter.add(0, mn_cmp=1, mn_reads=2, attach=True)
        if any_s.any():
            # seed changed MN-side; CN refreshes its copy (paper §4.3.1)
            # — trusted only under a live MN lease (docs/FAILURE_MODEL.md)
            if self.lease is not None:
                self.lease.on_seed_refresh(self)
            self._cn_written()
            bb = b[any_s]
            self.cn.seeds[bb] = self.seeds_mn[bb]
        hit_idx = idx[ok]
        a = res_addr[ok]
        v_lo[hit_idx] = self.heap_vlo[a]
        v_hi[hit_idx] = self.heap_vhi[a]
        match[hit_idx] = True
        return v_lo, v_hi, match

    def _resolve_makeups_reference(self, keys: np.ndarray, v_lo, v_hi, match,
                                   *, xp=np, skip=None):
        """The legacy per-lane Makeup-Get loop, kept as the parity twin
        the vectorised ``_resolve_makeups`` is tested against."""
        pending = ~np.asarray(match)
        if skip is not None:
            pending &= ~np.asarray(skip)
        idx = np.nonzero(pending)[0]
        if idx.size == 0:
            return v_lo, v_hi, match
        v_lo = np.asarray(v_lo).copy()
        v_hi = np.asarray(v_hi).copy()
        match = np.asarray(match).copy()
        for i in idx:
            k = int(keys[i])
            lo, hi = k & 0xFFFFFFFF, (k >> 32) & 0xFFFFFFFF
            b, _ = self.cn.locate(np.uint32([lo]), np.uint32([hi]))
            r = self._makeup_get(lo, hi, int(b[0]))
            if r.value is not None:
                v_lo[i] = r.value & 0xFFFFFFFF
                v_hi[i] = (r.value >> 32) & 0xFFFFFFFF
                match[i] = True
        return xp.asarray(v_lo), xp.asarray(v_hi), xp.asarray(match)

    # ----------------------------------------------------------- replication
    def mn_state(self) -> dict:
        """Deep-copied image of the memory-heavy MN half.

        Exactly the state a restarted replica must re-install to rejoin a
        K-way replica set (``repro.api.replication``): slot arrays +
        ``seeds_mn``, the KV heap, the overflow cache, and the key count.
        The CN half (locator + CN-cached seeds) is *not* included — a
        rejoining replica's stale CN seeds self-heal through the normal
        Makeup-Get path, which is the paper's own staleness mechanism
        (§4.3.1).  No meter events: state capture is host-side bookkeeping;
        the transfer cost is charged by the caller (one one-sided bulk
        READ of :meth:`mn_state_bytes`).
        """
        return {"slots_lo": self.slots_lo.copy(),
                "slots_hi": self.slots_hi.copy(),
                "seeds_mn": self.seeds_mn.copy(),
                "heap_klo": self.heap_klo.copy(),
                "heap_khi": self.heap_khi.copy(),
                "heap_vlo": self.heap_vlo.copy(),
                "heap_vhi": self.heap_vhi.copy(),
                "heap_top": self.heap_top,
                "overflow": self.overflow.state(),
                "n_keys": self.n_keys,
                "frozen": self.frozen}

    def install_mn_state(self, state: dict) -> None:
        """Overwrite this shard's MN half with another replica's
        :meth:`mn_state` (crash-recovery resync).  Bucket counts must
        match — replicas are always built from the same spec."""
        if state["slots_lo"].shape != self.slots_lo.shape:
            raise ValueError("bucket-count mismatch: replicas must be built "
                             "from the same spec")
        self._mn_written()
        self.slots_lo = state["slots_lo"].copy()
        self.slots_hi = state["slots_hi"].copy()
        self.seeds_mn = state["seeds_mn"].copy()
        self.heap_klo = state["heap_klo"].copy()
        self.heap_khi = state["heap_khi"].copy()
        self.heap_vlo = state["heap_vlo"].copy()
        self.heap_vhi = state["heap_vhi"].copy()
        self.heap_top = int(state["heap_top"])
        self.overflow.install(state["overflow"])
        self.n_keys = int(state["n_keys"])
        self.frozen = bool(state["frozen"])

    def mn_state_bytes(self) -> int:
        """On-wire size of one replica resync (live heap prefix only)."""
        return int(self.slots_lo.nbytes + self.slots_hi.nbytes
                   + self.seeds_mn.nbytes + self.heap_top * 16
                   + self.overflow.state_bytes())

    @classmethod
    def _from_state(cls, cn, mn_state: dict, *, load_factor: float,
                    transport=None) -> "OutbackShard":
        """Rebuild a shard from a locator copy + an MN image, without
        running the constructor's build (and without metering) — used by
        ``OutbackStore.install_mn_state`` when a restarted replica missed
        a §4.4 split and must re-materialise whole tables."""
        t = cls.__new__(cls)
        t.load_factor = load_factor
        t._dev = {}
        t.cn = cn
        t.slots_lo = mn_state["slots_lo"].copy()
        t.slots_hi = mn_state["slots_hi"].copy()
        t.seeds_mn = mn_state["seeds_mn"].copy()
        t.heap_klo = mn_state["heap_klo"].copy()
        t.heap_khi = mn_state["heap_khi"].copy()
        t.heap_vlo = mn_state["heap_vlo"].copy()
        t.heap_vhi = mn_state["heap_vhi"].copy()
        t.heap_top = int(mn_state["heap_top"])
        t.overflow = OverflowCache(int(mn_state["overflow"]["cap"]))
        t.overflow.install(mn_state["overflow"])
        t.meter = CommMeter()
        t.meter.sink = transport
        t.frozen = bool(mn_state["frozen"])
        t.cn_cache = None
        t.lease = None
        t.n_keys = int(mn_state["n_keys"])
        return t

    # ------------------------------------------------------------ accounting
    def cn_memory_bytes(self) -> int:
        return self.cn.memory_bytes()

    def mn_index_bytes(self) -> int:
        return (self.slots_lo.nbytes + self.slots_hi.nbytes
                + self.seeds_mn.nbytes + self.overflow.cap * 12)

    def dmph_load(self) -> float:
        return self.n_keys / (self.cn.num_buckets * 4)

    def needs_resize(self) -> bool:
        """The paper's s_slow trigger: DMPH load 97% or overflow half full."""
        return self.dmph_load() >= 0.97 or self.overflow.fill_ratio >= 0.5

    def must_stop(self) -> bool:
        """The paper's s_stop trigger: overflow cache over 90% full."""
        return self.overflow.fill_ratio >= 0.9

    def live_pairs(self):
        """All live (keys, values) as uint64 arrays (resize/rebuild path)."""
        lens = slots.unpack_len(self.slots_hi)
        b_idx, s_idx = np.nonzero(lens != 0)
        addrs = self.slots_lo[b_idx, s_idx].astype(np.int64)
        o_lo, o_hi, o_addr = self.overflow.items()
        addrs = np.concatenate([addrs, o_addr.astype(np.int64)])
        keys = (self.heap_khi[addrs].astype(np.uint64) << np.uint64(32)) | \
            self.heap_klo[addrs].astype(np.uint64)
        vals = (self.heap_vhi[addrs].astype(np.uint64) << np.uint64(32)) | \
            self.heap_vlo[addrs].astype(np.uint64)
        return keys, vals


def outback_get_batch(lo, hi, cn, mn, oth, num_buckets, xp=np):
    """The jit-friendly core of the batched Get (CN math + MN gathers)."""
    words_a, words_b, seeds = cn
    slots_lo, slots_hi, h_klo, h_khi, h_vlo, h_vhi = mn
    # ---- CN compute ----
    choice = oth.lookup(lo, hi, xp, words_a=words_a, words_b=words_b)
    b0, b1 = ludo.candidate_buckets(lo, hi, num_buckets, xp)
    bucket = xp.where(choice.astype(xp.bool_), b1, b0).astype(xp.int32)
    slot = slot_hash(lo, hi, seeds[bucket], xp).astype(xp.int32)
    # ---- one round trip; MN side: two dependent gathers, zero compute ----
    s_lo = slots_lo[bucket, slot]
    s_hi = slots_hi[bucket, slot]
    length = slots.unpack_len(s_hi, xp)
    addr = slots.unpack_addr32(s_lo, s_hi, xp).astype(xp.int32)
    k_lo, k_hi = h_klo[addr], h_khi[addr]
    v_lo, v_hi = h_vlo[addr], h_vhi[addr]
    # ---- CN full-key check ----
    match = (k_lo == lo) & (k_hi == hi) & (length != 0)
    return v_lo, v_hi, match
