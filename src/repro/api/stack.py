"""The composable CN-side stack: ``Pipeline → Meter → CNCache → Transport``.

Before this seam existed, every cross-cutting CN feature was threaded by
keyword through ten constructors (`cn_cache=`/`cn_cache_budget_bytes=`/
`transport=` on the shard, the store, all four baselines, the mesh
builder, and the session store).  The stack assembles the same layers
*once*, around any :class:`repro.api.protocol.KVStore` adapter:

* **Meter** (outermost, :class:`MeterLayer`) — stamps per-call attribution
  (round trips, wire bytes, Makeup-Get continuations, cache hits) onto
  every ``OpResult`` from the store's merged meter deltas.
* **CNCache** (:class:`CNCacheLayer`) — the FlexKV/DINOMO-style hot-key
  front (``repro.core.cn_cache``): probe before the wire, answer hits
  locally, forward misses with full Makeup-Get resolution (the cache only
  learns resolved truths), keep coherence on every mutation, and join the
  engine's split-time invalidation sync point via ``adapter.bind_cache``.
* **Transport** (innermost, :class:`TransportBinding`) — the recording
  seam *below* the engine: a ``repro.net.Transport`` plugged into each
  engine meter's ``sink`` so the op stream replays on the simulated RDMA
  clock.  It has to sit under the engine (resize-spawned tables must
  inherit it), so the stack binds it at construction time rather than
  wrapping calls.

Accounting parity with the legacy in-engine wiring is byte-for-byte
(tested in ``tests/test_api_stack.py``): for Outback kinds the cache
layer charges the same ``CACHE_*_SAVINGS`` into the same engine meter the
legacy path used (each adapter declares its own protocol's
``cache_hit_savings`` so cached baselines book *their* avoided wire
costs), and cache hits never reach the transport trace — exactly as
before.

The failure plane (ISSUE 6) added the first such layer below the cache:
:class:`RetryLayer` absorbs the ``"backoff"`` answers a
``repro.api.replication.ReplicaSetAdapter`` emits while an MN replica is
down — timeout + seeded jittered backoff, CN-driven failover after
``failover_after`` dead-primary rounds, and a degraded ``"unavailable"``
answer once the retry budget is spent (FlexChain's idiom: answer, never
block).  The assembled order with every stage active reads
``Pipeline → Meter → CNCache → Retry → ReplicaSet → adapters (→
Transport)``, so in-flight ``OpHandle``s resolve *through* a failover and
the CN cache only ever learns resolved truths.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.api.protocol import OpResult
from repro.core.cn_cache import CNKeyCache
from repro.core.hashing import split_u64


class StoreLayer:
    """Base middleware: wraps an inner KVStore, delegates what it doesn't
    override (``engine``, ``meter``, ...).

    The members :class:`repro.api.protocol.KVStore` lists are declared
    here rather than left to ``__getattr__``: ``isinstance`` against a
    runtime-checkable protocol looks members up with
    ``inspect.getattr_static``, which never calls ``__getattr__``."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    @property
    def spec(self):
        return self.inner.spec

    def meter_totals(self):
        return self.inner.meter_totals()

    def reset_meters(self) -> None:
        self.inner.reset_meters()


class RetryLayer(StoreLayer):
    """BACKOFF/retry stage: the recovery protocol above a replica set.

    Wraps every protocol op in a retry loop: a ``"backoff"`` answer (the
    serving MN is crashed, or the request was dropped on the wire) costs
    one completion timeout plus a seeded jittered backoff
    (``FaultPlane.backoff_us`` — deterministic, replayable), charged to
    the meter as ``fault_wait_us`` and to the trace as a posting stall on
    the retried op.  After ``failover_after`` rounds against a *crashed*
    primary the layer drives ``inner.failover()``; once ``max_retries``
    rounds are spent it answers degraded — ``"unavailable"`` statuses,
    ``found=False``, no exception, no state change — so callers (and
    pipelined ``OpHandle``s) always resolve.  On the no-fault path the
    wrap is a pure pass-through: no meter event, no trace event.
    """

    def __init__(self, inner, plane, transport=None, hub=None):
        super().__init__(inner)
        self.plane = plane
        self.transport = transport
        self.hub = hub

    def _with_retry(self, n: int, call) -> OpResult:
        from repro.api.replication import UNAVAILABLE, is_backoff
        res = call()
        if not is_backoff(res):
            return res
        sched = self.plane.schedule
        meter = self.inner.meter
        hub = self.hub
        for attempt in range(sched.max_retries):
            wait_us = sched.timeout_us + self.plane.backoff_us(attempt)
            meter.fault_wait_us += int(round(wait_us))
            if self.transport is not None:
                self.transport.add_wait(wait_us * 1e-6)
            if hub is not None:
                hub.count("retry.backoff_rounds")
                hub.hist("retry.backoff_wait_us").record(int(round(wait_us)))
                hub.annotate(backoff_rounds=1,
                             backoff_wait_us=int(round(wait_us)))
            if (attempt + 1 >= sched.failover_after
                    and self.plane.crash_open(self.inner.primary)
                    and self.inner.can_failover()):
                self.inner.failover()
            meter.retries += n
            res = call()
            if not is_backoff(res):
                return res
        if hub is not None:
            hub.count("retry.unavailable_lanes", n)
            hub.annotate(unavailable_lanes=n)
        return OpResult(values=np.zeros(n, np.uint64),
                        found=np.zeros(n, bool),
                        statuses=(UNAVAILABLE,) * n)

    def get(self, key: int) -> OpResult:
        return self._with_retry(1, lambda: self.inner.get(key))

    def get_batch(self, keys, xp=np, *,
                  resolve_makeup: bool | None = None) -> OpResult:
        return self._with_retry(
            len(keys), lambda: self.inner.get_batch(
                keys, xp, resolve_makeup=resolve_makeup))

    def insert(self, key: int, value: int) -> OpResult:
        return self._with_retry(1, lambda: self.inner.insert(key, value))

    def update(self, key: int, value: int) -> OpResult:
        return self._with_retry(1, lambda: self.inner.update(key, value))

    def delete(self, key: int) -> OpResult:
        return self._with_retry(1, lambda: self.inner.delete(key))

    def insert_batch(self, keys, values) -> OpResult:
        return self._with_retry(
            len(keys), lambda: self.inner.insert_batch(keys, values))

    def update_batch(self, keys, values) -> OpResult:
        return self._with_retry(
            len(keys), lambda: self.inner.update_batch(keys, values))

    def delete_batch(self, keys) -> OpResult:
        return self._with_retry(
            len(keys), lambda: self.inner.delete_batch(keys))


class CNCacheLayer(StoreLayer):
    """CN hot-key cache stage: hits answered locally, misses forwarded
    with Makeup-Get resolution, coherence kept on every mutation.

    Cache accounting lands in the *engine's* meter (``inner.meter``) so a
    middleware-built store and a legacy ``cn_cache=`` store report
    identical totals, and ``saved_*`` attribution stays next to the wire
    counters it offsets.
    """

    def __init__(self, inner, cache: CNKeyCache, hub=None):
        super().__init__(inner)
        self.cache = cache
        self.hub = hub
        inner.bind_cache(cache)  # engine-side sync points (resize)

    # ---------------------------------------------------------------- gets
    def get(self, key: int) -> OpResult:
        meter = self.inner.meter
        state, val = self.cache.lookup(int(key))
        if state == "hit":
            meter.add_cache_hit(1, **self.inner.cache_hit_savings)
            if self.hub is not None:
                self.hub.on_cache(1, 0, 0)
                self.hub.annotate(cache_hits=1)
            return OpResult(values=np.asarray([val], np.uint64),
                            found=np.asarray([True]))
        if state == "neg":
            meter.add_cache_hit(1, neg=True, **self.inner.cache_neg_savings)
            if self.hub is not None:
                self.hub.on_cache(0, 1, 0)
                self.hub.annotate(cache_neg_hits=1)
            return OpResult(values=np.zeros(1, np.uint64),
                            found=np.asarray([False]))
        if self.hub is not None:
            self.hub.on_cache(0, 0, 1)
        res = self.inner.get(key)
        if res.statuses is None:  # degraded answers teach the cache nothing
            self.cache.fill(int(key), res.value)
        return res

    def get_batch(self, keys, xp=np, *,
                  resolve_makeup: bool | None = None) -> OpResult:
        keys = np.asarray(keys, dtype=np.uint64)
        h_lo, h_hi = split_u64(keys)
        hit, neg, c_vlo, c_vhi = self.cache.probe_batch(h_lo, h_hi)
        # charge the savings the avoided Get would have cost on THIS
        # kind's wire (the adapter declares its protocol's shape)
        meter = self.inner.meter
        meter.add_cache_hit(int(hit.sum()), **self.inner.cache_hit_savings)
        meter.add_cache_hit(int(neg.sum()), neg=True,
                            **self.inner.cache_neg_savings)
        if self.hub is not None:
            n_hit, n_neg = int(hit.sum()), int(neg.sum())
            n_miss = len(keys) - n_hit - n_neg
            self.hub.on_cache(n_hit, n_neg, n_miss)
            self.hub.annotate(cache_hits=n_hit, cache_neg_hits=n_neg,
                              cache_misses=n_miss)
        values = ((np.asarray(c_vhi, np.uint64) << np.uint64(32))
                  | np.asarray(c_vlo, np.uint64))
        found = hit.copy()
        miss = ~hit & ~neg
        statuses = None
        if miss.any():
            # default: misses go down the stack with the full §4.3.1
            # resolution so the cache (and the caller) only ever learn
            # resolved truths; an explicit False is honoured exactly as
            # the legacy in-engine cache honoured it (raw 1-RT stream)
            if resolve_makeup is None:
                resolve_makeup = True
            sub = self.inner.get_batch(keys[miss], xp,
                                       resolve_makeup=resolve_makeup)
            values[miss] = sub.values
            found[miss] = sub.found
            if sub.statuses is not None:
                # degraded whole-call answer from the retry stage: those
                # lanes resolved nothing — observing them would poison
                # the cache with false negatives, so only the lanes the
                # cache itself answered are (re)observed, and the lane
                # statuses surface to the caller
                mi = iter(sub.statuses)
                statuses = tuple(next(mi) if m else "ok" for m in miss)
                learned = hit | neg
                if learned.any():
                    self.cache.observe_batch(
                        h_lo[learned], h_hi[learned],
                        (values[learned] & np.uint64(0xFFFFFFFF)
                         ).astype(np.uint32),
                        (values[learned] >> np.uint64(32)).astype(np.uint32),
                        found[learned], hit[learned], neg[learned])
                return OpResult(values=values, found=found,
                                statuses=statuses)
        self.cache.observe_batch(
            h_lo, h_hi, (values & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (values >> np.uint64(32)).astype(np.uint32), found, hit, neg)
        return OpResult(values=values, found=found)

    # ----------------------------------------------------------- mutations
    def insert(self, key: int, value: int) -> OpResult:
        res = self.inner.insert(key, value)
        if res.status not in ("frozen", "backoff", "unavailable"):
            self.cache.note_insert(int(key), int(value))
        return res

    def update(self, key: int, value: int) -> OpResult:
        res = self.inner.update(key, value)
        if bool(res.found[0]):
            self.cache.note_update(int(key), int(value))
        return res

    def delete(self, key: int) -> OpResult:
        res = self.inner.delete(key)
        if bool(res.found[0]):
            self.cache.note_delete(int(key))
        return res

    def insert_batch(self, keys, values) -> OpResult:
        res = self.inner.insert_batch(keys, values)
        for k, v, case in zip(keys, values, res.statuses):
            if case not in ("frozen", "backoff", "unavailable"):
                self.cache.note_insert(int(k), int(v))
        return res

    def update_batch(self, keys, values) -> OpResult:
        res = self.inner.update_batch(keys, values)
        for k, v, ok in zip(keys, values, res.found):
            if ok:
                self.cache.note_update(int(k), int(v))
        return res

    def delete_batch(self, keys) -> OpResult:
        res = self.inner.delete_batch(keys)
        for k, ok in zip(keys, res.found):
            if ok:
                self.cache.note_delete(int(k))
        return res


class MeterLayer(StoreLayer):
    """Outermost stage: stamps per-call meter deltas onto each OpResult.

    With a telemetry hub attached it also forwards each call's
    attribution to ``hub.on_op`` under its op kind (the per-op-kind
    counters/histograms of the ``obs`` plane) and annotates the active
    span — reading only the deltas it already computed, so metered
    results are byte-identical with the hub on or off."""

    def __init__(self, inner, hub=None):
        super().__init__(inner)
        self.hub = hub

    def _attributed(self, n: int, call, op: str = "get") -> OpResult:
        before = self.inner.meter_totals()
        res = call()
        after = self.inner.meter_totals()
        res.round_trips = after.round_trips - before.round_trips
        res.req_bytes = after.req_bytes - before.req_bytes
        res.resp_bytes = after.resp_bytes - before.resp_bytes
        # every lane opens one meter op; Makeup-Get continuations open one
        # more each (resize broadcasts can add a few — clamp at zero)
        res.makeups = max(0, (after.ops - before.ops) - n)
        res.cache_hits = after.cache_hits - before.cache_hits
        res.cache_neg_hits = after.cache_neg_hits - before.cache_neg_hits
        # failure-plane attribution (all-zero deltas on the no-fault path)
        res.retries = after.retries - before.retries
        res.backoffs = after.backoffs - before.backoffs
        res.failovers = after.failovers - before.failovers
        hub = self.hub
        if hub is not None:
            hub.on_op(op, n, round_trips=res.round_trips,
                      req_bytes=res.req_bytes, resp_bytes=res.resp_bytes,
                      makeups=res.makeups, retries=res.retries,
                      backoffs=res.backoffs, failovers=res.failovers)
            hub.annotate(round_trips=res.round_trips,
                         req_bytes=res.req_bytes, resp_bytes=res.resp_bytes,
                         makeups=res.makeups)
        return res

    def get(self, key: int) -> OpResult:
        return self._attributed(1, lambda: self.inner.get(key), "get")

    def get_batch(self, keys, xp=np, *,
                  resolve_makeup: bool | None = None) -> OpResult:
        return self._attributed(
            len(keys), lambda: self.inner.get_batch(
                keys, xp, resolve_makeup=resolve_makeup), "get")

    def insert(self, key: int, value: int) -> OpResult:
        return self._attributed(1, lambda: self.inner.insert(key, value),
                                "insert")

    def update(self, key: int, value: int) -> OpResult:
        return self._attributed(1, lambda: self.inner.update(key, value),
                                "update")

    def delete(self, key: int) -> OpResult:
        return self._attributed(1, lambda: self.inner.delete(key), "delete")

    def insert_batch(self, keys, values) -> OpResult:
        return self._attributed(
            len(keys), lambda: self.inner.insert_batch(keys, values),
            "insert")

    def update_batch(self, keys, values) -> OpResult:
        return self._attributed(
            len(keys), lambda: self.inner.update_batch(keys, values),
            "update")

    def delete_batch(self, keys) -> OpResult:
        return self._attributed(
            len(keys), lambda: self.inner.delete_batch(keys), "delete")


@dataclasses.dataclass(frozen=True)
class TransportBinding:
    """The innermost stage, made explicit: a ``repro.net.Transport`` bound
    to every engine meter's ``sink`` at construction (the factories pass it
    down so even resize-spawned tables inherit it).  Kept as a stack member
    so the assembled order — Meter → CNCache → Transport — reads off the
    object, and so future stages below the cache have a place to anchor."""

    transport: object | None = None


@dataclasses.dataclass(frozen=True)
class CNStack:
    """Composition root for the CN-side stack.  ``open_store`` builds one
    per store; tests may assemble their own around any adapter.

    ``policy`` (a ``repro.api.pipeline.BatchPolicy``, or ``None`` for the
    synchronous ``BatchPolicy.sync()``) shapes the outermost pipeline
    stage; ``retry`` (a ``repro.net.faults.FaultPlane``, set by the
    registry whenever the spec carries a ``FaultSchedule`` or
    ``replicas > 1``) inserts the recovery stage directly above the
    (replica-set) adapter, so the fully-assembled order reads
    ``Pipeline → Meter → [CNCache →] [Retry →] adapter (→ Transport)``.
    """

    cache: CNKeyCache | None = None
    transport_binding: TransportBinding = TransportBinding()
    policy: object | None = None  # BatchPolicy; None -> sync()
    retry: object | None = None   # FaultPlane; None -> no retry stage
    hub: object | None = None     # repro.obs.TelemetryHub; None -> dormant

    def assemble(self, adapter):
        from repro.api.pipeline import PipelineLayer  # avoid import cycle
        store = adapter  # transport already bound below the engine
        if self.hub is not None and hasattr(adapter, "hub"):
            adapter.hub = self.hub  # ReplicaSetAdapter annotations
        if self.retry is not None:
            store = RetryLayer(store, self.retry,
                               transport=self.transport_binding.transport,
                               hub=self.hub)
        if self.cache is not None:
            store = CNCacheLayer(store, self.cache, hub=self.hub)
        store = MeterLayer(store, hub=self.hub)
        return PipelineLayer(store, policy=self.policy,
                             transport=self.transport_binding.transport,
                             hub=self.hub)
