"""The ``faults`` suite: tail latency and availability through an MN crash.

The scenario is the failure-plane acceptance run (ISSUE 6): a K=2
replicated Outback store (``StoreSpec(..., replicas=2, faults=...)``)
serves a warm Get phase, then a write+read mix *through* a seeded MN
crash/restart window, then a recovery tail.  Everything is deterministic:
the crash is a :class:`repro.net.FaultSchedule` pinned to the op clock,
retries/backoff draw from the schedule's seeded oracle, and the recorded
trace replays on the simulated RDMA clock with ``replicas=2`` — so the
rows are reproducible bit-for-bit.

Rows (CSV contract ``name,us_per_call,derived`` + JSON extras):

* ``faults/p999_through_crash`` — Get/insert latency percentiles of the
  whole run replayed through the crash window (the p999 is the headline:
  ops that stall on retry/backoff/failover land in the tail).
* ``faults/availability``      — the ``outback-availability/v1`` curve
  (bucketed throughput normalised by the median bucket) with the fault
  windows annotated.  Since PR 7 the curve travels inside a validated
  ``outback-telemetry/v1`` JSONL series (``telemetry_jsonl`` extras:
  hub snapshots + spans from the crash run's TelemetryHub, the replayed
  sim row embedding the curve and latency histogram, and the pipeline
  stats row); CI's faults-smoke lane validates both schemas.
* ``faults/lost_acked_writes`` — MUST be 0 at K=2: every write the store
  acknowledged before/during/after the crash is readable after recovery.
  A non-zero count raises (→ an ERROR row, non-zero harness exit)
  rather than reporting a broken store as data.
* ``faults/recovery``          — failover/resync/retry/lease counters
  from the merged meters: proof the run actually crossed a failover and
  shipped a state image, not just idled through the window.
* ``faults/dormant_identity``  — a spec carrying a *dormant* schedule
  (no events, leasing off) meters and traces byte-identically to the
  plain spec; raises on any drift (the no-fault-path contract).
* ``faults/k1_degraded``       — the same crash at K=1 (nowhere to fail
  over): lanes degrade to ``"unavailable"`` during the window instead of
  erroring, and the store serves again after restart (FlexChain idiom).
"""

from __future__ import annotations

import numpy as np

from benchmarks import common as C
from repro.api import StoreSpec, open_store
from repro.net import FaultSchedule, Transport
from repro.net.replay import simulate
from repro.obs import (TelemetryConfig, pipeline_row, sim_rows,
                       telemetry_rows, validate_telemetry_rows)

# Fault windows are placed on the op clock (lanes), far larger than any
# single protocol call, so the window cannot be jumped by one batch tick
# (the documented quantisation rule: size windows in ops >> batch size).
_WARM_CALLS = 10          # warm Get batches before the write phase
_GET_LANES = 64           # lanes per warm/recovery Get batch
_WRITE_ROUNDS = 40        # insert+get rounds driven through the crash
_WRITE_LANES = 8          # insert lanes per round
_CRASH_AT = 800           # op-clock start of the crash window
_CRASH_OPS = 400          # op-clock duration of the crash window


def faults_suite(quick: bool = False):
    rows = []
    rows.extend(_crash_recovery_rows(quick))
    rows.append(_dormant_identity_row(quick))
    rows.append(_k1_degraded_row(quick))
    return rows


def _datasets(quick: bool):
    n = 20_000 if quick else 60_000
    keys = C.fb_like_keys(n)
    vals = C.values_for(keys)
    half = n // 2
    return keys[:half], vals[:half], keys[half:], vals[half:]


def _drive_through_crash(st, build_k, write_k, write_v):
    """Warm Gets, then a write+read mix through the crash, then a tail.

    Returns the (key, value) pairs the store *acknowledged* — the set the
    zero-lost-writes assertion replays after recovery.
    """
    half = len(build_k)
    q = build_k[C.uniform_indices(half, _GET_LANES * _WARM_CALLS, seed=31)]
    for i in range(_WARM_CALLS):
        st.get_batch(q[i * _GET_LANES:(i + 1) * _GET_LANES])
    acked = []
    for i in range(_WRITE_ROUNDS):
        wk = write_k[i * _WRITE_LANES:(i + 1) * _WRITE_LANES]
        wv = write_v[i * _WRITE_LANES:(i + 1) * _WRITE_LANES]
        r = st.insert_batch(wk, wv)
        stats = r.statuses or ("ok",) * len(wk)
        for k, v, ok, case in zip(wk, wv, r.found, stats):
            if ok and case not in ("backoff", "unavailable"):
                acked.append((int(k), int(v)))
        off = (i % _WARM_CALLS) * _GET_LANES
        st.get_batch(q[off:off + _GET_LANES // 2])
    for i in range(_WARM_CALLS):  # recovery tail: past the window's end
        st.get_batch(q[i * _GET_LANES:(i + 1) * _GET_LANES])
    return acked


def _crash_recovery_rows(quick: bool):
    build_k, build_v, spare_k, spare_v = _datasets(quick)
    write_k = spare_k[:_WRITE_ROUNDS * _WRITE_LANES]
    write_v = spare_v[:_WRITE_ROUNDS * _WRITE_LANES]
    sched = FaultSchedule.single_crash(at_op=_CRASH_AT,
                                      duration_ops=_CRASH_OPS,
                                      down_s=200e-6, lease_term_ops=256)
    # the crash run carries the telemetry plane (PR 7): the hub observes
    # the whole drive — failovers, resyncs, backoff rounds land on spans
    # and per-replica counters — without perturbing any asserted artifact
    spec = StoreSpec("outback", load_factor=0.85, replicas=2, faults=sched,
                     telemetry=TelemetryConfig(window_ops=256))
    tr = Transport()
    st = open_store(spec, build_k, build_v, transport=tr)
    acked = _drive_through_crash(st, build_k, write_k, write_v)

    ak = np.asarray([k for k, _ in acked], dtype=np.uint64)
    av = np.asarray([v for _, v in acked], dtype=np.uint64)
    g = st.get_batch(ak)
    lost = int((~g.found).sum()) + int((g.values != av)[g.found].sum())
    if lost:  # a broken store is an ERROR row, not a data point
        raise RuntimeError(
            f"{lost}/{len(acked)} acknowledged writes lost through the "
            f"crash at K=2 — the zero-lost-acked-writes guarantee broke")
    m = st.meter_totals()
    if m.failovers < 1 or m.resyncs < 1:
        raise RuntimeError(
            "the crash schedule produced no failover/resync — the suite "
            "idled through its own fault window (re-check the op clock)")

    res = simulate(tr.trace, clients=4, replicas=2)
    pct = res.percentiles()
    # the availability curve and crash-window percentiles now travel
    # through the obs exporters: one validated outback-telemetry/v1 JSONL
    # series (hub snapshots/spans + the replayed sim + pipeline stats)
    # rides the availability row's extras; CI's faults-smoke lane reads
    # the curve out of the series' sim row.
    series = (telemetry_rows(st.telemetry)
              + sim_rows(res, name="faults_crash")
              + [pipeline_row(st.stats)])
    validate_telemetry_rows(series)
    sim_row = next(r for r in series if r["row"] == "sim")
    avail = sim_row["availability"]
    sp = spec.to_json_dict()
    return [
        ("faults/p999_through_crash", round(pct["p999_us"], 4),
         f"p50={pct['p50_us']:.3f}us",
         {**{k: round(v, 4) for k, v in pct.items()},
          "tput_mops": round(res.tput_mops, 4),
          "fault_windows": [[a, b, k, r] for a, b, k, r
                            in res.fault_windows], "spec": sp}),
        ("faults/availability", round(avail["bucket_s"] * 1e6, 4),
         f"min={min(avail['availability']):.3f}",
         {"telemetry_jsonl": series, "spec": sp}),
        ("faults/lost_acked_writes", 0.0, lost,
         {"acked": len(acked), "lost": lost, "replicas": 2, "spec": sp}),
        ("faults/recovery", float(m.fault_wait_us),
         f"failovers={m.failovers};resyncs={m.resyncs}",
         {"failovers": m.failovers, "resyncs": m.resyncs,
          "retries": m.retries, "backoffs": m.backoffs, "drops": m.drops,
          "lease_renewals": m.lease_renewals,
          "fault_wait_us": m.fault_wait_us, "spec": sp}),
    ]


def _dormant_identity_row(quick: bool):
    """Byte-identity of the no-fault path: plain spec vs dormant schedule.

    The dormant schedule carries no events and leasing off — exactly what
    the registry builds for a replicas-only spec — so the assembled stack
    gains a ReplicaSetAdapter and a RetryLayer that must never meter."""
    build_k, build_v, spare_k, spare_v = _datasets(quick)
    plain = StoreSpec("outback", load_factor=0.85)
    dormant = StoreSpec("outback", load_factor=0.85,
                        faults=FaultSchedule(lease_term_ops=0))
    q = build_k[C.uniform_indices(len(build_k), 512, seed=33)]
    snaps, traces = [], []
    for spec in (plain, dormant):
        tr = Transport()
        st = open_store(spec, build_k, build_v, transport=tr)
        st.get_batch(q)
        st.insert_batch(spare_k[:64], spare_v[:64])
        st.update_batch(build_k[:64], build_v[:64])
        snaps.append(st.meter_totals().snapshot())
        traces.append(tr.trace)
    if snaps[0] != snaps[1] or traces[0] != traces[1]:
        raise RuntimeError("dormant fault plane drifted from the plain "
                           "store: meter/trace identity broke")
    return ("faults/dormant_identity", 0.0, "identical",
            {"ops": int(snaps[0]["ops"]),
             "round_trips": int(snaps[0]["round_trips"]),
             "spec": dormant.to_json_dict()})


def _k1_degraded_row(quick: bool):
    """K=1 under the same crash: degrade, don't block; recover after."""
    build_k, build_v, _, _ = _datasets(quick)
    sched = FaultSchedule.single_crash(at_op=_CRASH_AT,
                                      duration_ops=_CRASH_OPS,
                                      down_s=200e-6, max_retries=2,
                                      lease_term_ops=0)
    spec = StoreSpec("outback", load_factor=0.85, faults=sched)
    st = open_store(spec, build_k, build_v)
    q = build_k[C.uniform_indices(len(build_k),
                                  _GET_LANES * 3 * _WARM_CALLS, seed=35)]
    unavailable = served = 0
    for i in range(3 * _WARM_CALLS):
        r = st.get_batch(q[i * _GET_LANES:(i + 1) * _GET_LANES])
        if r.statuses is not None:
            unavailable += r.statuses.count("unavailable")
        else:
            served += len(r)
    post = st.get_batch(build_k[:256])
    if unavailable == 0:
        raise RuntimeError("K=1 crash produced no degraded lanes — the "
                           "retry stage should have exhausted its budget")
    if not bool(post.found.all()):
        raise RuntimeError("K=1 store did not recover after its crash "
                           "window closed")
    return ("faults/k1_degraded", 0.0,
            f"unavailable={unavailable}",
            {"unavailable_lanes": unavailable, "served_lanes": served,
             "recovered": True, "spec": spec.to_json_dict()})
