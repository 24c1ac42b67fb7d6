"""The ``slo`` suite: the serving front door under open-loop load.

PR 10's acceptance rows (ISSUE 10).  Every other suite drives the stack
closed-loop — a client posts only when a window slot frees, so offered
load can never exceed service and overload is inexpressible.  Here the
:mod:`repro.serve.traffic` generator produces an *open-loop* multi-tenant
arrival schedule, :class:`repro.serve.FrontDoor` decides each request's
outcome on the host path (rate limits -> singleflight -> admission), and
:func:`repro.net.replay.simulate_open` times the surviving upstream
lanes at their release instants.  Each row is one serving claim:

* ``slo/curve``             — goodput (completions meeting the SLO
  deadline) versus offered load through the dormant front door, swept as
  fractions of a measured capacity probe; the *knee* is the highest load
  still delivering >= 85% of offered as goodput.
* ``slo/overload/p999``     — the same store at 2x-knee offered load,
  admission off (unbounded queueing: p999 explodes, goodput collapses)
  versus on (bounded shed at arrival): p999 stays <= 3x the at-knee
  p999 while goodput holds >= 80% of knee goodput.  Raises otherwise.
* ``slo/singleflight``      — 8 tenants hammering one zipf(0.99) hot
  set: collapsed duplicate Gets save >= 20% of upstream lanes, metered
  as ``sf_hits`` with CN-cache-style saved req/resp bytes.
* ``slo/isolation``         — an abusive tenant offering ~8x its token
  bucket cannot move a compliant tenant's p999 by more than 10%.
* ``slo/acked_writes``      — through shedding, rate limiting, and
  window hazards, *zero lost acked writes*: every update answered
  ``ok`` is readable afterwards; every update shed or ratelimited was
  never applied.
* ``slo/dormant_identity``  — the ingress contract: a default-config
  FrontDoor leaves meters, the recorded transport trace, and the final
  MN state byte-identical to calling the stack directly.

Every row's extras carry the ``outback-slo/v1`` schema tag plus the
StoreSpec and TrafficSpec JSON that produced it (CI's serve-smoke lane
revalidates the invariants from the emitted JSON).  The whole suite is
deterministic end to end: seeded arrivals, no RNG or wall clock in the
host plane, tie-broken event heap in the sim.
"""

from __future__ import annotations

import pickle
import time

import numpy as np

from benchmarks import common as C
from repro.api import BatchPolicy, StoreSpec, open_store
from repro.net import Transport
from repro.net.replay import simulate_open
from repro.serve import (FrontDoor, FrontDoorConfig, TenantLimit, TenantSpec,
                         TrafficSpec, generate)

SLO_SCHEMA = "outback-slo/v1"

_WINDOW = 512        # pipeline doorbell window / front-door batch scope
_QPS = 8             # open-loop QP fan-out (matches the scale suite's CNs)
_C = 8               # admission lanes when the controller is on
_DEADLINE_X = 8.0    # SLO deadline = this multiple of lightly-loaded p50
_KNEE_FRAC = 0.85    # goodput/offered ratio that still counts as "good"


def slo_suite(quick: bool = False):
    """All ``slo/*`` rows (the run.py suite entry)."""
    keys, vals = _datasets(quick)
    probe_rate = _capacity_probe(keys, vals)
    curve_row, knee = _curve_row(keys, vals, probe_rate, quick)
    rows = [curve_row]
    rows.append(_overload_row(keys, vals, knee, quick))
    rows.append(_singleflight_row(keys, vals, quick))
    rows.append(_isolation_row(keys, vals, knee, quick))
    rows.append(_acked_writes_row(keys, vals, knee, quick))
    rows.append(_dormant_identity_row(keys, vals, quick))
    return rows


def _datasets(quick: bool):
    n = 30_000 if quick else 80_000
    keys = C.fb_like_keys(n)
    return keys, C.values_for(keys)


def _spec() -> StoreSpec:
    """The timing store: outback, pipelined, **no CN cache** (cache hits
    never reach the recorded wire, which would break the one lane == one
    trace OpEvent alignment ``simulate_open`` asserts)."""
    return StoreSpec("outback", load_factor=0.85,
                     batch=BatchPolicy(window=_WINDOW))


def _store(keys, vals):
    tr = Transport()
    st = open_store(_spec(), keys, vals, transport=tr)
    return st, tr


# ------------------------------------------------------------ driving runs
def _run(spec: TrafficSpec, keys, vals, cfg: FrontDoorConfig):
    """Generate ``spec``'s schedule, push it through a fresh store's front
    door, and time the surviving lanes open-loop.  Returns
    ``(records, sim_result, front_door, host_seconds)``."""
    offered = generate(spec, keys)
    st, tr = _store(keys, vals)
    fd = FrontDoor(st, cfg)
    t0 = time.perf_counter()
    recs = fd.run(offered)
    host_s = time.perf_counter() - t0
    arr = np.asarray(fd.lane_arrivals(), dtype=np.float64)
    res = simulate_open(tr.trace, arr, qps=_QPS)
    return recs, res, fd, host_s


def _latencies_us(recs, res) -> np.ndarray:
    """Arrival-to-completion latency for every answered request (``ok``
    and ``collapsed`` — followers complete when their leader's lane
    does).  Shed/ratelimited requests never completed; they are *not*
    latency samples, they are goodput losses."""
    done = res.completions_by_op_s
    # clamped at zero: a collapsed follower arriving after its leader's
    # lane completed still gets the answer no earlier than its own arrival
    out = [max((done[r.lane] - r.t_s) * 1e6, 0.0) for r in recs
           if r.outcome in ("ok", "collapsed") and r.lane >= 0]
    return np.asarray(out, dtype=np.float64)


def _goodput_mops(recs, res, deadline_us: float, duration_s: float) -> float:
    lat = _latencies_us(recs, res)
    return float((lat <= deadline_us).sum()) / duration_s / 1e6


def _p(lat: np.ndarray, q: float) -> float:
    return float(np.percentile(lat, q)) if len(lat) else float("nan")


# -------------------------------------------------------------- capacity
def _capacity_probe(keys, vals, n: int = 4_000) -> float:
    """Peak upstream service rate (ops/s): post ``n`` zipf Gets all at
    t=0 and measure the drain makespan.  An upper bound on sustainable
    open-loop load (full backlog coalesces doorbells perfectly), which
    is exactly what a sweep *fraction* axis wants."""
    idx = C.zipf_indices(len(keys), n, seed=11)
    st, tr = _store(keys, vals)
    for i in idx:
        st.submit("get", keys[i])
    st.flush()
    res = simulate_open(tr.trace, np.zeros(n), qps=_QPS)
    return n / float(res.completions_by_op_s.max())


def _curve_traffic(rate: float, duration_s: float, seed: int) -> TrafficSpec:
    """The sweep mix: four equal poisson tenants, zipf(0.99) over the
    whole build set, 90/10 read/update (YCSB-B-flavoured)."""
    tenants = tuple(
        TenantSpec(name=f"t{i}", rate_ops_per_s=rate / 4, read_frac=0.9,
                   zipf_theta=0.99, hot_salt=i)
        for i in range(4))
    return TrafficSpec(tenants=tenants, duration_s=duration_s, seed=seed)


def _curve_row(keys, vals, probe_rate: float, quick: bool):
    n_target = 6_000 if quick else 16_000
    fracs = ((0.25, 0.45, 0.65, 0.85, 1.1, 1.5, 2.0) if quick else
             (0.25, 0.4, 0.55, 0.7, 0.85, 1.0, 1.15, 1.3, 1.6, 2.0))
    deadline_us = None
    curve, knee, host_at_knee = [], None, 0.0
    for fi, f in enumerate(fracs):
        rate = f * probe_rate
        spec = _curve_traffic(rate, n_target / rate, seed=100 + fi)
        recs, res, fd, host_s = _run(spec, keys, vals, FrontDoorConfig())
        lat = _latencies_us(recs, res)
        if deadline_us is None:  # lowest load defines "fast enough"
            deadline_us = _DEADLINE_X * _p(lat, 50)
        offered_mops = len(recs) / spec.duration_s / 1e6
        good_mops = _goodput_mops(recs, res, deadline_us, spec.duration_s)
        pt = {"frac": f, "offered_mops": round(offered_mops, 4),
              "goodput_mops": round(good_mops, 4),
              "good_frac": round(good_mops / offered_mops, 4),
              "p50_us": round(_p(lat, 50), 3),
              "p999_us": round(_p(lat, 99.9), 3),
              "offered_ops": len(recs)}
        curve.append(pt)
        if good_mops >= _KNEE_FRAC * offered_mops:
            knee = dict(pt, deadline_us=round(deadline_us, 3),
                        rate_ops_per_s=rate)
            host_at_knee = host_s / max(len(recs), 1) * 1e6
    if knee is None:
        raise RuntimeError(
            f"no knee: goodput never reached {_KNEE_FRAC:.0%} of offered "
            f"even at {fracs[0]}x the capacity probe ({probe_rate:.0f} "
            f"ops/s) — curve: {curve}")
    row = ("slo/curve", round(host_at_knee, 4),
           f"knee={knee['offered_mops']:.3f}Mops@{knee['frac']}x "
           f"(probe {probe_rate / 1e6:.3f}Mops)",
           {"schema": SLO_SCHEMA, "curve": curve, "knee": knee,
            "probe_mops": round(probe_rate / 1e6, 4),
            "deadline_us": round(deadline_us, 3),
            "spec": _spec().to_json_dict(),
            "traffic": _curve_traffic(
                knee["rate_ops_per_s"], n_target / knee["rate_ops_per_s"],
                seed=0).to_json_dict()})
    return row, knee


# -------------------------------------------------------------- overload
def _admission_cfg(knee: dict, **kw) -> FrontDoorConfig:
    """Admission sized from the measured knee: ``_C`` lanes passing ~90%
    of the knee rate upstream, with the queue bounded so the worst host
    queue wait stays ~1.5x the at-knee p999 (the 3x tail budget then
    splits between waiting at the door and upstream service)."""
    admit_rate = 0.9 * knee["rate_ops_per_s"]
    depth = max(4, int(1.5 * knee["p999_us"] * 1e-6 * admit_rate))
    return FrontDoorConfig(max_inflight=_C, queue_depth=depth,
                           service_us=_C / admit_rate * 1e6,
                           window=_WINDOW, **kw)


def _overload_row(keys, vals, knee: dict, quick: bool):
    n_target = 6_000 if quick else 16_000
    rate = 2.0 * knee["rate_ops_per_s"]
    deadline_us = knee["deadline_us"]
    spec = _curve_traffic(rate, n_target / rate, seed=300)
    arms = {}
    host_per_op = 0.0
    for name, cfg in (("off", FrontDoorConfig()),
                      ("on", _admission_cfg(knee))):
        recs, res, fd, host_s = _run(spec, keys, vals, cfg)
        lat = _latencies_us(recs, res)
        arms[name] = {
            "p50_us": round(_p(lat, 50), 3),
            "p999_us": round(_p(lat, 99.9), 3),
            "goodput_mops": round(
                _goodput_mops(recs, res, deadline_us, spec.duration_s), 4),
            "stats": fd.stats()}
        host_per_op = host_s / max(len(recs), 1) * 1e6
    p999_on = arms["on"]["p999_us"]
    good_on = arms["on"]["goodput_mops"]
    p999_bound = 3.0 * knee["p999_us"]
    good_bound = 0.8 * knee["goodput_mops"]
    if p999_on > p999_bound:
        raise RuntimeError(
            f"admission failed to bound tail at 2x-knee: p999 "
            f"{p999_on:.1f}us > 3x at-knee {knee['p999_us']:.1f}us")
    if good_on < good_bound:
        raise RuntimeError(
            f"admission shed too much at 2x-knee: goodput {good_on:.4f} "
            f"Mops < 80% of knee {knee['goodput_mops']:.4f} Mops")
    return ("slo/overload/p999", round(host_per_op, 4),
            f"on={p999_on:.1f}us off={arms['off']['p999_us']:.1f}us "
            f"goodput {good_on:.3f}/{knee['goodput_mops']:.3f}Mops",
            {"schema": SLO_SCHEMA, "offered_x_knee": 2.0, "arms": arms,
             "knee": knee, "p999_bound_us": round(p999_bound, 3),
             "goodput_bound_mops": round(good_bound, 4),
             "admission": _admission_cfg(knee).to_json_dict(),
             "spec": _spec().to_json_dict(),
             "traffic": spec.to_json_dict()})


# ---------------------------------------------------------- singleflight
def _singleflight_row(keys, vals, quick: bool):
    """8 tenants share one zipf(0.99) hot set of 4096 build keys; inside
    each 512-request window duplicate Gets collapse onto one lane."""
    n_target = 10_000 if quick else 24_000
    rate = 8 * 100_000.0
    tenants = tuple(
        TenantSpec(name=f"t{i}", rate_ops_per_s=rate / 8, zipf_theta=0.99,
                   keyspace=4096, hot_salt=0)
        for i in range(8))
    spec = TrafficSpec(tenants=tenants, duration_s=n_target / rate, seed=400)
    cfg = FrontDoorConfig(singleflight=True, window=_WINDOW)
    recs, res, fd, host_s = _run(spec, keys, vals, cfg)
    st = fd.store
    meter = st.meter_totals()
    saved_frac = meter.sf_hits / max(len(recs), 1)
    stats = fd.stats()
    if stats["collapsed"] != meter.sf_hits:
        raise RuntimeError(
            f"singleflight meter drifted from outcomes: "
            f"{meter.sf_hits} sf_hits vs {stats['collapsed']} collapsed")
    if saved_frac < 0.20:
        raise RuntimeError(
            f"singleflight saved only {saved_frac:.1%} of upstream gets "
            f"(need >= 20% at zipf 0.99 x 8 tenants)")
    lat = _latencies_us(recs, res)
    return ("slo/singleflight", round(host_s / len(recs) * 1e6, 4),
            f"saved={saved_frac * 100:.1f}% of {len(recs)} gets",
            {"schema": SLO_SCHEMA, "offered_gets": len(recs),
             "sf_hits": int(meter.sf_hits), "lanes": stats["lanes"],
             "saved_frac": round(saved_frac, 4), "criterion": ">= 0.20",
             "saved_round_trips": int(meter.saved_round_trips),
             "saved_req_bytes": int(meter.saved_req_bytes),
             "saved_resp_bytes": int(meter.saved_resp_bytes),
             "p50_us": round(_p(lat, 50), 3),
             "spec": _spec().to_json_dict(),
             "traffic": spec.to_json_dict()})


# ------------------------------------------------------------- isolation
def _isolation_row(keys, vals, knee: dict, quick: bool):
    """A compliant tenant's p999, alone versus sharing the door with an
    abusive tenant offering ~8x its token bucket."""
    knee_rate = knee["rate_ops_per_s"]
    c_rate = 0.3 * knee_rate
    a_limit = 0.15 * knee_rate
    a_rate = 8.0 * a_limit
    n_compliant = 5_000 if quick else 12_000
    duration = n_compliant / c_rate
    compliant = TenantSpec(name="compliant", rate_ops_per_s=c_rate,
                           zipf_theta=0.99, hot_salt=1)
    abuser = TenantSpec(name="abuser", rate_ops_per_s=a_rate,
                        zipf_theta=0.99, hot_salt=2)
    cfg = _admission_cfg(
        knee, limits=(TenantLimit("abuser", a_limit, burst=16.0),))
    p999, stats = {}, {}
    specs = {"alone": TrafficSpec(tenants=(compliant,), duration_s=duration,
                                  seed=500),
             "contended": TrafficSpec(tenants=(compliant, abuser),
                                      duration_s=duration, seed=500)}
    for name, spec in specs.items():
        recs, res, fd, _ = _run(spec, keys, vals, cfg)
        mine = [r for r in recs if r.tenant == "compliant"]
        p999[name] = _p(_latencies_us(mine, res), 99.9)
        stats[name] = fd.stats()
    shift = abs(p999["contended"] - p999["alone"]) / max(p999["alone"], 1e-9)
    if shift > 0.10:
        raise RuntimeError(
            f"tenant isolation broke: compliant p999 moved "
            f"{shift:.1%} ({p999['alone']:.2f}us -> "
            f"{p999['contended']:.2f}us) under an abusive neighbour")
    return ("slo/isolation", 0.0,
            f"compliant p999 {p999['alone']:.2f}us -> "
            f"{p999['contended']:.2f}us ({shift * 100:+.1f}%)",
            {"schema": SLO_SCHEMA,
             "p999_alone_us": round(p999["alone"], 3),
             "p999_contended_us": round(p999["contended"], 3),
             "shift_frac": round(shift, 4), "criterion": "<= 0.10",
             "abuser_offered_x_limit": round(a_rate / a_limit, 1),
             "stats": stats, "admission": cfg.to_json_dict(),
             "spec": _spec().to_json_dict(),
             "traffic": specs["contended"].to_json_dict()})


# ----------------------------------------------------------- acked writes
def _acked_writes_row(keys, vals, knee: dict, quick: bool):
    """Overload with writes: every update the door answered ``ok`` is
    readable afterwards; every shed/ratelimited update never landed."""
    knee_rate = knee["rate_ops_per_s"]
    rate = 1.2 * knee_rate
    n_target = 8_000 if quick else 16_000
    tenants = (
        TenantSpec(name="rw0", rate_ops_per_s=rate * 0.4, read_frac=0.5,
                   zipf_theta=0.9, hot_salt=3),
        TenantSpec(name="rw1", rate_ops_per_s=rate * 0.4, read_frac=0.5,
                   zipf_theta=0.9, hot_salt=4),
        TenantSpec(name="greedy", rate_ops_per_s=rate * 0.2, read_frac=0.5,
                   zipf_theta=0.9, hot_salt=5),
    )
    spec = TrafficSpec(tenants=tenants, duration_s=n_target / rate, seed=600)
    cfg = _admission_cfg(knee, singleflight=True,
                         limits=(TenantLimit("greedy", rate * 0.05,
                                             burst=8.0),))
    offered = generate(spec, keys)
    st, tr = _store(keys, vals)
    fd = FrontDoor(st, cfg)
    recs = fd.run(offered)
    build = dict(zip(keys.tolist(), vals.tolist()))
    expect = dict(build)  # key -> last *acked* value (build value if none)
    touched, n_acked, n_refused = set(), 0, 0
    for r in recs:
        if r.op != "update":
            continue
        touched.add(r.key)
        if r.outcome == "ok":
            expect[r.key] = r.value
            n_acked += 1
        else:
            n_refused += 1
    karr = np.fromiter(touched, dtype=np.uint64, count=len(touched))
    h = st.submit("get", karr)
    st.flush()
    res = h.result()
    got = {int(k): int(v) for k, v in zip(karr.tolist(), res.values)}
    lost = [k for k in got if got[k] != expect[k]]
    if lost:
        raise RuntimeError(
            f"lost acked writes: {len(lost)}/{len(touched)} touched keys "
            f"read back wrong (e.g. key {lost[0]}: got {got[lost[0]]}, "
            f"last ack {expect[lost[0]]})")
    return ("slo/acked_writes", 0.0,
            f"0 lost of {n_acked} acked ({n_refused} refused) over "
            f"{len(touched)} keys",
            {"schema": SLO_SCHEMA, "acked": n_acked, "refused": n_refused,
             "keys_touched": len(touched), "lost": 0,
             "stats": fd.stats(), "admission": cfg.to_json_dict(),
             "spec": _spec().to_json_dict(),
             "traffic": spec.to_json_dict()})


# ------------------------------------------------------- dormant identity
def _dormant_identity_row(keys, vals, quick: bool):
    """A default-config FrontDoor versus calling the stack directly:
    meters, recorded trace, and final MN state must be byte-identical.
    Raises on any drift (an ERROR row, non-zero harness exit)."""
    n_ops = 2_000 if quick else 6_000
    idx = C.zipf_indices(len(keys), n_ops, seed=700)
    ops = []
    for j, i in enumerate(idx):
        k = int(keys[i])
        if j % 7 == 3:
            ops.append(("update", k, j))
        elif j % 31 == 10:
            ops.append(("insert", (k ^ 0xA5A5_5A5A) | 1, j))
        elif j % 53 == 20:
            ops.append(("delete", k, None))
        else:
            ops.append(("get", k, None))
    snaps, traces, states = [], [], []
    for through_door in (False, True):
        st, tr = _store(keys, vals)
        if through_door:
            fd = FrontDoor(st, FrontDoorConfig())
            for t, (op, k, v) in enumerate(ops):
                fd.offer("t0", op, k, v, t_s=t * 1e-6)
            fd.flush()
        else:
            for op, k, v in ops:
                st.submit(op, k, v)
            st.flush()
        snaps.append(st.meter_totals().snapshot())
        traces.append(tr.trace)
        states.append(pickle.dumps(st.engine.mn_state()))
    if snaps[0] != snaps[1]:
        diff = {k: (snaps[0][k], snaps[1][k]) for k in snaps[0]
                if snaps[0][k] != snaps[1][k]}
        raise RuntimeError(f"the dormant front door perturbed meters: "
                           f"{diff}")
    if traces[0] != traces[1]:
        raise RuntimeError("the dormant front door perturbed the trace")
    if states[0] != states[1]:
        raise RuntimeError("the dormant front door perturbed MN state")
    return ("slo/dormant_identity", 0.0, "identical",
            {"schema": SLO_SCHEMA, "ops": n_ops,
             "round_trips": int(snaps[0]["round_trips"]),
             "trace_items": len(traces[0]),
             "spec": _spec().to_json_dict()})
