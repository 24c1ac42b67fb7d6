"""Wall-clock spans and counters of the served Get and update path
(``repro.obs.wall``): where each span lands in a profiler trace, what the
counters count, and that neither changes an answer or a meter."""

import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.api import StoreSpec, open_store
from repro.core.hashing import splitmix64
from repro.core.outback import OutbackShard
from repro.core.store import make_uniform_keys
from repro.obs import wall

N = 4000
CALLER = "client.call"
KEYS = make_uniform_keys(N, 7)
FRESH = splitmix64(np.arange(1, 600, dtype=np.uint64) + np.uint64(9 << 40))
ABSENT = splitmix64(np.arange(1, 64, dtype=np.uint64) + np.uint64(1 << 45))
# slot residents, overflow residents of the pressured stores, absent keys
QUERIES = np.concatenate([KEYS[:800], FRESH[:400], ABSENT])


def _queries(cache: bool):
    """Keys that all sit in slots for the plain store (no lane for the
    Makeup-Get), every case of it for the pressured one."""
    return QUERIES if cache else KEYS[:1024]


def _store(cache: bool):
    """``plain``: a fresh store, no key in overflow.  ``cache``: a CN cache
    in front of a store driven past ``s_slow`` (as in
    tests/test_makeup_batch.py), so misses take the Makeup-Get."""
    if not cache:
        return open_store(StoreSpec("outback"), KEYS, splitmix64(KEYS))
    st = open_store(StoreSpec("outback", load_factor=0.95, rng_seed=3,
                              cache_budget_bytes=1 << 12,
                              params={"overflow_frac": 0.05}),
                    KEYS, splitmix64(KEYS))
    for k in FRESH:
        if st.engine.must_stop():
            break
        st.insert(int(k), int(splitmix64(np.uint64([k]))[0]))
    return st


def _pressured_shard():
    sh = OutbackShard(KEYS, splitmix64(KEYS), load_factor=0.95,
                      overflow_frac=0.05, rng_seed=3)
    for k in FRESH:
        if sh.must_stop():
            break
        sh.insert(int(k), int(splitmix64(np.uint64([k]))[0]))
    return sh


def _traced(trace_dir, call):
    """Run ``call`` inside a ``CALLER`` span under a profiler trace; return
    its result, the caller's (start, end) and the program's spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with TraceAnnotation(CALLER):
            out = call()
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events]
            outer = [(s, e) for n, s, e in events if n == CALLER]
            if outer:
                spans = sorted((ev for ev in events
                                if ev[0].startswith("repro.")),
                               key=lambda ev: ev[1])
                return out, outer[0], spans
    raise AssertionError("no caller span in the trace")


def _upload_bytes(engine, lanes: int) -> int:
    host = engine._cn_host() + engine._mn_host()
    return sum(a.nbytes for a in host) + 8 * lanes


GET_SPANS = [wall.GET_UPLOAD, wall.GET_DISPATCH, wall.GET_FETCH]


@pytest.mark.parametrize("case,want", [
    ("plain", GET_SPANS),
    ("cache", [wall.CACHE_PROBE] + GET_SPANS
     + [wall.GET_MAKEUP, wall.GET_UPLOAD, wall.GET_FETCH,
        wall.CACHE_OBSERVE]),
    ("update", [wall.CACHE_NOTE]),
])
def test_spans_of_one_call(tmp_path, case, want):
    st = _store(cache=case != "plain")
    q = _queries(cache=case != "plain")
    st.get_batch(q[:64], xp=jnp)  # compile outside the trace
    if case == "update":
        def call():
            return st.update_batch(KEYS[:64], splitmix64(KEYS[:64]))
    else:
        def call():
            return st.get_batch(q, xp=jnp)
    _, (a, b), spans = _traced(tmp_path, call)
    assert [n for n, _, _ in spans] == want
    # siblings on the calling thread: inside the caller, none overlapping
    assert all(a <= s <= e <= b for _, s, e in spans)
    assert all(e0 <= s1 for (_, _, e0), (_, s1, _) in zip(spans, spans[1:]))


@pytest.mark.parametrize("cache", [False, True], ids=["plain", "cache"])
def test_h2d_bytes_count_every_upload(cache):
    st = _store(cache)
    before = wall.totals().get(wall.H2D_BYTES, 0)
    q = _queries(cache)
    res = st.get_batch(q, xp=jnp)
    grown = wall.totals()[wall.H2D_BYTES] - before
    sent = q.shape[0] - res.cache_hits - res.cache_neg_hits
    want = _upload_bytes(st.engine, sent)
    assert bool(res.makeups) == cache
    if cache:
        want += 9 * sent  # Makeup answers re-uploaded: v_lo, v_hi, match
    assert grown == want


def _stale_seed_shard():
    """A replica that installed the MN half of a twin whose insert re-seeded
    a bucket: a Makeup-Get will refresh its CN seeds."""
    a, b = (OutbackShard(KEYS, splitmix64(KEYS), load_factor=0.95,
                         rng_seed=3) for _ in range(2))
    for k in FRESH:
        if b.insert(int(k), int(splitmix64(np.uint64([k]))[0])) == "reseed":
            break
    a.install_mn_state(b.mn_state())
    return a


def _nbytes(arrays) -> int:
    return sum(a.nbytes for a in arrays)


@pytest.mark.parametrize("write", ["none", "update", "seed refresh"])
def test_a_resident_store_sends_only_what_changed(write):
    """After the first device call, a call sends the key halves plus the
    half (CN or MN) that a write changed since, and counts an MN upload
    only where it re-sent the MN arrays."""
    sh = _stale_seed_shard()
    sh.get_batch(QUERIES, xp=jnp)  # the first call uploads both halves
    if write == "update":
        assert sh.update_batch(KEYS[:64], splitmix64(KEYS[:64] + 1)).all()
    if write == "seed refresh":
        seeds = sh.cn.seeds.copy()
        sh.get_batch(QUERIES, xp=np, resolve_makeup=True)
        assert (sh.cn.seeds != seeds).any()
    before = wall.totals()
    sh.get_batch(QUERIES, xp=jnp)
    grown = {n: wall.totals()[n] - before.get(n, 0)
             for n in (wall.H2D_BYTES, wall.MN_UPLOADS)}
    want = 8 * QUERIES.shape[0]
    want += {"none": 0, "update": _nbytes(sh._mn_host()),
             "seed refresh": _nbytes(sh._cn_host())}[write]
    assert grown == {wall.H2D_BYTES: want,
                     wall.MN_UPLOADS: int(write == "update")}


@pytest.mark.parametrize("cache", [False, True], ids=["plain", "cache"])
def test_the_host_path_makes_no_device_array(cache):
    st = _store(cache)
    live = {id(a) for a in jax.live_arrays()}
    before = wall.totals()
    st.get_batch(_queries(cache), xp=np)
    st.engine.get_batch(_queries(cache), xp=np, resolve_makeup=True)
    assert [a for a in jax.live_arrays() if id(a) not in live] == []
    assert {n: wall.totals().get(n, 0) for n in (wall.H2D_BYTES,
                                                 wall.MN_UPLOADS)} == \
        {n: before.get(n, 0) for n in (wall.H2D_BYTES, wall.MN_UPLOADS)}


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_makeup_lanes_match_the_reference(xp):
    a, b = _pressured_shard(), _pressured_shard()
    raw = b.get_batch(QUERIES, resolve_makeup=False)
    pending = ~np.asarray(raw[2])
    assert pending.sum() > 200, "workload sized for a real makeup wave"
    want = b._resolve_makeups_reference(QUERIES, *raw, xp=np)
    before = wall.totals().get(wall.MAKEUP_LANES, 0)
    got = a.get_batch(QUERIES, xp=xp, resolve_makeup=True)
    assert wall.totals()[wall.MAKEUP_LANES] - before == pending.sum()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("cache", [False, True], ids=["plain", "cache"])
def test_profiler_changes_no_answer_or_meter(tmp_path, cache):
    q = _queries(cache)

    def session(st):
        got = [st.get_batch(q, xp=jnp)]
        got.append(st.update_batch(KEYS[:64], splitmix64(KEYS[:64] + 1)))
        got.append(st.get_batch(q, xp=jnp))
        return got

    plain, traced = _store(cache), _store(cache)
    want = session(plain)
    got, _, spans = _traced(tmp_path, lambda: session(traced))
    assert spans
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.values, w.values)
        np.testing.assert_array_equal(g.found, w.found)
    assert traced.meter_totals().snapshot() == plain.meter_totals().snapshot()


@pytest.mark.parametrize("ring,t0,want", [
    (16, 0, 10),  # nothing dropped: the counter was 0 before its samples
    (16, 3, 7),
    (4, 7, 3),  # the samples the interval needs are still in the ring
    (4, 5, None),  # the sample at or before t0 has been dropped
    (4, 0, None),
])
def test_delta_over_a_bounded_ring(monkeypatch, ring, t0, want):
    clock = iter(range(1, 100))
    monkeypatch.setattr(wall, "RING_SAMPLES", ring)
    fake = types.SimpleNamespace(perf_counter=lambda: next(clock))
    monkeypatch.setattr(wall, "time", fake)
    monkeypatch.setattr(wall, "_rings", {})
    monkeypatch.setattr(wall, "_totals", {})
    monkeypatch.setattr(wall, "_dropped", set())
    for _ in range(10):
        wall.count("test.ring", 1)  # samples (1, 1) ... (10, 10)
    assert wall.delta("test.ring", t0, 50) == want
    assert wall.totals() == {"test.ring": 10}


# A replicated store through one scripted crash of its primary: rounds of
# R_GETS Gets then R_UPDATES updates; the op clock counts lanes, and a call
# sees it after its own.  MN 0 goes down on round 2's Get (its one backoff
# and retry add R_GETS lanes) and restarts on round 5's Get.
R_GETS, R_UPDATES = 256, 128
R_ROUND = R_GETS + R_UPDATES
CRASH_AT = 2 * R_ROUND + 1
RESTART_AT = 5 * R_ROUND + R_GETS + 1  # round 4's updates end below it
REPLICA_SPANS = (wall.REPLICA_FAILOVER, wall.RETRY_BACKOFF,
                 wall.REPLICA_RESYNC, wall.REPLICA_FANOUT)
REPLICA_COUNTERS = (wall.REPLICA_FAILOVERS, wall.REPLICA_RESYNCS,
                    wall.REPLICA_RESYNC_BYTES, wall.RETRY_BACKOFF_LANES)


def _replicated():
    from repro.net import FaultEvent, FaultSchedule
    crash = FaultEvent("mn_crash", CRASH_AT, RESTART_AT - CRASH_AT, mn=0,
                       down_s=1.0)
    return open_store(StoreSpec("outback", load_factor=0.85, replicas=2,
                                faults=FaultSchedule(events=(crash,))),
                      KEYS, splitmix64(KEYS))


def _replica_session(st, annotate=False):
    """Seven rounds; each call in a ``client.<op>`` span when annotating."""
    got = []
    for r in range(7):
        gets = KEYS[r * R_GETS:(r + 1) * R_GETS]
        upd = KEYS[-(r + 1) * R_UPDATES:][:R_UPDATES]
        for op, call in (("get", lambda: st.get_batch(gets, xp=jnp)),
                         ("update", lambda: st.update_batch(
                             upd, splitmix64(upd + np.uint64(r + 1))))):
            if annotate:
                with TraceAnnotation(f"client.{op}"):
                    got.append(call())
            else:
                got.append(call())
    return got


def test_replica_counters_of_one_scripted_crash():
    st = _replicated()
    before = {n: wall.totals().get(n, 0) for n in REPLICA_COUNTERS}
    got = _replica_session(st)
    grown = {n: wall.totals().get(n, 0) - before[n] for n in REPLICA_COUNTERS}
    rs = st
    while not hasattr(rs, "replicas"):
        rs = rs.inner
    assert grown == {wall.REPLICA_FAILOVERS: 1, wall.REPLICA_RESYNCS: 1,
                     wall.REPLICA_RESYNC_BYTES:
                         rs.replicas[1].engine.mn_state_bytes(),
                     wall.RETRY_BACKOFF_LANES: R_GETS}
    assert [bool(r.failovers) for r in got[::2]] == \
        [False, False, True, False, False, False, False]


def test_replica_spans_nest_in_their_calls(tmp_path):
    """Failover and backoff lie in round 2's Get call, side by side; the
    retried Get's own spans lie in the backoff span; the resync and the
    fail-back to MN 0 lie in round 5's Get; a fan-out lies in each update
    call made while both replicas are up.  Answers and meters match an
    untraced twin run."""
    plain, traced = _replicated(), _replicated()
    want = _replica_session(plain)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got = _replica_session(traced, annotate=True)
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    events = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for plane in ProfileData.from_file(str(xplane)).planes
                     for line in plane.lines for e in line.events),
                    key=lambda ev: ev[1])
    calls = sorted((s, e) for n, s, e in events if n.startswith("client."))
    assert len(calls) == 14

    def call_of(s, e):
        return [i for i, (a, b) in enumerate(calls) if a <= s and e <= b]

    placed = {n: [] for n in REPLICA_SPANS}
    for n, s, e in events:
        if n in placed:
            placed[n] += call_of(s, e)
    assert placed == {wall.REPLICA_FAILOVER: [4, 10],
                      wall.RETRY_BACKOFF: [4], wall.REPLICA_RESYNC: [10],
                      wall.REPLICA_FANOUT: [1, 3, 11, 13]}
    (f0, f1), (r0, r1) = [(s, e) for n, s, e in events
                          if n == wall.REPLICA_FAILOVER]
    (s0, s1), = [(s, e) for n, s, e in events if n == wall.REPLICA_RESYNC]
    assert s1 <= r0
    (b0, b1), = [(s, e) for n, s, e in events if n == wall.RETRY_BACKOFF]
    assert f1 <= b0
    assert [n for n, s, e in events if b0 <= s and e <= b1
            and n.startswith("repro.get.")] == GET_SPANS
    for g, w in zip(got, want, strict=True):
        np.testing.assert_array_equal(g.values, w.values)
        np.testing.assert_array_equal(g.found, w.found)
    assert traced.meter_totals().snapshot() == plain.meter_totals().snapshot()
