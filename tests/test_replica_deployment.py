"""A 2-way replicated store served through the device Get, through a memory
node's crash and restart: every answer against a plain map, twins that are
copies of one build, and a crashed replica that keeps nothing on the
device."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ReplicaSetAdapter, StoreSpec, build_adapter,
                       open_store)
from repro.core.hashing import splitmix64
from repro.core.store import make_uniform_keys
from repro.net import FaultEvent, FaultSchedule, Transport
from repro.obs import wall

N = 2048
KEYS = make_uniform_keys(N, 23)
VALUES = splitmix64(KEYS)
ABSENT = splitmix64(np.arange(1, 65, dtype=np.uint64) + np.uint64(7 << 50))
GETS, UPDATES = 96, 64
ROUND = GETS + UPDATES  # op-clock ticks of one round without a retry


def _spec(kind="outback", *, mn=0, at_op=None, duration=4 * ROUND,
          **params):
    """Two replicas; with ``at_op``, MN ``mn`` is down ``duration`` ops of
    the op clock from ``at_op``."""
    faults = None
    if at_op is not None:
        faults = FaultSchedule(
            events=(FaultEvent("mn_crash", at_op, duration, mn=mn,
                               down_s=1.0),))
    return StoreSpec(kind, load_factor=0.85, replicas=2, faults=faults,
                     params=params)


def _replica_set(st) -> ReplicaSetAdapter:
    while not isinstance(st, ReplicaSetAdapter):
        st = st.inner
    return st


def _state_sig(x):
    """Comparable form of an ``mn_state`` tree (the directory store's
    shipped CN locators left out)."""
    if isinstance(x, dict):
        return tuple(sorted((k, _state_sig(v)) for k, v in x.items()
                            if k != "cn"))
    if isinstance(x, np.ndarray):
        return (x.dtype.str, x.shape, x.tobytes())
    if isinstance(x, (list, tuple)):
        return tuple(_state_sig(v) for v in x)
    return x


def _shards(engine) -> list:
    return list(getattr(engine, "tables", [engine]))


class Oracle:
    """The plain map the store must agree with."""

    def __init__(self):
        self.values = VALUES.copy()

    def get(self, keys):
        pos = np.minimum(np.searchsorted(KEYS, keys), N - 1)
        found = KEYS[pos] == keys
        return found, np.where(found, self.values[pos], np.uint64(0))

    def update(self, keys, values):
        self.values[np.searchsorted(KEYS, keys)] = values


def _round(rng, r):
    """One round: Gets (loaded and absent keys), then updates of distinct
    loaded keys to values no earlier round wrote."""
    gets = np.concatenate([KEYS[rng.integers(0, N, GETS - 8)],
                           ABSENT[rng.integers(0, ABSENT.size, 8)]])
    upd = KEYS[rng.choice(N, UPDATES, replace=False)]
    return gets, upd, splitmix64(upd ^ np.uint64(0x5DEECE66D + r))


def _assert_get(res, oracle, keys):
    found, values = oracle.get(keys)
    np.testing.assert_array_equal(res.found, found)
    np.testing.assert_array_equal(res.values[found], values[found])


@pytest.mark.parametrize("crashed", ["primary", "secondary"])
@pytest.mark.parametrize("opens", ["at_get", "at_update"])
@pytest.mark.parametrize("closes", ["at_get", "at_update"])
def test_every_answer_through_a_crash_and_restart(crashed, opens, closes):
    """The crash window opens on round 3's Get call (before its writes) or
    on its update call (inside them), and the restart lands on round 8's
    Get or update call; write rounds follow the restart."""
    # a call sees the op clock after its own lanes: round r's Get ends at
    # r * ROUND + GETS, its updates at (r + 1) * ROUND; a crashed primary's
    # Get is retried once, which adds GETS to the clock from then on
    at_op = 3 * ROUND + (1 if opens == "at_get" else GETS + 1)
    shift = GETS if crashed == "primary" else 0
    end = 8 * ROUND + shift + (1 if closes == "at_get" else GETS + 1)
    st = open_store(_spec(mn=0 if crashed == "primary" else 1, at_op=at_op,
                          duration=end - at_op), KEYS, VALUES)
    rs = _replica_set(st)
    oracle, rng = Oracle(), np.random.default_rng(5)
    restarted = []
    for r in range(12):
        gets, upd, vals = _round(rng, r)
        resyncs = rs.meter_totals().resyncs
        _assert_get(st.get_batch(gets, xp=jnp), oracle, gets)
        if rs.meter_totals().resyncs > resyncs:
            restarted.append((r, "at_get"))
        resyncs = rs.meter_totals().resyncs
        ack = st.update_batch(upd, vals)
        assert ack.found.all() and set(ack.statuses) <= {"ok", "update"}
        oracle.update(upd, vals)
        if rs.meter_totals().resyncs > resyncs:
            restarted.append((r, "at_update"))
    assert restarted == [(8, closes)]
    m = rs.meter_totals()
    assert m.failovers == int(crashed == "primary")
    assert rs.primary == 0  # a restarted MN 0 takes reads back
    sigs = {_state_sig(rep.engine.mn_state()) for rep in rs.replicas}
    assert len(sigs) == 1, "replicas diverged after the restart"
    q = np.concatenate([KEYS, ABSENT])
    for rep in rs.replicas:  # each replica read directly
        _assert_get(rep.get_batch(q, xp=jnp), oracle, q)


@pytest.mark.parametrize("kind,params", [("outback", {}),
                                         ("outback-dir",
                                          {"initial_depth": 1})])
def test_twins_are_copies_of_one_build(kind, params):
    tr = Transport()
    adapter, _ = build_adapter(_spec(kind, **params), KEYS, VALUES,
                               transport=tr)
    a, b = (rep.engine for rep in adapter.replicas)
    assert a is not b
    assert _state_sig(a.mn_state()) == _state_sig(b.mn_state())
    for sa, sb in zip(_shards(a), _shards(b), strict=True):
        for x, y in zip(sa._cn_host() + sa._mn_host(),
                        sb._cn_host() + sb._mn_host(), strict=True):
            np.testing.assert_array_equal(x, y)
            assert not np.shares_memory(x, y)
        assert sa.meter is not sb.meter
        assert sa.meter.sink is tr and sb.meter.sink is tr
    assert adapter.replicas[0].spec is adapter.replicas[1].spec
    ra, rb = adapter.replicas
    zero = ra.meter_totals().snapshot()
    assert set(zero.values()) == {0}
    assert rb.meter_totals().snapshot() == zero
    untouched = _state_sig(a.mn_state())
    ack = rb.update_batch(KEYS[:32], VALUES[:32] + np.uint64(1))
    assert ack.found.all()
    assert _state_sig(a.mn_state()) == untouched
    assert _state_sig(b.mn_state()) != untouched
    assert ra.meter_totals().snapshot() == zero
    assert rb.meter_totals().snapshot() != zero


@pytest.mark.parametrize("kind,params", [("outback", {}),
                                         ("outback-dir",
                                          {"initial_depth": 0})])
def test_a_crashed_primary_keeps_no_device_copy(kind, params):
    """The failover frees the dead primary's device copies before the new
    primary's first device Get, which uploads its MN arrays exactly once;
    at the restart MN 0 takes reads back, MN 1's copies are freed and MN 0
    uploads once."""
    # the window opens on the first Get after two rounds and one more Get,
    # and closes on the Get after four more rounds: the clock then stands
    # at 6 rounds and 4 Gets (the extra Get, and the failed one retried)
    at_op = 2 * ROUND + GETS + 1
    st = open_store(_spec(kind, at_op=at_op,
                          duration=6 * ROUND + 4 * GETS + 1 - at_op,
                          **params), KEYS, VALUES)
    rs = _replica_set(st)
    first, second = (_shards(rep.engine) for rep in rs.replicas)
    oracle, rng = Oracle(), np.random.default_rng(9)
    for r in range(2):  # the primary serves and keeps its copies
        gets, upd, vals = _round(rng, r)
        st.get_batch(gets, xp=jnp)
        st.update_batch(upd, vals)
        oracle.update(upd, vals)
    st.get_batch(KEYS[:GETS], xp=jnp)  # no write since: copies are kept
    assert all(set(t._dev) == {"cn", "mn"} for t in first)
    assert all(t._dev == {} for t in second)
    before = wall.totals().get(wall.MN_UPLOADS, 0)
    res = st.get_batch(KEYS[:GETS], xp=jnp)  # backs off, fails over
    assert res.failovers == 1 and rs.primary == 1
    assert wall.totals()[wall.MN_UPLOADS] - before == 1
    assert all(t._dev == {} for t in first)
    assert all(set(t._dev) == {"cn", "mn"} for t in second)
    st.get_batch(KEYS[:GETS], xp=jnp)  # resident on the new primary
    assert wall.totals()[wall.MN_UPLOADS] - before == 1
    for r in range(2, 6):  # writes while MN 0 is down, then its restart
        gets, upd, vals = _round(rng, r)
        st.update_batch(upd, vals)
        oracle.update(upd, vals)
        assert rs.primary == 1 and rs.meter_totals().resyncs == 0
        st.get_batch(gets, xp=jnp)
    before = wall.totals()[wall.MN_UPLOADS]
    _assert_get(st.get_batch(KEYS, xp=jnp), oracle, KEYS)  # resyncs
    assert rs.meter_totals().resyncs == 1 and rs.primary == 0
    assert all(t._dev == {} for t in second)
    assert all(set(t._dev) == {"cn", "mn"} for t in first)
    assert wall.totals()[wall.MN_UPLOADS] - before == 1


def test_a_resync_that_installs_nothing_is_read_back_wrong(monkeypatch):
    """After the restart the primary is the restarted replica, so a resync
    that leaves it stale shows in the Gets' answers."""
    from repro.core import outback
    monkeypatch.setattr(outback.OutbackShard, "install_mn_state",
                        lambda self, state: None)
    st = open_store(_spec(at_op=2 * ROUND + 1, duration=4 * ROUND), KEYS,
                    VALUES)
    rs, oracle, rng = _replica_set(st), Oracle(), np.random.default_rng(3)
    for r in range(8):
        gets, upd, vals = _round(rng, r)
        st.get_batch(gets, xp=jnp)
        st.update_batch(upd, vals)
        oracle.update(upd, vals)
    assert rs.meter_totals().resyncs == 1 and rs.primary == 0
    found, values = oracle.get(KEYS)
    res = st.get_batch(KEYS, xp=jnp)
    assert res.found.all()
    assert (res.values != values).sum() > 0
