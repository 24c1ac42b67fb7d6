"""The device Get reuses the CN and MN arrays it uploaded until a write
changes them: after every kind of write, ``get_batch(xp=jnp)`` answers
exactly as ``get_batch(xp=np)`` on the same store and as a plain map."""

import copy

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import StoreSpec, open_store
from repro.core.hashing import split_u64, splitmix64
from repro.core.outback import OutbackShard
from repro.core.store import make_uniform_keys

N = 3000
KEYS = make_uniform_keys(N, 11)
FRESH = splitmix64(np.arange(1, 2000, dtype=np.uint64) + np.uint64(5 << 44))
ABSENT = splitmix64(np.arange(1, 200, dtype=np.uint64) + np.uint64(3 << 46))
PRESSURED = dict(load_factor=0.95, overflow_frac=0.05, rng_seed=3)


def _val(k) -> int:
    return int(splitmix64(np.uint64([k]))[0])


def _new_val(k) -> int:
    return _val(k) ^ 0x5A5A5A5A5A5A5A5A


class Case:
    """A shard (or an ``outback-dir`` store's directory) and a plain map of
    what it holds."""

    def __init__(self, engine, oracle: dict):
        self.engine, self.oracle = engine, oracle

    def insert(self, k, v=None) -> str:
        v = _val(k) if v is None else v
        case = self.engine.insert(int(k), v)
        if case != "frozen":
            self.oracle[int(k)] = v
        return case

    def shards(self) -> list:
        return getattr(self.engine, "tables", [self.engine])


def _shard(**kw) -> Case:
    return Case(OutbackShard(KEYS, splitmix64(KEYS), **kw),
                dict(zip(KEYS.tolist(), splitmix64(KEYS).tolist())))


def _first_fresh(case: str) -> int:
    """Index of the first fresh key whose insert resolves as ``case`` once
    every fresh key before it is in (on a pressured shard)."""
    probe = _shard(**PRESSURED)
    for i, k in enumerate(FRESH):
        if probe.insert(k) == case:
            return i
    raise AssertionError(f"no fresh key resolves as {case!r}")


def _pressured(before: int) -> Case:
    c = _shard(**PRESSURED)
    for k in FRESH[:before]:
        c.insert(k)
    return c


def _overflow_resident() -> tuple[Case, int]:
    i = _first_fresh("overflow")
    return _pressured(i + 1), int(FRESH[i])


def _queries(c: Case) -> np.ndarray:
    present = np.fromiter(c.oracle, np.uint64)
    gone = np.setdiff1d(np.concatenate([KEYS, FRESH[:400]]), present)
    return np.concatenate([present, gone, ABSENT])


def _warm(c: Case) -> None:
    c.engine.get_batch(_queries(c)[:256], xp=jnp)


def _kept(c: Case) -> list:
    """Per shard and half: the device copy now kept and the host arrays'
    contents it was made from."""
    return [(sh, half, half(jnp), [a.copy() for a in half(np)])
            for sh in c.shards() for half in (sh.cn_arrays, sh.mn_arrays)]


def _same(a: list, b: tuple) -> bool:
    return all(x.shape == y.shape and (x == y).all() for x, y in zip(a, b))


def _check(c: Case, kept: list) -> None:
    # a write that changed a half's host arrays dropped its device copy (on
    # the CPU a device array may alias its host array, so the answers alone
    # could miss a stale copy there)
    for sh, half, copy_, host in kept:
        if sh in c.shards() and not _same(host, half(np)):
            assert half(jnp) is not copy_
    q = _queries(c)
    raw_dev = c.engine.get_batch(q, xp=jnp, resolve_makeup=False)
    raw_host = c.engine.get_batch(q, xp=np, resolve_makeup=False)
    for d, h in zip(raw_dev, raw_host):
        np.testing.assert_array_equal(np.asarray(d), np.asarray(h))
    v_lo, v_hi, match = (np.asarray(a) for a in c.engine.get_batch(
        q, xp=jnp, resolve_makeup=True))
    want = [c.oracle.get(int(k)) for k in q]
    np.testing.assert_array_equal(match, [w is not None for w in want])
    got = (v_hi.astype(np.uint64) << np.uint64(32)) | v_lo
    np.testing.assert_array_equal(got[match],
                                  [w for w in want if w is not None])


# each write kind: (set-up before the device copy is made, the write)

def insert_case(kind):
    def setup():
        i = _first_fresh(kind)
        return _pressured(i), int(FRESH[i])

    def write(c, k):
        assert c.insert(k) == kind
    return setup, write


def insert_existing():
    return _shard(), int(KEYS[5])


def write_insert_existing(c, k):
    assert c.insert(k, _new_val(k)) == "update"


def update_fast():
    return _shard(), KEYS[:64]


def write_update(c, keys):
    vals = np.array([_new_val(k) for k in keys], np.uint64)
    assert c.engine.update_batch(keys, vals).all()
    c.oracle.update(zip(keys.tolist(), vals.tolist()))


def update_scalar():
    return _shard(), KEYS[:8]


def write_update_scalar(c, keys):
    for k in keys.tolist():
        assert c.engine.update(k, _new_val(k))
        c.oracle[k] = _new_val(k)


def update_residual():
    c, k = _overflow_resident()
    return c, np.array([k], np.uint64)


def update_stale_seed():
    """Present keys that the replica's stale CN seeds send to a slot of
    another key: the update retries every slot of the bucket."""
    c, _ = makeup_refresh()
    present = np.fromiter(c.oracle, np.uint64)
    raw = c.engine.get_batch(present, resolve_makeup=False)
    in_slot = c.engine.overflow.lookup_batch(*split_u64(present))[0] < 0
    return c, present[~raw[2] & in_slot][:1]  # later lanes find it fresh


def write_update_stale_seed(c, keys):
    seeds = c.engine.cn.seeds.copy()
    write_update(c, keys)
    assert (c.engine.cn.seeds != seeds).any()


def delete_fast():
    return _shard(), KEYS[:64]


def write_delete(c, keys):
    assert c.engine.delete_batch(keys).all()
    for k in keys.tolist():
        del c.oracle[k]


def delete_residual():
    c, k = _overflow_resident()
    return c, (k, int(KEYS[7]))


def write_delete_residual(c, keys):
    overflow_key, slot_key = keys
    assert c.engine.delete_batch(np.array([overflow_key], np.uint64)).all()
    assert c.engine.delete(slot_key)  # the scalar walk clears a slot
    del c.oracle[overflow_key], c.oracle[slot_key]


def heap_growth():
    return _shard(heap_cap=N), FRESH[:32]


def write_heap_growth(c, keys):
    cap = c.engine.heap_klo.shape[0]
    for k in keys:
        c.insert(k)
    assert c.engine.heap_klo.shape[0] > cap


def _reseeded_twin() -> tuple[Case, Case, int]:
    """Twin shards; the second took an insert that re-seeded a bucket."""
    i = _first_fresh("reseed")
    a, b = _pressured(i), _pressured(i)
    assert b.insert(FRESH[i]) == "reseed"
    return a, b, int(FRESH[i])


def install_mn_state():
    a, b, _ = _reseeded_twin()
    return a, b


def write_install(c, twin):
    c.engine.install_mn_state(twin.engine.mn_state())
    c.oracle = dict(twin.oracle)


def makeup_refresh():
    """A replica that installed its twin's MN half: its CN seeds are stale
    for the re-seeded bucket until a Makeup-Get refreshes them."""
    a, b, _ = _reseeded_twin()
    a.engine.install_mn_state(b.engine.mn_state())
    a.oracle = dict(b.oracle)
    return a, None


def write_makeup_refresh(c, _):
    seeds = c.engine.cn.seeds.copy()
    c.engine.get_batch(_queries(c), xp=np, resolve_makeup=True)
    assert (c.engine.cn.seeds != seeds).any()


def write_makeup_refresh_scalar(c, _):
    seeds = c.engine.cn.seeds.copy()
    for k in _queries(c):
        c.engine.get(int(k))
    assert (c.engine.cn.seeds != seeds).any()


def from_state():
    return _shard(), None


def write_from_state(c, _):
    sh = c.engine
    c.engine = OutbackShard._from_state(copy.deepcopy(sh.cn), sh.mn_state(),
                                        load_factor=sh.load_factor)


def dir_split():
    st = open_store(StoreSpec("outback-dir", load_factor=0.85), KEYS,
                    splitmix64(KEYS))
    return Case(st.engine, dict(zip(KEYS.tolist(),
                                    splitmix64(KEYS).tolist()))), None


def write_dir_split(c, _):
    tables = len(c.engine.tables)
    h = c.engine.begin_split(0)
    h.build()
    h.finish()
    assert len(c.engine.tables) == tables + 1


CASES = {
    "insert_free_slot": insert_case("slot"),
    "insert_reseed": insert_case("reseed"),
    "insert_overflow": insert_case("overflow"),
    "insert_existing_key": (insert_existing, write_insert_existing),
    "update_fast": (update_fast, write_update),
    "update_scalar": (update_scalar, write_update_scalar),
    "update_residual": (update_residual, write_update),
    "update_stale_seed": (update_stale_seed, write_update_stale_seed),
    "delete_fast": (delete_fast, write_delete),
    "delete_residual": (delete_residual, write_delete_residual),
    "heap_growth": (heap_growth, write_heap_growth),
    "makeup_seed_refresh": (makeup_refresh, write_makeup_refresh),
    "makeup_seed_refresh_scalar": (makeup_refresh,
                                   write_makeup_refresh_scalar),
    "install_mn_state": (install_mn_state, write_install),
    "from_state": (from_state, write_from_state),
    "outback_dir_split": (dir_split, write_dir_split),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_get_is_exact_after_a_write(case):
    setup, write = CASES[case]
    c, arg = setup()
    _warm(c)  # the device copies are made here and kept
    kept = _kept(c)
    write(c, arg)
    _check(c, kept)
    _check(c, _kept(c))  # and again, on the copies the first check kept
