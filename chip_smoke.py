"""Chip smoke: drive the Outback KVS main path once on a TPU, at 2^25 pairs.

Run from the root of a checkout, on a machine whose JAX sees a TPU:

    python chip_smoke.py [--pairs N] [--seed S] [--chips 4]

One chip (the default).  Build an ``outback`` store through
``repro.api.open_store`` from ``--pairs`` distinct 8 B keys with 8 B values
``splitmix64(key)`` at load factor 0.85 (the paper's §5 record shape).
Answer two Get batches of 65,536 present keys (uniform, then zipf 0.99),
each with 4,096 absent keys appended, through ``store.get_batch(q,
xp=jnp)``.  Then insert 1,024 fresh pairs, update 1,024 present keys and
delete 1,024 others through the same store, and read every acknowledged
write back through the device Get.

``--chips 4``.  Build a ``sharded`` store (4 shards, ``data_parallel=1``),
place its mesh state on a ``(data=1, model=4)`` mesh of the four TPUs and
answer 65,536 uniform present keys plus 4,096 absent keys with the SPMD
Get of ``repro.core.sharded_kvs.make_get_fn``.  No other phase runs.

Every answer is checked against the oracle: a present key carries
``splitmix64(key)`` (or the value last written to it), an absent or deleted
key is not found.  Any failed check or exception exits non-zero, and so
does a machine whose first JAX device is not a TPU.  The last line of
standard output is ``{"ok": true, "device": {...}}``.  The seconds printed
before it are timings of one smoke run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import resource
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
LOAD_FACTOR = 0.85
GET_BATCH = 65_536
ABSENT = 4_096
WRITES = 1_024
WARM_CALLS = 3
# pre-image offsets of the absent and fresh key sets: disjoint from the
# ``make_uniform_keys`` pre-images of any seed below 256
ABSENT_SALT = 0xA << 40
FRESH_SALT = 0xB << 40
WRITE_SALT = np.uint64(0x5DEECE66D)  # written values differ from the loaded

CN_ARRAYS = ("words_a", "words_b", "seeds")
MN_ARRAYS = ("slots_lo", "slots_hi", "heap_klo", "heap_khi", "heap_vlo",
             "heap_vhi")


def check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAIL: {what}")


def report(name: str, seconds: float) -> None:
    print(f"{name}_seconds={seconds!r}", flush=True)


def report_peak_rss(when: str) -> None:
    peak_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(f"host_peak_rss_{when}_mib={peak_kib / 1024:.1f}", flush=True)


def make_data(pairs: int, seed: int):
    """``pairs`` distinct uint64 keys (sorted) and their oracle values."""
    from repro.core.hashing import splitmix64
    from repro.core.store import make_uniform_keys
    keys = make_uniform_keys(pairs, seed)
    return keys, splitmix64(keys)


def keys_outside(keys: np.ndarray, count: int, salt: int) -> np.ndarray:
    """``count`` distinct keys that are not in the sorted key set."""
    from repro.core.hashing import splitmix64
    cand = np.unique(splitmix64(np.arange(1, 2 * count + 64, dtype=np.uint64)
                                + np.uint64(salt)))
    pos = np.minimum(np.searchsorted(keys, cand), keys.shape[0] - 1)
    cand = cand[keys[pos] != cand]
    check(cand.shape[0] >= count, f"only {cand.shape[0]} keys outside the set")
    return cand[:count]


def written_value(keys: np.ndarray) -> np.ndarray:
    from repro.core.hashing import splitmix64
    return splitmix64(keys ^ WRITE_SALT)


def check_answers(label: str, found, values, expected, n: int):
    """Lanes ``[:n]`` hold present keys whose values must be ``expected``;
    every later lane must be not found."""
    missing = int(n - found[:n].sum())
    check(missing == 0, f"{label}: {missing} of {n} present keys not found")
    wrong = int((values[:n] != expected).sum())
    check(wrong == 0, f"{label}: {wrong} of {n} present keys carry a wrong value")
    stray = int(found[n:].sum())
    check(stray == 0, f"{label}: {stray} absent or deleted keys found")


def run_one_chip(pairs: int, seed: int, device) -> None:
    """Build, Get and write through ``open_store(StoreSpec('outback'))``;
    the Get's CN and MN arrays must sit on ``device``."""
    import jax.numpy as jnp

    from benchmarks.common import uniform_indices, zipf_indices
    from repro.api import KVStore, StoreSpec, open_store

    keys, values = make_data(pairs, seed)
    t0 = time.perf_counter()
    store = open_store(StoreSpec("outback", load_factor=LOAD_FACTOR),
                       keys, values)
    report("host_build", time.perf_counter() - t0)
    report_peak_rss("after_build")
    check(isinstance(store, KVStore), "open_store result is not a KVStore")

    engine = store.engine
    arrays = engine.cn_arrays(jnp) + engine.mn_arrays(jnp)
    for name, arr in zip(CN_ARRAYS + MN_ARRAYS, arrays):
        check(arr.devices() == {device},
              f"Get array {name} sits on {arr.devices()}, not on {device}")
    del arrays

    absent = keys_outside(keys, ABSENT, ABSENT_SALT)

    def get(label, present, expected):
        # the OpResult is host numpy copied off the device, so the clock
        # stops only after the device work has finished
        q = np.concatenate([present, absent])
        t0 = time.perf_counter()
        res = store.get_batch(q, xp=jnp)
        seconds = time.perf_counter() - t0
        check_answers(label, res.found, res.values, expected,
                      present.shape[0])
        return seconds

    uni = uniform_indices(pairs, GET_BATCH, seed=seed)
    report("get_first_call", get("uniform", keys[uni], values[uni]))
    warm = [get("uniform", keys[uni], values[uni]) for _ in range(WARM_CALLS)]
    report("get_warm_per_call", float(np.median(warm)))
    zipf = zipf_indices(pairs, GET_BATCH, theta=0.99, seed=seed)
    get("zipf0.99", keys[zipf], values[zipf])

    fresh = keys_outside(keys, WRITES, FRESH_SALT)
    pick = np.random.default_rng(seed).choice(pairs, 2 * WRITES, replace=False)
    updated, deleted = keys[pick[:WRITES]], keys[pick[WRITES:]]
    ins = store.insert_batch(fresh, written_value(fresh))
    check(ins.found.all(), f"inserts not acknowledged: {ins.statuses}")
    cases = {c: ins.statuses.count(c) for c in sorted(set(ins.statuses))}
    print(f"insert_cases={json.dumps(cases, sort_keys=True)}")
    check(store.update_batch(updated, written_value(updated)).found.all(),
          "updates not acknowledged")
    check(store.delete_batch(deleted).found.all(), "deletes not acknowledged")
    written = np.concatenate([fresh, updated])
    q = np.concatenate([written, deleted])
    res = store.get_batch(q, xp=jnp)
    check_answers("write read-back", res.found, res.values,
                  written_value(written), written.shape[0])
    print(f"read_back_makeup_lanes={res.makeups}")


def run_four_chips(pairs: int, seed: int, devices) -> None:
    """Build a 4-shard ``sharded`` store and answer one Get batch with the
    SPMD mesh Get over a ``(data=1, model=4)`` mesh of ``devices``."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.common import uniform_indices
    from repro.api import KVStore, StoreSpec, open_store
    from repro.core.hashing import join_u64, split_u64
    from repro.core.sharded_kvs import make_get_fn, place_state

    keys, values = make_data(pairs, seed)
    spec = StoreSpec("sharded", load_factor=LOAD_FACTOR,
                     params={"num_shards": 4, "data_parallel": 1})
    t0 = time.perf_counter()
    store = open_store(spec, keys, values)
    report("host_build", time.perf_counter() - t0)
    report_peak_rss("after_build")
    check(isinstance(store, KVStore), "open_store result is not a KVStore")

    st = store.mesh_state()
    mesh = Mesh(np.asarray(devices).reshape(1, 4), ("data", "model"))
    placed = place_state(mesh, st)
    names = ("words_a", "words_b", "seeds", "oth_meta") + MN_ARRAYS
    for name, arr, host in zip(names, placed, st.arrays()):
        shards = arr.addressable_shards
        check(len({s.device for s in shards}) == 4,
              f"{name} is not spread over four devices")
        for s in shards:
            m = s.index[0].start
            check(s.data.shape[0] == 1 and mesh.devices[0, m] == s.device,
                  f"{name}: shard {m} is not on mesh column {m}")
            if name in names[:4]:  # the small CN arrays: compare contents
                check((np.asarray(s.data) == host[m:m + 1]).all(),
                      f"{name}: device copy of shard {m} differs")

    uni = uniform_indices(pairs, GET_BATCH, seed=seed)
    # the mesh Get serves slot-resident keys only: a query key the host
    # shards keep in their overflow cache would be reported missing
    lo, hi = split_u64(keys[uni])
    resident = sum(int((sh.overflow.lookup_batch(lo, hi)[0] >= 0).sum())
                   for sh in store.shards)
    print(f"overflow_resident_queries={resident}", flush=True)
    q = np.concatenate([keys[uni], keys_outside(keys, ABSENT, ABSENT_SALT)])
    fn, _ = make_get_fn(mesh, st, q.shape[0] // 4)
    lanes = NamedSharding(mesh, P(("data", "model")))
    q_lo, q_hi = (jax.device_put(a, lanes) for a in split_u64(q))

    def get():
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(q_lo, q_hi, *placed))
        return out, time.perf_counter() - t0

    out, first = get()
    report("get_first_call", first)
    report("get_warm_per_call",
           float(np.median([get()[1] for _ in range(WARM_CALLS)])))
    v_lo, v_hi, match = (np.asarray(a) for a in out)
    check_answers("mesh uniform", match, join_u64(v_lo, v_hi), values[uni],
                  GET_BATCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pairs", type=int, default=1 << 25,
                    help="key-value pairs loaded (default 2^25)")
    ap.add_argument("--seed", type=int, default=1,
                    help="seed of the key set and the query draws")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh Get on four chips")
    args = ap.parse_args(argv)

    check((ROOT / "src" / "repro").is_dir(),
          f"no src/repro beside {ROOT / 'chip_smoke.py'}: run it from a "
          f"checkout of the repository")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX sees "
          f"{len(devices)}")

    report_peak_rss("after_device_init")
    from repro.compile_cache import enable_compile_cache
    print(f"compile_cache={enable_compile_cache()}", flush=True)

    if args.chips == 4:
        run_four_chips(args.pairs, args.seed, devices[:4])
    else:
        run_one_chip(args.pairs, args.seed, dev)
    report_peak_rss("at_end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
