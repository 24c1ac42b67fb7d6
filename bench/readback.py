"""Read each replica of a replicated cell back after its window.

    python3 bench/readback.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell as ``bench/run.py`` does and prints its result line, with the
store kept past the window and every update call's acknowledgement noted.
Then it prints one more JSON line: the replica set's failovers, resyncs and
primary; for each replica, read directly through its own adapter with
``get_batch(xp=jnp)``, the answers among ``KEYS`` keys (half of them keys
updated while a replica was down, half loaded keys drawn from ``--seed``)
that differ from the values last acknowledged; and whether the replicas' MN
states are equal, field by field.  Not run by the benchmark's own runs.
"""

import time

T_PROCESS = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

import run  # noqa: E402
from lib import runner  # noqa: E402
from lib.reference import Reference  # noqa: E402

KEYS = 1 << 16


def _replica_set(store):
    while not hasattr(store, "replicas"):
        store = store.inner
    return store


class Kept:
    """The program's store, kept, with each update call noted: its keys,
    values, acknowledgements and whether a replica was down after it."""

    def __init__(self, spec: dict, keys, values):
        self.store = runner.program_store(spec, keys, values)
        self.rs = _replica_set(self.store)
        self.keys, self.values = keys, values
        self.updates = []

    def get_batch(self, *a, **k):
        return self.store.get_batch(*a, **k)

    def update_batch(self, keys, values):
        res = self.store.update_batch(keys, values)
        down = any(self.rs.plane.crash_open(i)
                   for i in range(len(self.rs.replicas)))
        self.updates.append((np.array(keys), np.array(values),
                             np.array(res.found, bool), down))
        return res

    def insert_batch(self, *a, **k):
        return self.store.insert_batch(*a, **k)

    def meter_totals(self):
        return self.store.meter_totals()


def _equal(a, b) -> bool:
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_equal(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_equal, a, b))
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and np.array_equal(a, b)
    return a == b


def readback(kept: Kept, seed: int) -> dict:
    import jax.numpy as jnp
    ref = Reference(kept.keys, kept.values)
    outage = []
    for keys, values, acked, down in kept.updates:
        ref.update(keys[acked], values[acked])
        if down:
            outage.append(keys[acked])
    rng = np.random.default_rng(seed)
    hit = np.unique(np.concatenate(outage)) if outage else kept.keys[:0]
    hit = rng.permutation(hit)[:KEYS // 2]
    q = np.concatenate([hit, rng.choice(kept.keys, KEYS - hit.shape[0])])
    found, want = ref.get(q)
    rs = kept.rs
    wrong = []
    for rep in rs.replicas:
        res = rep.get_batch(q, xp=jnp)
        got = np.asarray(res.values, np.uint64)
        bad = (np.asarray(res.found, bool) != found) | (found & (got != want))
        wrong.append(int(bad.sum()))
    states = [rep.engine.mn_state() for rep in rs.replicas]
    m = rs.meter_totals()
    return {"failovers": int(m.failovers), "resyncs": int(m.resyncs),
            "primary": int(rs.primary), "keys": int(q.shape[0]),
            "outage_keys": int(hit.shape[0]), "wrong": wrong,
            "mn_state_equal": all(_equal(states[0], s) for s in states[1:])}


def main(argv=None) -> int:
    run.T_PROCESS = T_PROCESS
    args = run.parse(argv, __doc__.splitlines()[0])
    kept = []

    def keep(spec, keys, values):
        kept.append(Kept(spec, keys, values))
        return kept[-1]

    rc = run.run_and_report(args, open_store=keep)
    print(json.dumps({"readback": readback(kept[0], args.seed)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
