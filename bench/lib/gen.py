"""Data and traffic of the benchmark, drawn from ``--seed``.

The generators are copies, kept here so that no change to the program can
move the yardstick, and nothing here imports the program:

* ``splitmix64`` and ``make_keys`` follow ``repro.core.hashing.splitmix64``
  and ``repro.core.store.make_uniform_keys`` (distinct uniform 64-bit keys,
  value ``splitmix64(key)``);
* the uniform and zipf index draws follow ``benchmarks/common.py``, with the
  zipf CDF computed once instead of on every draw;
* ``YCSB`` is the share table of ``benchmarks/common.py``, less workload F;
* ``keys_outside`` follows ``chip_smoke.py``'s absent keys.

A traffic mix is a JSON file under ``bench/traffic/`` (see ``Traffic``).
"""

from __future__ import annotations

import numpy as np

M64 = (1 << 64) - 1

# independent streams drawn from one --seed
KEY_STREAM, PERM_STREAM, FILL_STREAM, WARM_STREAM, WINDOW_STREAM = range(5)
ABSENT_SALT = 0xA5 << 56
FRESH_SALT = 0xF7 << 56
UPDATE_SALT = 0x5DEECE66D
INSERT_SALT = 0x1D5E7

# YCSB core workloads (Cooper et al., SoCC 2010, Table 2).  F (50% read,
# 50% read-modify-write) waits for a read-modify-write op; E (scans) has none
YCSB = {
    "A": {"get": 0.5, "update": 0.5},
    "B": {"get": 0.95, "update": 0.05},
    "C": {"get": 1.0},
    "D": {"get": 0.95, "insert": 0.05},
}
OPS = ("get", "update", "insert")

# the cache fill: rounds of pairs / FILL_ROUND_DIV Gets, compared in blocks
# of FILL_BLOCK rounds (an eighth of the key count per block)
FILL_ROUND_DIV, FILL_BLOCK, FILL_MAX_ROUNDS = 64, 8, 128


def splitmix64(x) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = x + np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def mix(seed: int, salt: int) -> np.uint64:
    """One 64-bit word from ``(seed, salt)``; any whole ``seed`` is taken."""
    return splitmix64(np.uint64((int(seed) ^ splitmix64(np.uint64(salt & M64))
                                 .item()) & M64))[()]


def make_keys(n: int, seed: int) -> np.ndarray:
    """``n`` distinct uniform uint64 keys, sorted."""
    base = mix(seed, KEY_STREAM)
    with np.errstate(over="ignore"):
        cand = splitmix64(np.arange(1, int(n * 1.05) + 16, dtype=np.uint64)
                          + base)
    keys = np.unique(cand)[:n]
    if keys.shape[0] != n:
        raise RuntimeError(f"only {keys.shape[0]} distinct keys of {n}")
    return keys


def values_of(keys: np.ndarray) -> np.ndarray:
    """The value each key is loaded with."""
    return splitmix64(keys)


def update_values(keys: np.ndarray, seed: int, rnd: int) -> np.ndarray:
    """Values written by the updates of round ``rnd``: differ from the loaded
    value and from every other round's."""
    with np.errstate(over="ignore"):
        return splitmix64(keys ^ mix(seed, UPDATE_SALT + rnd))


def insert_values(keys: np.ndarray) -> np.ndarray:
    return splitmix64(keys ^ np.uint64(INSERT_SALT))


def in_sorted(sorted_keys: np.ndarray, keys: np.ndarray) -> np.ndarray:
    pos = np.minimum(np.searchsorted(sorted_keys, keys),
                     sorted_keys.shape[0] - 1)
    return sorted_keys[pos] == keys


def keys_outside(sorted_keys: np.ndarray, count: int, seed: int,
                 salt: int, start: int = 0) -> np.ndarray:
    """``count`` distinct keys not in ``sorted_keys``, the ``start``-th on of
    the ``(seed, salt)`` stream, in stream order."""
    base = mix(seed, salt)
    with np.errstate(over="ignore"):
        cand = splitmix64(np.arange(start + 1, start + 2 * count + 64,
                                    dtype=np.uint64) + base)
    cand = cand[~in_sorted(sorted_keys, cand)]
    _, first = np.unique(cand, return_index=True)
    cand = cand[np.sort(first)]
    if cand.shape[0] < count:
        raise RuntimeError(f"only {cand.shape[0]} keys outside the set")
    return cand[:count]


def round_counts(shares: dict[str, float], round_ops: int
                 ) -> list[tuple[str, int]]:
    """Exact op counts of one round, in issue order (get → update → insert):
    the share of each op times the round size, rounded by largest
    remainder so the counts add up to ``round_ops``."""
    ops = [op for op in OPS if shares.get(op, 0.0) > 0.0]
    unknown = set(shares) - set(OPS)
    if unknown:
        raise ValueError(f"unknown ops in the mix: {sorted(unknown)}")
    total = sum(shares[op] for op in ops)
    exact = [shares[op] / total * round_ops for op in ops]
    counts = [int(np.floor(x)) for x in exact]
    order = sorted(range(len(ops)), key=lambda i: counts[i] - exact[i])
    for i in order[:round_ops - sum(counts)]:
        counts[i] += 1
    return [(op, c) for op, c in zip(ops, counts) if c > 0]


class Zipf:
    """Zipf(theta) ranks over ``n`` items; rank 0 is the most popular.

    The distribution of ``rng.choice(n, p=r**-theta / sum)`` in
    ``benchmarks/common.py``, with its CDF built once."""

    def __init__(self, n: int, theta: float):
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(theta)
        self.cdf = np.cumsum(w)
        self.cdf /= self.cdf[-1]
        self.n = n

    def draw(self, rng: np.random.Generator, count: int) -> np.ndarray:
        r = np.searchsorted(self.cdf, rng.random(count), side="right")
        return np.minimum(r, self.n - 1)


def _draw(dist: str, zipf: Zipf | None, rng, n_now: int, count: int):
    """Indices into a population of ``n_now`` items, held in load order
    (scrambled once by ``Traffic``)."""
    if dist == "uniform":
        return rng.integers(0, n_now, count)
    if dist == "zipf":
        return zipf.draw(rng, count)
    if dist == "latest":  # YCSB "latest": zipf over recency, newest first
        return np.maximum(n_now - 1 - zipf.draw(rng, count), 0)
    raise ValueError(f"unknown key distribution {dist!r}")


class Traffic:
    """One traffic mix over one loaded key set.

    The mix file holds ``loop`` (``closed``) and ``clients`` (1), the only
    load the harness offers; ``round_ops`` (ops per closed-loop round),
    ``shares`` (op → share), ``distribution`` (``uniform``, ``zipf`` or
    ``latest``), ``theta`` for the skewed ones, ``absent_get_share`` (Gets of
    keys never loaded, drawn from a pool of ``absent_keys`` by the same
    distribution), ``max_inserts`` where it inserts, ``warmup_rounds``, and
    ``cache_fill_settle`` where set-up fills a cache (see ``fill_settled``).
    Zipf ranks are scrambled over the key space, as YCSB's scrambled zipfian
    is.  Each round is drawn from its own stream, so round ``r`` of a seed
    is the same whatever ran before it."""

    def __init__(self, spec: dict, keys: np.ndarray, seed: int):
        if (spec.get("loop"), spec.get("clients")) != ("closed", 1):
            raise ValueError("the harness offers one closed-loop client: "
                             f"loop={spec.get('loop')!r} "
                             f"clients={spec.get('clients')!r}")
        self.seed = int(seed)
        self.round_ops = int(spec["round_ops"])
        self.counts = round_counts(spec["shares"], self.round_ops)
        self.dist = spec["distribution"]
        n = keys.shape[0]
        perm_rng = np.random.default_rng([self.seed & M64, PERM_STREAM])
        max_inserts = int(spec.get("max_inserts", 0))
        self.population = np.empty(n + max_inserts, np.uint64)
        self.population[:n] = keys[perm_rng.permutation(n)]
        self.n_now = n
        self.loaded = keys
        self.zipf = (Zipf(n, spec["theta"])
                     if self.dist in ("zipf", "latest") else None)
        self.absent_share = float(spec.get("absent_get_share", 0.0))
        self.absent = None
        if self.absent_share > 0:
            self.absent = keys_outside(keys, int(spec["absent_keys"]),
                                       self.seed, ABSENT_SALT)
            self.absent_zipf = (Zipf(self.absent.shape[0], spec["theta"])
                                if self.zipf is not None else None)
        self.fresh_used = 0

    def _rng(self, stream: int, rnd: int) -> np.random.Generator:
        return np.random.default_rng([self.seed & M64, stream, rnd])

    def _gets(self, rng, count: int) -> np.ndarray:
        n_abs = int(round(count * self.absent_share))
        keys = self.population[_draw(self.dist, self.zipf, rng, self.n_now,
                                     count - n_abs)]
        if n_abs:
            absent = self.absent[_draw(self.dist, self.absent_zipf, rng,
                                       self.absent.shape[0], n_abs)]
            keys = np.concatenate([keys, absent])
        return keys

    def get_round(self, stream: int, rnd: int, count: int) -> np.ndarray:
        """``count`` Gets drawn as this mix draws them (cache fill)."""
        return self._gets(self._rng(stream, rnd), count)

    def round(self, stream: int, rnd: int, *, writes: bool = True
              ) -> list[tuple[str, np.ndarray, np.ndarray | None]]:
        """``[(op, keys, values)]`` of round ``rnd``, in issue order.  With
        ``writes=False`` an update writes the loaded value back and no
        insert is drawn (warm-up that leaves the data as loaded)."""
        rng = self._rng(stream, rnd)
        out = []
        for op, count in self.counts:
            if op == "get":
                out.append(("get", self._gets(rng, count), None))
            elif op == "update":
                keys = self.population[_draw(self.dist, self.zipf, rng,
                                             self.n_now, count)]
                vals = (update_values(keys, self.seed, rnd) if writes
                        else values_of(keys))
                out.append(("update", keys, vals))
            elif op == "insert" and writes:
                keys = keys_outside(self.loaded, count, self.seed,
                                    FRESH_SALT, start=self.fresh_used)
                self.fresh_used += 2 * count + 63
                end = self.n_now + count
                if end > self.population.shape[0]:
                    raise RuntimeError("more inserts than max_inserts")
                self.population[self.n_now:end] = keys
                self.n_now = end
                out.append(("insert", keys, insert_values(keys)))
        return out


def fill_settled(shares: list[float], settle: float) -> bool:
    """Whether a cache fill has levelled off: the mean hit share of the last
    ``FILL_BLOCK`` fill rounds lies within ``settle`` of the block before."""
    if len(shares) < 2 * FILL_BLOCK:
        return False
    last = np.mean(shares[-FILL_BLOCK:])
    before = np.mean(shares[-2 * FILL_BLOCK:-FILL_BLOCK])
    return bool(abs(last - before) < settle)
