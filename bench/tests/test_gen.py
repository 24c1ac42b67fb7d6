"""The copied generators: exact YCSB shares, draws reproducible per seed."""

import json
import pathlib

import numpy as np
import pytest

from lib import gen

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("letter", sorted(gen.YCSB))
@pytest.mark.parametrize("round_ops", [8192, 1000, 7])
def test_round_counts_are_exact_shares(letter, round_ops):
    counts = gen.round_counts(gen.YCSB[letter], round_ops)
    assert sum(c for _, c in counts) == round_ops
    for op, c in counts:
        assert abs(c - gen.YCSB[letter][op] * round_ops) < 1
    ops = [op for op, _ in counts]
    assert ops == [op for op in gen.OPS if op in ops]


def test_ycsb_b_round_is_7782_gets_then_410_updates():
    assert gen.round_counts(gen.YCSB["B"], 8192) == [("get", 7782),
                                                     ("update", 410)]


@pytest.mark.parametrize("path", sorted(TRAFFIC.glob("*.json")),
                         ids=lambda p: p.stem)
def test_mix_files_hold_their_ycsb_shares(path):
    spec = json.loads(path.read_text())
    assert spec["shares"] == gen.YCSB[spec["ycsb"]]
    keys = gen.make_keys(4096, 3)
    mix = gen.Traffic(spec, keys, BIG_SEED)
    for rnd in range(3):
        ops = mix.round(gen.WINDOW_STREAM, rnd)
        assert [(op, q.shape[0]) for op, q, _ in ops] == mix.counts
        assert sum(q.shape[0] for _, q, _ in ops) == spec["round_ops"]


def test_splitmix64_is_the_programs():
    from repro.core.hashing import splitmix64
    x = np.arange(0, 1 << 16, 7, dtype=np.uint64) * np.uint64(0x9E3779B1)
    np.testing.assert_array_equal(gen.splitmix64(x), splitmix64(x))


def test_keys_distinct_sorted_and_reproducible():
    a = gen.make_keys(10_000, BIG_SEED)
    assert a.shape == (10_000,) and (np.diff(a) > 0).all()
    np.testing.assert_array_equal(a, gen.make_keys(10_000, BIG_SEED))
    assert not np.array_equal(a, gen.make_keys(10_000, BIG_SEED + 1))


def test_keys_outside_are_absent_and_distinct():
    keys = gen.make_keys(10_000, 9)
    out = gen.keys_outside(keys, 500, 9, gen.ABSENT_SALT)
    assert np.unique(out).shape == (500,)
    assert not gen.in_sorted(keys, out).any()


@pytest.mark.parametrize("dist", ["uniform", "zipf"])
def test_draws_reproducible_per_seed(dist):
    keys = gen.make_keys(20_000, 1)
    spec = {"loop": "closed", "clients": 1, "round_ops": 512,
            "shares": {"get": 0.95, "update": 0.05},
            "distribution": dist, "theta": 0.99}

    def rounds(seed, stream=gen.WINDOW_STREAM):
        mix = gen.Traffic(spec, keys, seed)
        return [q for r in range(4) for _, q, _ in mix.round(stream, r)]

    a, b = rounds(BIG_SEED), rounds(BIG_SEED)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y)
               for x, y in zip(a, rounds(BIG_SEED + 1)))
    assert any(not np.array_equal(x, y)
               for x, y in zip(a, rounds(BIG_SEED, gen.WARM_STREAM)))
    assert all(gen.in_sorted(keys, q).all() for q in a)


def test_zipf_follows_its_ranks():
    z = gen.Zipf(1000, 0.99)
    r = z.draw(np.random.default_rng(0), 400_000)
    p = np.arange(1, 1001, dtype=float) ** -0.99
    p /= p.sum()
    counts = np.bincount(r, minlength=1000)
    np.testing.assert_allclose(counts[:5] / r.shape[0], p[:5], rtol=0.03)
    assert r.min() >= 0 and r.max() < 1000


def test_updates_write_new_values():
    keys = gen.make_keys(1000, 4)
    v0 = gen.values_of(keys)
    v1 = gen.update_values(keys, 4, 0)
    v2 = gen.update_values(keys, 4, 1)
    assert (v0 != v1).all() and (v1 != v2).all()


def test_warmup_round_leaves_loaded_values():
    keys = gen.make_keys(4096, 6)
    spec = {"loop": "closed", "clients": 1, "round_ops": 100,
            "shares": {"get": 0.5, "update": 0.5}, "distribution": "uniform"}
    mix = gen.Traffic(spec, keys, 6)
    for op, q, v in mix.round(gen.WARM_STREAM, 0, writes=False):
        if op == "update":
            np.testing.assert_array_equal(v, gen.values_of(q))


def test_inserts_are_fresh_and_join_the_population():
    keys = gen.make_keys(4096, 8)
    spec = {"loop": "closed", "clients": 1, "round_ops": 100,
            "shares": {"get": 0.9, "insert": 0.1},
            "distribution": "latest", "theta": 0.99, "max_inserts": 1000}
    mix = gen.Traffic(spec, keys, 8)
    seen = []
    for r in range(3):
        ins = [q for op, q, _ in mix.round(gen.WINDOW_STREAM, r)
               if op == "insert"][0]
        assert not gen.in_sorted(keys, ins).any()
        seen.append(ins)
    allins = np.concatenate(seen)
    assert np.unique(allins).shape == allins.shape
    assert mix.n_now == 4096 + 30


def test_ycsb_table_is_the_sources():
    # F is read-modify-write, which no op here is; E is scans
    assert sorted(gen.YCSB) == ["A", "B", "C", "D"]


@pytest.mark.parametrize("load", [{"loop": "open", "clients": 1},
                                  {"loop": "closed", "clients": 4}, {}])
def test_traffic_refuses_load_the_harness_does_not_offer(load):
    spec = {"round_ops": 8, "shares": {"get": 1.0},
            "distribution": "uniform", **load}
    with pytest.raises(ValueError, match="closed-loop"):
        gen.Traffic(spec, gen.make_keys(64, 1), 1)


def test_fill_stops_once_the_hit_share_levels_off():
    b = gen.FILL_BLOCK
    rising = list(np.linspace(0.0, 0.7, 3 * b))
    assert not gen.fill_settled(rising, 0.005)
    assert not gen.fill_settled([0.7] * (2 * b - 1), 0.005)
    assert gen.fill_settled(rising + [0.75] * (2 * b), 0.005)
    assert not gen.fill_settled(rising + [0.75] * b + [0.76] * b, 0.005)
