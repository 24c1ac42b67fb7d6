"""The plain reference and the comparison that decides ``correct``."""

import numpy as np

from lib import gen, reference


def _ref(n=1000, seed=2):
    keys = gen.make_keys(n, seed)
    return keys, reference.Reference(keys, gen.values_of(keys))


def test_get_present_and_absent():
    keys, ref = _ref()
    absent = gen.keys_outside(keys, 10, 2, gen.ABSENT_SALT)
    found, vals = ref.get(np.concatenate([keys[:5], absent]))
    assert found.tolist() == [True] * 5 + [False] * 10
    np.testing.assert_array_equal(vals[:5], gen.values_of(keys[:5]))
    assert (vals[5:] == 0).all()


def test_update_last_lane_wins_and_absent_not_acked():
    keys, ref = _ref()
    absent = gen.keys_outside(keys, 1, 2, gen.ABSENT_SALT)
    q = np.concatenate([keys[[3, 3, 4]], absent])
    ok = ref.update(q, np.uint64([7, 8, 9, 10]))
    assert ok.tolist() == [True, True, True, False]
    assert ref.get(keys[[3, 4]])[1].tolist() == [8, 9]


def test_insert_then_get_and_update():
    keys, ref = _ref()
    new = gen.keys_outside(keys, 3, 2, gen.FRESH_SALT)
    assert ref.insert(new, np.uint64([1, 2, 3])).all()
    assert ref.update(new[:1], np.uint64([5])).all()
    found, vals = ref.get(new)
    assert found.all() and vals.tolist() == [5, 2, 3]


def test_compare_counts_every_kind_of_wrong_answer():
    keys, ref = _ref()
    q = keys[:4]
    vals = gen.values_of(q)
    assert reference.compare(ref, "get", q, vals, np.ones(4, bool)) == 0
    bad = vals.copy()
    bad[1] ^= np.uint64(1)
    assert reference.compare(ref, "get", q, bad, np.ones(4, bool)) == 1
    assert reference.compare(ref, "get", q, vals,
                             np.array([1, 0, 1, 0], bool)) == 2
    assert reference.compare(ref, "get", q, vals[:2], np.ones(2, bool)) == 4
    new = gen.update_values(q, 2, 0)
    assert reference.compare(ref, "update", q, None, np.zeros(4, bool),
                             ack_values=new) == 4
