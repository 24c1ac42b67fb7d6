"""The plain reference: a key-value map with the store's guarantees.

Loaded pairs sit in a sorted key array with a value array beside it; a
later insert goes into a dict.  A Get answers the value last written to a
key that is present and "not found" otherwise; an update of a present key
is acknowledged and the last lane of a key in a batch wins; an insert of a
new key is acknowledged.  It imports nothing of the program.
"""

from __future__ import annotations

import numpy as np


class Reference:
    def __init__(self, keys: np.ndarray, values: np.ndarray):
        self.keys = np.asarray(keys, np.uint64)
        self.values = np.asarray(values, np.uint64).copy()
        self.inserted: dict[int, int] = {}

    def _locate(self, keys: np.ndarray):
        pos = np.minimum(np.searchsorted(self.keys, keys),
                         self.keys.shape[0] - 1)
        return pos, self.keys[pos] == keys

    def get(self, keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """``(found, values)``, values 0 where not found."""
        pos, found = self._locate(keys)
        values = np.where(found, self.values[pos], np.uint64(0))
        if self.inserted:
            for i in np.nonzero(~found)[0]:
                v = self.inserted.get(int(keys[i]))
                if v is not None:
                    found[i], values[i] = True, v
        return found, values

    def update(self, keys: np.ndarray, values: np.ndarray) -> np.ndarray:
        """Acknowledgement per lane; applies the writes in lane order."""
        pos, ok = self._locate(keys)
        last = _last_lanes(keys)
        w = last[ok[last]]
        self.values[pos[w]] = values[w]
        for i in np.nonzero(~ok)[0]:
            k = int(keys[i])
            if k in self.inserted:
                self.inserted[k] = int(values[i])
                ok[i] = True
        return ok

    def insert(self, keys: np.ndarray, values: np.ndarray) -> np.ndarray:
        """Acknowledgement per lane: every lane stores its pair."""
        for k, v in zip(keys.tolist(), values.tolist()):
            self.inserted[k] = v
        return np.ones(keys.shape[0], bool)


def _last_lanes(keys: np.ndarray) -> np.ndarray:
    """Index of the last lane of each distinct key."""
    _, first_rev = np.unique(keys[::-1], return_index=True)
    return keys.shape[0] - 1 - first_rev


def compare(ref: Reference, op: str, keys, values, found, ack_values=None
            ) -> int:
    """Apply one call of the program's answers to the reference and return
    how many lanes the program answered wrongly."""
    found = np.asarray(found, bool)
    if found.shape[0] != keys.shape[0]:
        return int(keys.shape[0])
    if op == "get":
        r_found, r_vals = ref.get(keys)
        got = np.asarray(values, np.uint64)
        wrong = (found != r_found) | (r_found & (got != r_vals))
        return int(wrong.sum())
    if op == "update":
        return int((found != ref.update(keys, ack_values)).sum())
    if op == "insert":
        return int((found != ref.insert(keys, ack_values)).sum())
    raise ValueError(f"no reference for op {op!r}")
