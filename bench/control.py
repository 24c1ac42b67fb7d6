"""Control of the check that decides ``correct``: it has to read false.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py`` does, with the plain reference put in
the program's place and serving its values at the next lower precision:
4-byte values where the configurations state 8 (the high word is lost, as
a device path that kept values in one 32-bit word would lose it).  It
breaks the guarantee that a Get returns the value stored, so the run's
check must count wrong answers and print ``"correct": false``.  Not run by
the benchmark's own runs.
"""

import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

import run  # noqa: E402
from lib.reference import Reference  # noqa: E402

LOW_WORD = np.uint64(0xFFFFFFFF)


class Low32Store:
    """The reference as a store whose values keep only their low word."""

    def __init__(self, spec: dict, keys, values):
        self.ref = Reference(keys, values)

    @staticmethod
    def _result(found, values):
        return types.SimpleNamespace(found=found, values=values & LOW_WORD,
                                     cache_hits=0)

    def get_batch(self, keys, xp=None):
        return self._result(*self.ref.get(keys))

    def update_batch(self, keys, values):
        return self._result(self.ref.update(keys, values), values)

    def insert_batch(self, keys, values):
        return self._result(self.ref.insert(keys, values), values)

    def meter_totals(self):
        return types.SimpleNamespace(cache_hits=0, cache_neg_hits=0)


def main(argv=None) -> int:
    run.T_PROCESS = T_PROCESS
    return run.run_and_report(run.parse(argv, __doc__.splitlines()[0]),
                              open_store=Low32Store)


if __name__ == "__main__":
    sys.exit(main())
