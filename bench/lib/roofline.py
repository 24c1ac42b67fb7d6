"""Bytes that one Outback Get needs from device memory, from the algorithm.

Counted from §4 of the paper, not from any implementation: the CN locate
reads one 32-bit word of each of the Othello locator's two bit arrays and
the 1-byte seed of the chosen bucket (the two candidate buckets are hashes
and read nothing); the MN side reads the 64-bit slot, then the 8-byte key
and 8-byte value of the heap block; the lane reads its 8-byte query key and
writes an 8-byte value and a 1-byte found flag.
"""

from __future__ import annotations

GET_LANE_BYTES = {
    "query_key": 8,
    "locator_words": 2 * 4,
    "bucket_seed": 1,
    "slot": 8,
    "heap_key": 8,
    "heap_value": 8,
    "result": 8 + 1,
}


def get_bytes(lanes: int) -> int:
    """Bytes ``lanes`` device Get lanes need at the least."""
    return int(lanes) * sum(GET_LANE_BYTES.values())


def roofline_share(nbytes: float, device_s: float, hbm_bytes_per_s: float
                   ) -> float | None:
    """Share (%) of the bandwidth roofline: the least time the bytes need
    over the device time they took.  ``None`` where no device time was
    recorded."""
    if device_s <= 0:
        return None
    return 100.0 * nbytes / hbm_bytes_per_s / device_s
