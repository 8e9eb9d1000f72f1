"""The bytes a kernel's algorithm has to move, from its shapes and
from nothing of the implementation, so that a roofline share stays
true when a later PR replaces the kernel. Found by name from a
per-layer metric's file (``"work": "sieve_bytes"``); each takes the
window's counters and the configuration and returns bytes.

Neither kernel has an operation count that does not depend on how it
is written (a DFA walk, a bit-parallel shift-and and a compare chain
do different arithmetic for the same answer), so both rooflines are
the bandwidth bound: bytes over the chip's HBM bandwidth.
"""

from __future__ import annotations

# rank and row index read (2 x int32), the advisory row gathered
# (4 interval arrays x MAX_INTERVALS 4 x int32 + flags int32 = 68:
# ops/intervals.py interval_hits_resident_impl), one bool written
INTERVAL_JOB_BYTES = 8 + 68 + 1


def sieve_row_bytes(seg_len: int, patterns: int) -> int:
    """One segment row read once, one hit bit a pattern written
    (rounded up to the 16-bit words the mask is kept in)."""
    return seg_len + 2 * ((patterns + 15) // 16)


def sieve_bytes(stats: dict, config: dict) -> float:
    """``secret.device_bytes`` counts the segment bytes sent to the
    sieve; rows are that over the segment length."""
    seg_len = config["sizes"]["seg_len"]
    rows = stats["secret"]["device_bytes"] / seg_len
    return rows * sieve_row_bytes(seg_len, config["patterns"])


def interval_bytes(stats: dict, config: dict) -> float:
    """``detect.device_rows`` counts the jobs sent in interval
    waves."""
    return stats["detect"]["device_rows"] * INTERVAL_JOB_BYTES
