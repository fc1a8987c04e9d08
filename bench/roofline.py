"""Work that an operation needs, whatever implements it, and the least time
the chip could take for it.

Least time is the larger of operations over the integer peak and bytes over
HBM bandwidth, with peaks from ``peaks.json`` by ``device_kind``.  Counts are
of the work the answer needs, so no implementation can read over 100 %:

* a lookup of q keys in a sorted table of n keys: the queries in (8 bytes
  each), the row ids out (4 bytes each), one tile of 128 keys per query
  (8 bytes a key), and the table's fences (one key per tile) once; one
  compare per level of a binary search per query;
* a prefix search over the sorted path table: per prefix, its bytes in,
  ⌈log2 n⌉ path rows of the table's mean path length to find where its
  range starts, and the bytes of the paths it returns.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

TILE = 128
KEY_BYTES = 8
ROW_ID_BYTES = 4


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).resolve().parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def lookup_work(q: int, n: int) -> tuple[float, float]:
    """(ops, bytes) of a batched lookup of q keys in a table of n keys."""
    if q <= 0:
        return 0.0, 0.0
    levels = max(1, math.ceil(math.log2(max(n, 2))))
    nbytes = q * (KEY_BYTES + ROW_ID_BYTES + TILE * KEY_BYTES) + math.ceil(n / TILE) * KEY_BYTES
    return float(q * levels), float(nbytes)


def scan_work(prefix_bytes: list[int], returned_bytes: list[int], n: int,
              mean_path_bytes: float) -> tuple[float, float]:
    """(ops, bytes) of prefix searches over a sorted table of n paths:
    prefix_bytes[i] and returned_bytes[i] (the total bytes of the paths it
    returned) per prefix."""
    levels = max(1, math.ceil(math.log2(max(n, 2))))
    nbytes = ops = 0.0
    for pb, rb in zip(prefix_bytes, returned_bytes):
        nbytes += pb + levels * mean_path_bytes + rb
        ops += levels * math.ceil(max(pb, 1) / 4) + math.ceil(rb / 4)
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, peak: dict) -> float:
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])
