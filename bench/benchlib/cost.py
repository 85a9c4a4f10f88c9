"""Operations and bytes that each partition kernel's algorithm needs for
one call, from its shapes alone: the same whatever implements it, so a
roofline share computed from them does not move when the implementation
pads, tiles or re-reads.

Both kernels are bound by memory: they do a few integer operations per id,
far below what the chip's bytes per second could feed.
"""

from __future__ import annotations

ID_BYTES = 4          # int32 partition ids and destinations


def histogram_cost(rows: int, buckets: int) -> dict:
    """Per-bucket counts of ``rows`` ids: read every id once, write the
    counts. One compare-and-add per id."""
    return {"ops": rows, "bytes": ID_BYTES * (rows + buckets)}


def destinations_cost(rows: int, buckets: int) -> dict:
    """Each id's stable destination in the grouped order: read every id,
    write one destination per id (plus the bucket offsets). A rank within
    the id's bucket and one add per id."""
    return {"ops": 2 * rows, "bytes": ID_BYTES * (2 * rows + buckets + 1)}


def roofline_seconds(cost: dict, peaks: dict) -> float:
    """Least time the chip could take: the larger of the operations over
    the peak operation rate (int8 TOP/s, the highest integer rate
    published) and the bytes over HBM bandwidth."""
    return max(cost["ops"] / peaks["int8_ops"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])
