"""The plain reference of the query, and the comparison that decides
``correct``.

    SELECT d.cat, SUM(f.v0 * f.v1)
    FROM store_sales f JOIN item d ON f.key = d.key
    WHERE f.v0 > 0
    GROUP BY d.cat

``reference_sums`` is numpy in float64 and reads only the tables the
benchmark made; it imports nothing of the system under test. Its
arithmetic is copied from ``reference_query_numpy`` and
``oracle_relative_error`` in ``repro.analytics.query`` so that a change
there cannot move the yardstick.

``control_sums`` is the same query one precision below the float32 the
configuration states: ``v0``, ``v1`` and their product rounded to bfloat16
(the step that would halve the fact table's bytes), summed in float64. It
has to come out as not correct under the limit.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def host_columns(parts: list[dict], names: tuple[str, ...]) -> dict:
    """Concatenate device partitions into host numpy columns."""
    return {n: np.concatenate([np.asarray(p[n]) for p in parts])
            for n in names}


def _matched(fact: dict, dim: dict, num_groups: int):
    """(group of each fact row that passes and matches, the row mask)."""
    fk = fact["key"]
    dk, cat = dim["key"], dim["cat"]
    order = np.argsort(dk, kind="stable")
    sorted_keys = dk[order]
    pos = np.maximum(np.searchsorted(sorted_keys, fk, side="right") - 1, 0)
    hit = (fact["v0"] > 0) & (sorted_keys[pos] == fk)
    groups = cat[order][pos][hit].astype(np.int64) % num_groups
    return groups, hit


def reference_sums(fact: dict, dim: dict, num_groups: int) -> np.ndarray:
    """Float64 per-group ``SUM(v0 * v1)`` over the passing fact rows whose
    key has an item row (a duplicated item key resolves to its last row)."""
    if dim["key"].size == 0:
        return np.zeros(num_groups)
    groups, hit = _matched(fact, dim, num_groups)
    v = fact["v0"].astype(np.float64) * fact["v1"].astype(np.float64)
    return np.bincount(groups, weights=v[hit], minlength=num_groups)


def control_sums(fact: dict, dim: dict, num_groups: int) -> np.ndarray:
    """The reference with values and products in bfloat16."""
    if dim["key"].size == 0:
        return np.zeros(num_groups)
    groups, hit = _matched(fact, dim, num_groups)
    bf = ml_dtypes.bfloat16
    v = (fact["v0"].astype(bf) * fact["v1"].astype(bf)).astype(bf)
    return np.bincount(groups, weights=v[hit].astype(np.float64),
                       minlength=num_groups)


def relative_error(got, ref) -> float:
    """``max |got - ref| / max |ref|``: the error of the worst group,
    relative to the largest group sum. A missing answer reads ``inf``."""
    if got is None:
        return float("inf")
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        return float("inf")
    err = np.abs(got - ref).max()
    return float(err / max(np.abs(ref).max(), np.finfo(np.float64).tiny))
