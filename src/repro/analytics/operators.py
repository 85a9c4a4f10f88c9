"""Table-level analytics operators — thin columnar shells over the kernel
dispatch layer (``repro.kernels.ops``).

Two join implementations with genuinely different execution structure (the
paper's Fig. 3):

  * ``sort_merge_join_indices`` — sort both sides, linear merge via
    searchsorted (the shuffle-heavy plan: records with equal keys must be
    co-located).
  * ``hash_join_indices``       — build an open-addressing hash table over
    the (smaller) build side, probe with the (larger) probe side (the
    broadcast-heavy plan).

Join contract: the build side has unique keys (fact ⋈ dim); output is one row
per probe row with a ``found`` mask — static shapes, as JAX requires.

Since the vectorized-data-plane refactor the jitted primitives themselves
(hashing, partition permutation, join index computation, segment sums) live
in ``repro.kernels.ops``, which dispatches each to the Pallas kernel on TPU
or the jitted jnp fallback elsewhere; this module only lifts them to
``Table``s. The names below re-export the primitives so existing callers
and tests keep working.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.analytics.table import Table
from repro.kernels.ops import (  # noqa: F401  (re-exported primitives)
    EMPTY,
    HASH_MULT,
    MAX_PROBES,
    HashTable,
    build_hash_table,
    device_copy,
    grouping_indices,
    hash_join_indices,
    host_copy,
    partition_ids,
    partition_permutation,
    segment_sum,
    sort_merge_join_indices,
)
from repro.obs.tracer import get_tracer


def join(probe: Table, build: Table, key: str = "key",
         method: str = "hash", suffix: str = "_b") -> Table:
    """Inner-join (probe ⋈ build); returns probe columns + matched build
    columns + 'found' mask column. The index computation is one kernel
    dispatch per side (build + probe for hash, sort + merge for merge)."""
    pk, bk = device_copy([probe[key], build[key]])
    if method == "hash":
        table = build_hash_table(bk)
        idx, found = hash_join_indices(pk, bk, table)
        _note_probe_rounds(table)
    elif method == "merge":
        idx, found = sort_merge_join_indices(pk, bk)
    else:
        raise ValueError(method)
    cols = dict(probe.columns)
    host_idx = None
    for name, col in build.columns.items():
        if name == key:
            continue
        if isinstance(col, np.ndarray):
            # a host build side is gathered on the host: the indices come
            # down (waiting for the join) and the gathered column goes up
            if host_idx is None:
                host_idx = host_copy(idx, "join_idx")
            got = device_copy(col[host_idx])
        else:
            got = col[idx]
        out_name = name + (suffix if name in cols else "")
        cols[out_name] = jnp.where(
            found if col.ndim == 1 else found[:, None], got, 0)
    cols["found"] = found
    return Table(cols)


def _note_probe_rounds(table) -> None:
    """Traced, record the probe's depth on the enclosing ``kernel/join``
    span (attrs ``probe_rounds``, ``max_probes``); the read waits for the
    build only, the probe already dispatched. Untraced, nothing is read."""
    span = get_tracer().current()
    if span is None or span.name != "kernel/join":
        return
    span.attrs["probe_rounds"] = int(host_copy(table.rounds, "probe_rounds"))
    span.attrs["max_probes"] = MAX_PROBES


def groupby_sum(group_ids, values, num_groups: int):
    """Segment-sum values by group id (kernel-dispatched)."""
    return segment_sum(values, group_ids, num_groups)


def filter_table(t: Table, keep) -> Table:
    """Static-shape filter: zero out dropped rows, keep a validity column."""
    cols = {k: jnp.where(keep if v.ndim == 1 else keep[:, None], v, 0)
            for k, v in t.columns.items()}
    cols["valid"] = keep & t.columns.get("valid", jnp.ones_like(keep))
    return Table(cols)
