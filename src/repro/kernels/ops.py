"""The data plane's single kernel-dispatch point.

Every primitive the analytics operators and the serverless function library
touch routes through here: attention for the model plane, and the
partition / join / aggregate primitives for the analytics plane. Each entry
dispatches to the fastest available implementation — a Pallas kernel on TPU
(``partition_histogram``/``partition_destinations``/``fused_probe``), a
jitted single-pass jnp computation elsewhere — so callers never carry their
own ad-hoc ``jax.jit`` wrappers and every call site shares one compilation
cache. On a TPU the analytics entries never fall back to the jnp path; each
``kernel/*`` span records which path ran (``path="pallas"|"jit"``).

Shape classes: the partition-grouping entry point (``grouping_indices``)
pads its input to the next power of two before hitting the jitted body, so
32 map partitions with 32 different post-filter row counts compile a
handful of executables (one per power-of-two class), not 32 — the
no-per-partition-recompilation property the CI smoke benchmark asserts.

Host <-> device: a function body that needs a device result on the host,
or host numpy on the device, goes through ``device_wait`` /
``host_copy`` / ``device_copy``. With the tracer on they open
``sync/<site>`` (the host blocked on the device) and ``xfer/d2h`` /
``xfer/h2d`` spans (the copy, with its ``bytes``), tagged with the
calling function's name; with it off they are the bare copy or wait, so
tracing adds no blocking point.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.partition import (
    fused_probe as _fused_probe,
    partition_destinations as _destinations,
    partition_histogram as _hist,
    partition_scatter as _scatter,
)
from repro.obs.tracer import get_tracer

HASH_MULT = jnp.uint32(0x9E3779B1)   # Knuth multiplicative hash
EMPTY = jnp.int32(-1)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -- host <-> device -----------------------------------------------------------


def _arrays(tree) -> list:
    """The arrays of a column dict, a list or tuple, or one array."""
    if isinstance(tree, dict):
        return list(tree.values())
    if isinstance(tree, (list, tuple)):
        return list(tree)
    return [tree]


def _rebuilt(tree, arrays: list):
    """``tree``'s shape (key order kept) holding ``arrays``."""
    if isinstance(tree, dict):
        return dict(zip(tree, arrays))
    if isinstance(tree, (list, tuple)):
        return type(tree)(arrays)
    return arrays[0]


def device_wait(tree, site: str):
    """Block until every device array of ``tree`` is computed, under a
    ``sync/<site>`` span; returns ``tree``."""
    tr = get_tracer()
    if not tr.enabled or not any(isinstance(x, jax.Array)
                                 for x in _arrays(tree)):
        return jax.block_until_ready(tree)
    with tr.span(f"sync/{site}", "sync", func=tr.current_attr("func")):
        return jax.block_until_ready(tree)


def host_copy(tree, site: str):
    """``tree`` with every array as host numpy. Traced, the wait for the
    device results is a ``sync/<site>`` span and the copy an ``xfer/d2h``
    span; untraced, one ``np.asarray`` per array, as blocking as before."""
    arrays = _arrays(tree)
    tr = get_tracer()
    on_dev = [x for x in arrays if isinstance(x, jax.Array)] \
        if tr.enabled else ()
    if not on_dev:
        return _rebuilt(tree, [np.asarray(x) for x in arrays])
    func = tr.current_attr("func")
    with tr.span(f"sync/{site}", "sync", func=func):
        jax.block_until_ready(on_dev)
    with tr.span("xfer/d2h", "xfer", func=func,
                 bytes=sum(int(x.nbytes) for x in on_dev)):
        return _rebuilt(tree, [np.asarray(x) for x in arrays])


def device_copy(tree):
    """``tree`` with its host numpy arrays put on the device in one
    ``jax.device_put``, under an ``xfer/h2d`` span (traced, it lasts until
    the copy has landed); device arrays pass through untouched."""
    arrays = _arrays(tree)
    host = [i for i, x in enumerate(arrays) if isinstance(x, np.ndarray)]
    if not host:
        return tree
    tr = get_tracer()
    if not tr.enabled:
        moved = jax.device_put([arrays[i] for i in host])
    else:
        with tr.span("xfer/h2d", "xfer", func=tr.current_attr("func"),
                     bytes=sum(int(arrays[i].nbytes) for i in host)):
            moved = jax.block_until_ready(
                jax.device_put([arrays[i] for i in host]))
    for i, x in zip(host, moved):
        arrays[i] = x
    return _rebuilt(tree, arrays)


# -- attention -----------------------------------------------------------------


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, force_kernel: bool = False):
    """(B,S,H,hd) attention; kernel on TPU, oracle elsewhere."""
    if on_tpu() or force_kernel:
        return _flash(q, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=not on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal)


def decode_attention(q, k_cache, v_cache, length, block_k: int = 512,
                     force_kernel: bool = False):
    if on_tpu() or force_kernel:
        return _decode(q, k_cache, v_cache, length, block_k=block_k,
                       interpret=not on_tpu())
    return ref.decode_attention_ref(q, k_cache, v_cache, length)


# -- partitioning (the shuffle primitive) --------------------------------------


def _hash(keys: jax.Array, bits: int) -> jax.Array:
    h = keys.astype(jnp.uint32) * HASH_MULT
    return (h >> (32 - bits)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("num_partitions",))
def partition_ids(keys: jax.Array, num_partitions: int) -> jax.Array:
    """Radix/hash partition id per row."""
    bits = max(1, int(np.ceil(np.log2(num_partitions))))
    return _hash(keys, bits) % num_partitions


@partial(jax.jit, static_argnames=("num_partitions",))
def partition_permutation(keys: jax.Array, num_partitions: int):
    """Stable permutation grouping rows by partition + per-partition counts.

    The jitted single-pass fallback the dispatch layer uses off-TPU; the
    Pallas histogram/scatter pair computes the same grouping on TPU.
    """
    pids = partition_ids(keys, num_partitions)
    order = jnp.argsort(pids, stable=True)
    counts = jnp.bincount(pids, length=num_partitions)
    return order, counts, pids


def _kernel_path(force_kernel: bool) -> str:
    """``"pallas"`` on TPU (or when a test forces the interpret-mode
    kernel), ``"jit"`` for the jnp path elsewhere — the ``path`` attribute
    every ``kernel/*`` span carries."""
    return "pallas" if on_tpu() or force_kernel else "jit"


def partition_histogram(part_ids, num_partitions: int,
                        force_kernel: bool = False):
    """Per-partition row counts: the Pallas histogram on TPU, jnp bincount
    elsewhere. The kernel pads any row count to its block, so on a TPU it
    serves every ``n > 0``."""
    n = int(part_ids.shape[0])
    if n == 0:
        return jnp.zeros((num_partitions,), jnp.int32)
    path = _kernel_path(force_kernel)
    with get_tracer().span("kernel/histogram", "kernel", rows=n,
                           buckets=num_partitions, path=path):
        if path == "pallas":
            return _hist(part_ids, num_partitions, interpret=not on_tpu())
        return ref.partition_histogram_ref(part_ids, num_partitions)


def partition_scatter(rows, part_ids, num_partitions: int,
                      force_kernel: bool = False):
    """Stable grouping of 2-D rows by partition id -> (grouped, offsets):
    the Pallas destinations kernel plus an XLA scatter on TPU, the jnp
    reference elsewhere."""
    n = int(rows.shape[0])
    if n == 0:
        return rows, jnp.zeros((num_partitions,), jnp.int32)
    if _kernel_path(force_kernel) == "pallas":
        return _scatter(rows, part_ids, num_partitions,
                        interpret=not on_tpu())
    return ref.partition_scatter_ref(rows, part_ids, num_partitions)


def _pad_len(n: int) -> int:
    """Next power of two >= n (floor 8): the shape-class quantizer that
    keeps per-partition row-count jitter from recompiling the jitted
    grouping body."""
    return max(8, 1 << int(np.ceil(np.log2(max(1, n)))))


# (padded_len, num_partitions) pairs already dispatched — tells the kernel
# span whether this call paid a fresh trace/compile or hit the jit cache
_SHAPE_CLASSES: set[tuple[int, int]] = set()

# per-thread padded-vs-actual row tally for every shape-class dispatch; the
# invoker snapshots it around each function body so padding waste lands on
# the invocation record (-> profile_feedback "padding_overhead") instead of
# needing a re-profile to spot a probe-side blowup
_padding_tls = threading.local()


def _note_padding(rows: int, padded: int) -> None:
    c = getattr(_padding_tls, "counts", None)
    if c is None:
        c = _padding_tls.counts = [0, 0]
    c[0] += int(rows)
    c[1] += int(padded)


def padding_counters() -> tuple[int, int]:
    """``(actual_rows, padded_rows)`` dispatched through shape-class-padded
    kernel entry points by this thread since ``reset_padding_counters``."""
    c = getattr(_padding_tls, "counts", None)
    return (c[0], c[1]) if c else (0, 0)


def reset_padding_counters() -> None:
    _padding_tls.counts = [0, 0]


@partial(jax.jit, static_argnames=("num_partitions",))
def _grouping_padded(pids_padded: jax.Array, num_partitions: int):
    """Grouping permutation over a padded id vector.

    Padding rows carry the sentinel id ``num_partitions`` — larger than any
    real id, so the stable sort parks them at the end and the first
    ``offsets[-1]`` entries of ``order`` are exactly the real rows'
    grouping permutation. ``offsets`` has ``num_partitions + 1`` entries
    (exclusive prefix; the last is the total real-row count).
    """
    order = jnp.argsort(pids_padded, stable=True)
    counts = jnp.bincount(pids_padded, length=num_partitions + 1)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts[:num_partitions]).astype(jnp.int32)])
    return order, offsets


@partial(jax.jit, static_argnames=("num_partitions", "interpret"))
def _grouping_pallas(pids_padded: jax.Array, num_partitions: int,
                     interpret: bool = False):
    """The Pallas twin of ``_grouping_padded``: the kernel's destinations
    over ``num_partitions + 1`` buckets (the sentinel bucket last), then
    one XLA scatter inverts them into the grouping permutation. The
    exclusive offsets of the ``P + 1`` buckets are exactly
    ``[0, c0, c0+c1, ..., real-row count]``."""
    dest, offsets = _destinations(pids_padded, num_partitions + 1,
                                  interpret=interpret)
    n = pids_padded.shape[0]
    order = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True)
    return order, offsets


def grouping_indices(part_ids, num_partitions: int,
                     force_kernel: bool = False):
    """One-call shuffle grouping: ``(order, offsets)`` for a partition-id
    vector, where ``order[offsets[p]:offsets[p+1]]`` are partition ``p``'s
    row indices in stable (original) order.

    This is the single-pass replacement for the per-bucket
    ``np.nonzero``/``take`` loop: one device computation yields every
    bucket's membership at once. Inputs are padded to a power-of-two shape
    class before the jitted body (or the Pallas destinations kernel on
    TPU) runs, so heterogeneous per-partition row counts share a handful
    of compiled executables.
    """
    n = int(part_ids.shape[0])
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((num_partitions + 1,), jnp.int32))
    n_pad = _pad_len(n)
    _note_padding(n, n_pad)
    shape_class = (n_pad, num_partitions)
    fresh = shape_class not in _SHAPE_CLASSES
    _SHAPE_CLASSES.add(shape_class)
    path = _kernel_path(force_kernel)
    with get_tracer().span("kernel/grouping", "kernel", rows=n,
                           shape_class=n_pad, buckets=num_partitions,
                           compile="fresh" if fresh else "cached",
                           path=path):
        pids = jnp.asarray(part_ids, jnp.int32)
        if n_pad != n:
            pids = jnp.concatenate(
                [pids, jnp.full((n_pad - n,), num_partitions, jnp.int32)])
        if path == "pallas":
            order, offsets = _grouping_pallas(pids, num_partitions,
                                              interpret=not on_tpu())
        else:
            order, offsets = _grouping_padded(pids, num_partitions)
        return order[:n], offsets


def shape_class_count() -> int:
    """Distinct (padded_len, num_partitions) shape classes dispatched so
    far — the compile-cache growth figure the skew regression test bounds
    (salted sub-joins quantize their chunk sizes so a lopsided bucket adds
    at most two classes, not one per chunk)."""
    return len(_SHAPE_CLASSES)


# heavy-hitter sketch sizing: one hash-slot histogram per shuffle writer.
# 512 slots keeps the counter array a single cache line level while a
# dominating key still owns its slot with overwhelming probability.
HOT_SKETCH_SLOTS = 512
HOT_KEYS_K = 8


def heavy_hitter_sketch(keys, k: int = HOT_KEYS_K,
                        num_slots: int = HOT_SKETCH_SLOTS,
                        force_kernel: bool = False,
                        ) -> tuple[tuple[int, int], ...]:
    """Exact top-k heavy hitters of a key column, sketch-then-verify.

    Phase 1 hashes every key into ``num_slots`` counters — the Pallas
    one-hot histogram on TPU (``force_kernel`` for interpret-mode tests),
    the jnp bincount reference elsewhere: the same dispatch as
    ``partition_histogram``, and a single fixed shape class regardless of
    key cardinality. Phase 2 takes the ``k`` heaviest slots as candidates
    and counts their actual keys exactly on the host (a small subset when
    the data is skewed). Returns ``((key, count), ...)`` sorted by
    (-count, key) — deterministic, so the runtime's observed sketch and
    the simulator's recomputation of it are identical tuples.
    """
    n = int(keys.shape[0])
    if n == 0:
        return ()
    k = max(1, int(k))
    keys = jnp.asarray(keys, jnp.int32)
    slot_ids = partition_ids(keys, num_slots)
    hist = host_copy(partition_histogram(slot_ids, num_slots,
                                         force_kernel=force_kernel),
                     "sketch_hist")
    cand = np.argsort(-hist, kind="stable")[:k]
    cand = cand[hist[cand] > 0]
    if cand.size == 0:
        return ()
    slots_h, keys_h = host_copy([slot_ids, keys], "sketch_keys")
    tr = get_tracer()
    with tr.span("host/sketch_verify", "host", func=tr.current_attr("func"),
                 rows=n):
        sub = keys_h[np.isin(slots_h, cand)]
        uniq, counts = np.unique(sub, return_counts=True)
        order = np.lexsort((uniq, -counts))[:k]
    return tuple((int(uniq[i]), int(counts[i])) for i in order)


def salted_ranges(total_rows: int, salt: int) -> tuple[tuple[int, int], ...]:
    """Row ranges splitting a heavy join bucket ``salt`` ways for the
    salted sub-joins. The chunk size is quantized UP to a power of two
    (``_pad_len``), so every full chunk is exactly one padded shape class
    and only the final remainder chunk can add a second — the cap that
    keeps a skewed bucket from fanning the compile cache into per-chunk
    classes. May return fewer than ``salt`` ranges after quantization."""
    total = int(total_rows)
    if total <= 0:
        return ()
    chunk = _pad_len(-(-total // max(1, int(salt))))
    return tuple((lo, min(lo + chunk, total))
                 for lo in range(0, total, chunk))


def grouping_cache_size() -> int:
    """Compiled-executable count of the jitted grouping body — the CI
    smoke benchmark asserts this stays at one per (shape class, bucket
    count), i.e. no per-partition recompilation."""
    return int(_grouping_padded._cache_size())


# -- joins ---------------------------------------------------------------------


@jax.jit
def sort_merge_join_indices(probe_keys: jax.Array, build_keys: jax.Array):
    """Sort-merge: sort build side, binary-merge probe side.

    Returns (idx_into_build, found) aligned with probe rows.
    """
    build_order = jnp.argsort(build_keys)
    sorted_build = build_keys[build_order]
    pos = jnp.searchsorted(sorted_build, probe_keys)
    pos = jnp.clip(pos, 0, build_keys.shape[0] - 1)
    found = sorted_build[pos] == probe_keys
    idx = jnp.where(found, build_order[pos], 0)
    return idx, found


def _hash_table_size(n: int) -> int:
    # load factor <= 0.25: linear-probing cluster lengths stay far below
    # the probe budget even for multi-million-row build sides
    return max(16, int(2 ** np.ceil(np.log2(4 * n))))


class HashTable(NamedTuple):
    """An open-addressing table over a build side's row indices.

    ``rounds`` (device int32 scalar) is how deep the build placed its keys:
    a key placed in round ``p`` sits at slot ``(h0 + p) % cap``, and
    ``p < rounds`` for every placed key, so a probe of ``rounds`` rounds
    finds all that a deeper one would."""

    slots: jax.Array                  # stored row index, -1 = empty
    rounds: jax.Array


# the build's round budget: the deepest probe a table can ask for
MAX_PROBES = 16


@partial(jax.jit, static_argnames=("max_probes",))
def build_hash_table(build_keys: jax.Array,
                     max_probes: int = MAX_PROBES) -> HashTable:
    """Open-addressing (linear probing) insert of unique build keys.

    Parallel insertion: each round, every unplaced key writes its row index
    to its current probe slot; scatter conflicts resolve max-row-wins,
    losers advance to the next probe position. Rounds go on while some key
    is unplaced, at most ``max_probes``; a key still unplaced then stays
    out of the table. At the load factor <= 0.25 of ``_hash_table_size``
    dense keys all land in round 0.

    ``rounds`` is one more than the last round that placed a key, or
    ``max_probes`` if some key was never placed.
    """
    n = build_keys.shape[0]
    cap = _hash_table_size(n)
    bits = int(np.log2(cap))
    h0 = _hash(build_keys, bits)
    rows = jnp.arange(n, dtype=jnp.int32)

    def unplaced(carry):
        p, _, placed = carry
        return jnp.logical_and(p < max_probes,
                               jnp.logical_not(jnp.all(placed)))

    def round_(carry):
        p, slots, placed = carry
        pos = (h0 + p) % cap
        # only unplaced keys contending for currently-empty slots
        want = jnp.logical_and(jnp.logical_not(placed), slots[pos] == EMPTY)
        cand = jnp.where(want, rows, EMPTY)
        tgt = jnp.where(want, pos, cap)        # park non-contenders off-table
        slots_ext = jnp.concatenate([slots, jnp.full((1,), EMPTY)])
        slots_ext = slots_ext.at[tgt].max(cand)   # max = deterministic winner
        slots = slots_ext[:cap]
        placed = jnp.logical_or(placed, slots[pos] == rows)
        return p + 1, slots, placed

    # the loop stops right after the round that placed the last key, or at
    # max_probes with some key unplaced: either way p is the depth
    rounds, slots, _ = jax.lax.while_loop(
        unplaced, round_,
        (jnp.int32(0), jnp.full((cap,), EMPTY), jnp.zeros((n,), bool)))
    return HashTable(slots, rounds)


@jax.jit
def hash_join_indices(probe_keys: jax.Array, build_keys: jax.Array,
                      table: HashTable):
    """Probe the hash table, ``table.rounds`` rounds deep (a traced bound:
    no recompilation as the depth changes). Returns (idx_into_build,
    found) per probe row."""
    slots = table.slots
    cap = slots.shape[0]
    bits = int(np.log2(cap))
    h = _hash(probe_keys, bits)

    def probe(p, carry):
        idx, found = carry
        pos = (h + p) % cap
        cand = slots[pos]
        hit = jnp.logical_and(
            cand != EMPTY,
            jnp.logical_and(build_keys[jnp.maximum(cand, 0)] == probe_keys,
                            jnp.logical_not(found)))
        idx = jnp.where(hit, cand, idx)
        return idx, jnp.logical_or(found, hit)

    idx0 = jnp.zeros_like(probe_keys)
    found0 = jnp.zeros(probe_keys.shape, bool)
    idx, found = jax.lax.fori_loop(0, table.rounds, probe, (idx0, found0))
    return idx, found


# -- fused partition+probe (the pipelined join's bucket primitive) -------------

# build sides at or below this padded row count ride in the kernel's SMEM
# (two int32 columns, 32 KiB at 4096 rows) and cost one pass over the probe
# block per build row; larger buckets take the jitted sorted-search body
FUSED_VMEM_ROWS = 4096


@partial(jax.jit, static_argnames=("num_groups",))
def _fused_probe_padded(pk, v0, v1, bk, bc, bv, num_groups: int):
    """Jitted sorted-search body over shape-class-padded buckets: sort the
    build side once, binary-search every probe key, mask invalid (padding)
    build rows through the sort so a sentinel collision can never fake a
    match."""
    big = jnp.int32(2**31 - 1)
    keys = jnp.where(bv != 0, bk, big)     # park padding rows at the end
    order = jnp.argsort(keys)
    skeys = keys[order]
    scat = bc[order]
    svalid = bv[order]
    pos = jnp.clip(jnp.searchsorted(skeys, pk), 0, skeys.shape[0] - 1)
    found = jnp.logical_and(skeys[pos] == pk, svalid[pos] != 0)
    cat = jnp.where(found, scat[pos], 0)
    weight = jnp.where(found, v0 * v1, jnp.float32(0.0))
    return cat % num_groups, weight


def fused_probe_groups(probe_keys, v0, v1, build_keys, build_cat,
                       num_groups: int, force_kernel: bool = False):
    """Fused partition+probe+weight for one shuffled join bucket.

    Collapses the bucket's sort-merge join, the found-mask ``where`` and
    the group projection into ONE dispatch: returns ``(group, weight)``
    numpy columns aligned with probe rows, where non-matching probe rows
    carry group 0 / weight 0 — bit-identical to the unfused
    ``join -> where(found) -> cat % G`` pipeline (build keys unique per the
    join contract). Probe and build sides are padded to power-of-two shape
    classes; the Pallas path runs when the build side is at most
    ``FUSED_VMEM_ROWS`` rows, the jitted sorted-search body elsewhere.
    """
    n = int(probe_keys.shape[0])
    m = int(build_keys.shape[0])
    if n == 0 or m == 0:
        return (np.zeros((n,), np.int32), np.zeros((n,), np.float32))
    n_pad, m_pad = _pad_len(n), _pad_len(m)
    _note_padding(n + m, n_pad + m_pad)
    kernel_ok = _kernel_path(force_kernel) == "pallas" and \
        m_pad <= FUSED_VMEM_ROWS
    with get_tracer().span("kernel/fused_probe", "kernel", rows=n,
                           build_rows=m, shape_class=n_pad,
                           path="pallas" if kernel_ok else "jit"):
        probe_keys, v0, v1, build_keys, build_cat = device_copy(
            [probe_keys, v0, v1, build_keys, build_cat])
        pk = jnp.asarray(probe_keys, jnp.int32)
        v0 = jnp.asarray(v0, jnp.float32)
        v1 = jnp.asarray(v1, jnp.float32)
        if n_pad != n:
            pk = jnp.concatenate([pk, jnp.zeros((n_pad - n,), jnp.int32)])
            v0 = jnp.concatenate([v0, jnp.zeros((n_pad - n,), jnp.float32)])
            v1 = jnp.concatenate([v1, jnp.zeros((n_pad - n,), jnp.float32)])
        bk = jnp.asarray(build_keys, jnp.int32)
        bc = jnp.asarray(build_cat, jnp.int32)
        bv = jnp.ones((m,), jnp.int32)
        if m_pad != m:
            bk = jnp.concatenate([bk, jnp.zeros((m_pad - m,), jnp.int32)])
            bc = jnp.concatenate([bc, jnp.zeros((m_pad - m,), jnp.int32)])
            bv = jnp.concatenate([bv, jnp.zeros((m_pad - m,), jnp.int32)])
        if kernel_ok:
            grp, wgt = _fused_probe(pk, v0, v1, bk, bc, bv, num_groups,
                                    interpret=not on_tpu())
        else:
            grp, wgt = _fused_probe_padded(pk, v0, v1, bk, bc, bv,
                                           num_groups)
        grp, wgt = host_copy([grp, wgt], "fused_probe")
        return grp[:n], wgt[:n]


# -- aggregation ---------------------------------------------------------------


@partial(jax.jit, static_argnames=("num_segments",))
def segment_sum(values: jax.Array, segment_ids: jax.Array,
                num_segments: int) -> jax.Array:
    """Segment-sum values by id — the grouped-aggregation primitive."""
    return jax.ops.segment_sum(values, segment_ids,
                               num_segments=num_segments)
