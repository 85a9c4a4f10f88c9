"""Seeded TPC-DS store_sales / item tables, made on the device.

The schema is the columnar projection of TPC-DS Q42/Q52 that the system
under test reads: the fact table (``store_sales``) holds ``key`` (int32,
``ss_item_sk``), ``v0`` and ``v1`` (float32; 12 bytes a row), the dimension
table (``item``) holds ``key`` and ``cat`` (int32, the item's category).
Every fact key names an item row: keys are foreign keys, with no miss.

Every seed gets the same sizes in another order. A partition's row count,
the number of its rows that pass ``v0 > 0``, and the multiset of keys among
the passing rows and among the others are fixed by the configuration and
the key law alone; the seed draws the order of the rows, the values and
the item categories. So every shape the program compiles for is the same
from seed to seed, and a run's set-up finds its programs in the compile
cache whatever its seed.

Key laws (``traffic["keys"]``):

* ``uniform`` — every item holds the same share of the rows.
* ``zipf`` with exponent ``s`` — the item of rank ``r`` holds a share
  proportional to ``(r + 1) ** -s`` (Chaudhuri & Narasayya's skewed TPC-D
  generator, Zipf factor z). Ranks are placed on item keys by one fixed
  permutation (``PLACEMENT_SEED``), the same for every run seed, so the hot
  items, and with them the shuffle's bucket sizes, do not move between
  seeds.

Each share is turned into whole row counts per partition by largest
remainder, so a partition holds exactly its share of each key.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PLACEMENT_SEED = 20250715      # fixed: where the ranks of the key law land


def prng_key(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, its high bits folded in (a
    bare ``PRNGKey`` keeps only the low 32 bits, so ``7`` and ``2**33 + 7``
    would draw the same tables)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


def key_weights(law: dict, num_keys: int) -> np.ndarray:
    """Share of the fact rows that each key rank holds (float64, sums to 1)."""
    kind = law.get("law", "uniform")
    if kind == "uniform":
        return np.full(num_keys, 1.0 / num_keys)
    if kind == "zipf":
        w = np.arange(1, num_keys + 1, dtype=np.float64) ** -float(law["s"])
        return w / w.sum()
    raise ValueError(f"unknown key law {kind!r}")


def whole_counts(weights: np.ndarray, rows: int) -> np.ndarray:
    """Largest-remainder split of ``rows`` by ``weights`` (ties to the
    lower rank): exact, and a fixed function of its arguments."""
    want = weights * rows
    counts = np.floor(want).astype(np.int64)
    short = rows - int(counts.sum())
    if short:
        counts[np.argsort(-(want - counts), kind="stable")[:short]] += 1
    return counts


def placement(num_keys: int) -> np.ndarray:
    """Item key of each rank of the key law (a fixed permutation)."""
    return np.random.default_rng(PLACEMENT_SEED).permutation(num_keys)


def split_rows(rows: int, parts: int) -> list[int]:
    """Row count of each of ``parts`` partitions, as ``np.array_split``."""
    return [rows // parts + (i < rows % parts) for i in range(parts)]


@dataclass(frozen=True)
class FactPlan:
    """The fixed part of a fact table: per partition, its row count, how
    many of its rows pass the filter, and how many rows of each key rank
    pass and do not. Host arrays, the same for every seed."""

    rows: tuple[int, ...]
    passing: tuple[int, ...]
    place: np.ndarray            # (keys,) item key of each rank
    counts_pass: np.ndarray      # (parts, keys) rows of each rank that pass
    counts_rest: np.ndarray      # (parts, keys) rows of each rank that don't


def fact_plan(rows: int, parts: int, num_keys: int, law: dict,
              pass_share: float = 0.5) -> FactPlan:
    w = key_weights(law, num_keys)
    sizes = split_rows(rows, parts)
    passing = [int(n * pass_share) for n in sizes]
    return FactPlan(
        tuple(sizes), tuple(passing), placement(num_keys).astype(np.int32),
        np.stack([whole_counts(w, p) for p in passing]).astype(np.int32),
        np.stack([whole_counts(w, n - p)
                  for n, p in zip(sizes, passing)]).astype(np.int32))


@partial(jax.jit, static_argnames=("rows", "passing"))
def _make_fact(key, place, counts_pass, counts_rest, rows, passing):
    """Every fact partition in one program: per partition the fixed keys
    in a seeded row order, ``v0 > 0`` on exactly the passing rows, ``v1``
    standard normal."""
    out = []
    for i, (n, n_pass) in enumerate(zip(rows, passing)):
        k_perm, k_v0, k_v1 = jax.random.split(jax.random.fold_in(key, i), 3)
        keys = jnp.concatenate([
            jnp.repeat(place, counts_pass[i], total_repeat_length=n_pass),
            jnp.repeat(place, counts_rest[i],
                       total_repeat_length=n - n_pass)])
        order = jax.random.permutation(k_perm, n)
        passes = (jnp.arange(n) < n_pass)[order]
        mag = jnp.abs(jax.random.normal(k_v0, (n,), jnp.float32))
        mag = jnp.maximum(mag, jnp.float32(1e-6))      # never exactly 0
        out.append({"key": keys[order], "v0": jnp.where(passes, mag, -mag),
                    "v1": jax.random.normal(k_v1, (n,), jnp.float32)})
    return out


@partial(jax.jit, static_argnames=("rows", "num_groups"))
def _make_dim(key, rows, num_groups):
    """Item keys ``0..sum(rows)-1`` in contiguous ranges (fixed), each
    partition in a seeded order with seeded categories."""
    out, lo = [], 0
    for i, n in enumerate(rows):
        k_perm, k_cat = jax.random.split(jax.random.fold_in(key, i))
        keys = jnp.arange(lo, lo + n, dtype=jnp.int32)
        out.append({"key": jax.random.permutation(k_perm, keys),
                    "cat": jax.random.randint(k_cat, (n,), 0, num_groups,
                                              jnp.int32)})
        lo += n
    return out


def make_fact(seed: int, plan: FactPlan) -> list[dict]:
    """Fact partitions (dicts of device columns) for ``seed``."""
    return _make_fact(jax.random.fold_in(prng_key(seed), 0), plan.place,
                      plan.counts_pass, plan.counts_rest, plan.rows,
                      plan.passing)


def make_dim(seed: int, rows: int, parts: int, num_groups: int) -> list[dict]:
    """Dimension partitions (dicts of device columns) for ``seed``."""
    return _make_dim(jax.random.fold_in(prng_key(seed), 1),
                     tuple(split_rows(rows, parts)), num_groups)
