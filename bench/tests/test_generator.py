import numpy as np
import pytest

from benchlib import gen
from repro.analytics.query import zipf_weights

ROWS, KEYS, PARTS = 40_003, 997, 4


def _fact(seed, law):
    plan = gen.fact_plan(ROWS, PARTS, KEYS, law)
    return plan, [{k: np.asarray(v) for k, v in p.items()}
                  for p in gen.make_fact(seed, plan)]


@pytest.mark.parametrize("law", [{"law": "uniform"},
                                 {"law": "zipf", "s": 1.5}])
def test_every_fact_key_names_an_item(law):
    _, parts = _fact(3, law)
    dim = [{k: np.asarray(v) for k, v in p.items()}
           for p in gen.make_dim(3, KEYS, 2, 64)]
    dim_keys = np.concatenate([d["key"] for d in dim])
    assert np.array_equal(np.sort(dim_keys), np.arange(KEYS))
    fact_keys = np.concatenate([p["key"] for p in parts])
    assert fact_keys.size == ROWS
    assert np.isin(fact_keys, dim_keys).all()
    cats = np.concatenate([d["cat"] for d in dim])
    assert cats.min() >= 0 and cats.max() < 64


def test_each_partition_passes_exactly_its_share():
    plan, parts = _fact(5, {"law": "uniform"})
    assert [p["key"].size for p in parts] == list(plan.rows)
    assert sum(plan.rows) == ROWS
    for p, n_pass in zip(parts, plan.passing):
        assert int((p["v0"] > 0).sum()) == n_pass
        assert not (p["v0"] == 0).any()


def test_zipf_law_matches_zipf_weights():
    plan, parts = _fact(11, {"law": "zipf", "s": 1.5})
    keys = np.concatenate([p["key"] for p in parts])
    by_rank = np.bincount(keys, minlength=KEYS)[plan.place]
    want = zipf_weights(KEYS, 1.5) * ROWS
    # largest remainder per partition and per filter side: each rank is
    # off by less than one row in each of the 2 * PARTS splits
    assert np.abs(by_rank - want).max() < 2 * PARTS
    assert by_rank[0] / ROWS == pytest.approx(zipf_weights(KEYS, 1.5)[0],
                                              abs=1e-3)


@pytest.mark.parametrize("law", [{"law": "uniform"},
                                 {"law": "zipf", "s": 1.5}])
def test_a_seed_changes_the_data_and_not_the_sizes(law):
    _, a = _fact(7, law)
    _, b = _fact(8, law)
    for pa, pb in zip(a, b):
        assert not np.array_equal(pa["key"], pb["key"])
        assert not np.array_equal(pa["v1"], pb["v1"])
        # the same keys pass the filter, in another order
        assert np.array_equal(np.sort(pa["key"][pa["v0"] > 0]),
                              np.sort(pb["key"][pb["v0"] > 0]))
        assert np.array_equal(np.sort(pa["key"]), np.sort(pb["key"]))


def test_same_seed_same_tables_and_high_bits_count():
    _, a = _fact(2**33 + 7, {"law": "uniform"})
    _, b = _fact(2**33 + 7, {"law": "uniform"})
    _, c = _fact(7, {"law": "uniform"})
    assert all(np.array_equal(x["v1"], y["v1"]) for x, y in zip(a, b))
    assert not np.array_equal(a[0]["v1"], c[0]["v1"])
