"""Per-kernel allclose vs the jnp oracles (interpret mode), shape/dtype
sweeps + hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.partition import (fused_probe, partition_destinations,
                                     partition_histogram,
                                     partition_scatter)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _qkv(key, b, s, h, hd, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    mk = lambda k: jax.random.normal(k, (b, s, h, hd), dtype)
    return mk(k1), mk(k2), mk(k3)


@pytest.mark.parametrize("b,s,h,hd", [
    (1, 64, 1, 32), (2, 128, 4, 64), (1, 256, 2, 128), (2, 64, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, h, hd, dtype, causal):
    q, k, v = _qkv(jax.random.PRNGKey(42), b, s, h, hd, dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_block_shape_invariance():
    q, k, v = _qkv(jax.random.PRNGKey(7), 1, 128, 2, 32, jnp.float32)
    outs = [
        flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(s_blocks=st.integers(1, 4), h=st.sampled_from([1, 2, 4]),
       hd=st.sampled_from([16, 32]), seed=st.integers(0, 2 ** 16))
def test_flash_attention_property(s_blocks, h, hd, seed):
    s = 32 * s_blocks
    q, k, v = _qkv(jax.random.PRNGKey(seed), 1, s, h, hd, jnp.float32)
    out = flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    expected = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,s,kh,g,hd", [
    (1, 128, 1, 1, 32), (2, 256, 2, 4, 64), (1, 512, 4, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, s, kh, g, hd, dtype):
    h = kh * g
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (b, h, hd), dtype)
    kc = jax.random.normal(keys[1], (b, s, kh, hd), dtype)
    vc = jax.random.normal(keys[2], (b, s, kh, hd), dtype)
    length = jnp.asarray(np.random.default_rng(0).integers(1, s, b),
                         jnp.int32)
    out = decode_attention(q, kc, vc, length, block_k=64, interpret=True)
    expected = ref.decode_attention_ref(q, kc, vc, length)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_attention_respects_length():
    """Tokens beyond `length` must not influence the output."""
    b, s, kh, g, hd = 1, 128, 2, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, kh * g, hd))
    kc = jax.random.normal(keys[1], (b, s, kh, hd))
    vc = jax.random.normal(keys[2], (b, s, kh, hd))
    length = jnp.asarray([40], jnp.int32)
    out1 = decode_attention(q, kc, vc, length, block_k=32, interpret=True)
    kc2 = kc.at[:, 40:].set(99.0)
    vc2 = vc.at[:, 40:].set(-99.0)
    out2 = decode_attention(q, kc2, vc2, length, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-6)


@pytest.mark.parametrize("n,p,block", [(1024, 4, 256), (2048, 16, 512),
                                       (4096, 64, 1024)])
def test_partition_histogram(n, p, block):
    pids = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, p, jnp.int32)
    hist = partition_histogram(pids, p, block=block, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(hist), np.asarray(ref.partition_histogram_ref(pids, p)))


@pytest.mark.parametrize("n,p,d,block", [(512, 4, 4, 128), (2048, 16, 8, 512)])
def test_partition_scatter_matches_ref(n, p, d, block):
    pids = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, p, jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(3), (n, d))
    out, offsets = partition_scatter(rows, pids, p, block=block,
                                     interpret=True)
    r_out, r_off = ref.partition_scatter_ref(rows, pids, p)
    np.testing.assert_array_equal(np.asarray(offsets), np.asarray(r_off))
    np.testing.assert_allclose(np.asarray(out), np.asarray(r_out))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), p=st.sampled_from([2, 8, 32]))
def test_partition_is_stable_grouping(seed, p):
    """Property: output is a permutation, grouped by pid, stable within."""
    n, d = 512, 2
    pids = jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, p,
                              jnp.int32)
    rows = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.ones((1, d))
    out, offsets = partition_scatter(rows, pids, p, block=128,
                                     interpret=True)
    out_ids = np.asarray(out[:, 0]).astype(int)
    pids_np = np.asarray(pids)
    # permutation
    assert sorted(out_ids) == list(range(n))
    # grouped by pid, original order within group
    counts = np.bincount(pids_np, minlength=p)
    start = 0
    for part in range(p):
        seg = out_ids[start: start + counts[part]]
        expect = np.nonzero(pids_np == part)[0]
        np.testing.assert_array_equal(seg, expect)
        start += counts[part]


@pytest.mark.parametrize("n,p,block", [(1, 3, 1024), (200, 7, 1024),
                                       (5000, 33, 2048), (3000, 512, 1024)])
def test_partition_destinations_are_stable_grouping(n, p, block):
    """Row counts that are not a whole block are padded inside the wrapper;
    every destination is the row's slot in the stable grouped order, across
    rows and grid steps."""
    pids = jax.random.randint(jax.random.PRNGKey(n), (n,), 0, p, jnp.int32)
    dest, offsets = partition_destinations(pids, p, block=block,
                                           interpret=True)
    order = np.argsort(np.asarray(pids), kind="stable")
    expect = np.empty(n, np.int64)
    expect[order] = np.arange(n)
    np.testing.assert_array_equal(np.asarray(dest), expect)
    counts = np.bincount(np.asarray(pids), minlength=p)
    np.testing.assert_array_equal(np.asarray(offsets),
                                  np.cumsum(counts) - counts)


@pytest.mark.parametrize("n,m,pad", [(300, 40, 0), (2048, 512, 100),
                                     (1500, 64, 64)])
def test_fused_probe_matches_ref_with_padding_collisions(n, m, pad):
    """Padding build rows carry key 0 like the dispatch layer pads them; a
    real build key 0 must still match, and padding must never match."""
    rng = np.random.default_rng(n + m)
    keys = np.concatenate([[0], 1 + rng.permutation(4 * m - 1)[:m - 1]])
    bk = jnp.asarray(np.concatenate([keys, np.zeros(pad, int)]), jnp.int32)
    bc = jnp.asarray(rng.integers(0, 1000, m + pad), jnp.int32)
    bv = jnp.asarray(np.arange(m + pad) < m, jnp.int32)
    pk = jnp.asarray(rng.integers(0, 4 * m, n), jnp.int32)
    v0 = jnp.asarray(rng.standard_normal(n), jnp.float32)
    v1 = jnp.asarray(rng.standard_normal(n), jnp.float32)
    grp, wgt = fused_probe(pk, v0, v1, bk, bc, bv, 64, interpret=True)
    egrp, ewgt = ref.fused_probe_ref(pk, v0, v1, bk, bc, bv, 64)
    np.testing.assert_array_equal(np.asarray(grp), np.asarray(egrp))
    np.testing.assert_allclose(np.asarray(wgt), np.asarray(ewgt))


# -- dispatch-layer differentials: Pallas kernels vs ref vs numpy oracle ----------
#
# The dispatch layer (repro.kernels.ops) must agree with kernels/ref.py AND
# a from-scratch numpy oracle on the edges the raw kernels cannot express:
# empty input, a single bucket, every row in one bucket, and bucket counts
# that are not a power of two. force_kernel=True drives the Pallas path in
# interpret mode where shapes allow, so CI covers it without a TPU.


def _numpy_grouping_oracle(pids: np.ndarray, p: int):
    """Independent oracle: stable grouping permutation + exclusive offsets."""
    order = np.argsort(pids, kind="stable")
    counts = np.bincount(pids, minlength=p)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return order.astype(np.int32), offsets


@pytest.mark.parametrize("force_kernel", [False, True])
@pytest.mark.parametrize("case", [
    "empty", "single_bucket", "all_rows_one_bucket", "non_pow2_buckets"])
def test_grouping_indices_edges_match_numpy_oracle(case, force_kernel):
    from repro.kernels import ops as kops

    if case == "empty":
        pids, p = np.zeros((0,), np.int32), 4
    elif case == "single_bucket":
        pids, p = np.zeros((96,), np.int32), 1
    elif case == "all_rows_one_bucket":
        pids, p = np.full((128,), 2, np.int32), 8
    else:  # non_pow2_buckets
        rng = np.random.default_rng(5)
        pids, p = rng.integers(0, 7, size=200).astype(np.int32), 7
    order, offsets = kops.grouping_indices(jnp.asarray(pids), p,
                                           force_kernel=force_kernel)
    ref_order, ref_offsets = _numpy_grouping_oracle(pids, p)
    np.testing.assert_array_equal(np.asarray(offsets), ref_offsets)
    np.testing.assert_array_equal(np.asarray(order), ref_order)


@pytest.mark.parametrize("force_kernel", [False, True])
@pytest.mark.parametrize("n,p", [(0, 4), (256, 1), (128, 8), (384, 6)])
def test_dispatch_histogram_matches_ref_and_numpy(n, p, force_kernel):
    from repro.kernels import ops as kops

    rng = np.random.default_rng(n + p)
    pids = (rng.integers(0, p, size=n).astype(np.int32) if n else
            np.zeros((0,), np.int32))
    if n and p == 8:
        pids[:] = 3          # all rows in one bucket
    got = np.asarray(kops.partition_histogram(jnp.asarray(pids), p,
                                              force_kernel=force_kernel))
    np.testing.assert_array_equal(got, np.bincount(pids, minlength=p))
    np.testing.assert_array_equal(
        got, np.asarray(ref.partition_histogram_ref(jnp.asarray(pids), p)))


@pytest.mark.parametrize("force_kernel", [False, True])
@pytest.mark.parametrize("n,p,d", [(0, 4, 3), (128, 1, 2), (256, 8, 2),
                                   (320, 5, 4)])
def test_dispatch_scatter_matches_ref_and_numpy(n, p, d, force_kernel):
    from repro.kernels import ops as kops

    rng = np.random.default_rng(n + p + d)
    pids = (rng.integers(0, p, size=n).astype(np.int32) if n else
            np.zeros((0,), np.int32))
    rows = rng.standard_normal((n, d)).astype(np.float32)
    got, got_off = kops.partition_scatter(jnp.asarray(rows),
                                          jnp.asarray(pids), p,
                                          force_kernel=force_kernel)
    # numpy oracle: stable grouping
    order = np.argsort(pids, kind="stable")
    counts = np.bincount(pids, minlength=p)
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got_off), offsets)
    np.testing.assert_allclose(np.asarray(got), rows[order])
    if n:
        r_out, r_off = ref.partition_scatter_ref(jnp.asarray(rows),
                                                 jnp.asarray(pids), p)
        np.testing.assert_array_equal(np.asarray(got_off), np.asarray(r_off))
        np.testing.assert_allclose(np.asarray(got), np.asarray(r_out))
