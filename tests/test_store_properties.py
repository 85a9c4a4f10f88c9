"""Property-based invariants for the shuffle store + quota machinery.

The hypothesis suite drives random interleavings of put / retry-overwrite /
delete_stage / clear_app / seal / get against a model and checks the store's
accounting invariants hold at every step:

  * ``resident_bytes`` equals the live blob bytes per node, never negative
  * ``app_bytes`` equals the live blob bytes per app, never negative
  * ``read_bytes`` / ``sent_bytes`` / ``cross_node_bytes`` are conserved:
    every byte a reader is charged was either local or counted exactly once
    against its source node's ``sent_bytes`` and the global cross-node total

The base interleaving suite runs once per *primary* storage backend
(memory / disk / emulated object store — accounting is medium-agnostic),
and a tiered variant adds demote (spill), promote-on-read, and stage-loss
operations with per-tier byte conservation and tombstone invariants.

The quota tests (plain pytest, always run) cover eviction of sealed stages,
blocking admission backpressure, the timeout error, and a whole query
executing under a quota with peak-footprint bounding, plus regressions for
batch-write atomicity, eviction targeting, and replace-path admission.
"""

import threading
import time

import pytest

from tests._hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.runtime import (DiskBackend, ObjectStoreBackend,
                           QuotaExceededError, ShuffleStore, StageLostError)


class FakeTable:
    """Duck-typed stand-in: the store only touches nbytes/num_rows/concat."""

    def __init__(self, nbytes: int, rows: int):
        self.nbytes = nbytes
        self.num_rows = rows

    def concat(self, other: "FakeTable") -> "FakeTable":
        return FakeTable(self.nbytes + other.nbytes,
                         self.num_rows + other.num_rows)


APPS = ("a", "b")
STAGES = ("s0", "s1")
WRITERS = ("w0", "w1")
NODES = (0, 1, 2)

op_put = st.tuples(st.just("put"), st.sampled_from(APPS),
                   st.sampled_from(STAGES), st.integers(0, 2),
                   st.sampled_from(WRITERS), st.integers(1, 100),
                   st.sampled_from(NODES))
op_delete = st.tuples(st.just("delete"), st.sampled_from(APPS),
                      st.sampled_from(STAGES))
op_clear = st.tuples(st.just("clear"), st.sampled_from(APPS))
op_seal = st.tuples(st.just("seal"), st.sampled_from(APPS),
                    st.sampled_from(STAGES))
op_get = st.tuples(st.just("get"), st.sampled_from(APPS),
                   st.sampled_from(STAGES), st.integers(0, 2),
                   st.sampled_from(NODES))
ops_strategy = st.lists(st.one_of(op_put, op_delete, op_clear, op_seal,
                                  op_get),
                        max_size=80)

# primary backends the base suite must hold on identically: accounting is
# medium-agnostic, only the payload's resting place differs
BACKENDS = ("memory", "disk", "object")


def _make_store(backend: str, **kw) -> ShuffleStore:
    """A store whose *primary* tier is ``backend``. The object tier is
    built with zeroed latency/bandwidth/cost so property runs stay
    instantaneous; disk uses a real tempdir (closed by the caller)."""
    if backend == "object":
        return ShuffleStore(backend=ObjectStoreBackend(
            latency_s=0.0, bw=None, cost_per_request=0.0, cost_per_gb=0.0),
            **kw)
    return ShuffleStore(backend=backend, **kw)


@pytest.mark.parametrize("backend", BACKENDS)
def test_store_accounting_invariants_under_interleavings(backend):
    @settings(deadline=None)
    @given(ops=ops_strategy)
    def prop(ops):
        store = _make_store(backend)
        try:
            _check_accounting_interleaving(store, ops)
        finally:
            store.close()

    prop()


def _check_accounting_interleaving(store: ShuffleStore, ops) -> None:
    # model: (app, stage) -> partition -> writer -> (nbytes, node)
    model: dict = {}
    total_read = 0          # every byte charged to any reader
    total_remote = 0        # the subset that crossed nodes

    for op in ops:
        if op[0] == "put":
            _, app, stage, part, writer, nbytes, node = op
            store.put(app, stage, part, FakeTable(nbytes, 1), node,
                      writer=writer)
            model.setdefault((app, stage), {}).setdefault(
                part, {})[writer] = (nbytes, node)
        elif op[0] == "delete":
            _, app, stage = op
            freed = store.delete_stage(app, stage)
            parts = model.pop((app, stage), {})
            assert freed == sum(b for blobs in parts.values()
                                for b, _ in blobs.values())
        elif op[0] == "clear":
            _, app = op
            freed = store.clear_app(app)
            expect = 0
            for key in [k for k in model if k[0] == app]:
                expect += sum(b for blobs in model.pop(key).values()
                              for b, _ in blobs.values())
            assert freed == expect
        elif op[0] == "seal":
            _, app, stage = op
            store.seal(app, stage)    # no quota: marker only, bytes stay
        else:
            _, app, stage, part, reader = op
            got = store.get(app, stage, part, node=reader)
            blobs = model.get((app, stage), {}).get(part, {})
            if not blobs:
                assert got is None
            else:
                assert got.nbytes == sum(b for b, _ in blobs.values())
                total_read += got.nbytes
                total_remote += sum(b for b, n in blobs.values()
                                    if n != reader)

        # -- invariants after every operation ---------------------------------
        live_per_node: dict[int, int] = {}
        live_per_app: dict[str, int] = {}
        for (app_k, _), parts in model.items():
            for blobs in parts.values():
                for b, n in blobs.values():
                    live_per_node[n] = live_per_node.get(n, 0) + b
                    live_per_app[app_k] = live_per_app.get(app_k, 0) + b
        assert all(v >= 0 for v in store.resident_bytes.values())
        assert {n: v for n, v in store.resident_bytes.items() if v} == \
            live_per_node
        assert all(v >= 0 for v in store.app_bytes.values())
        assert {a: v for a, v in store.app_bytes.items() if v} == \
            live_per_app
        # conservation: reader charges == model reads; remote subset appears
        # once in the source's sent_bytes and once in the global total
        assert sum(store.read_bytes.values()) == total_read
        assert sum(store.sent_bytes.values()) == total_remote
        assert store.cross_node_bytes == total_remote


# -- tiered interleavings: demotion / promotion / loss ------------------------

TIERS = ("disk", "object")

op_demote = st.tuples(st.just("demote"), st.sampled_from(APPS),
                      st.sampled_from(STAGES), st.sampled_from(TIERS))
op_lose = st.tuples(st.just("lose"), st.sampled_from(APPS),
                    st.sampled_from(STAGES))
tier_ops_strategy = st.lists(st.one_of(op_put, op_delete, op_seal, op_get,
                                       op_demote, op_lose),
                             max_size=80)


def _make_tiered_store() -> ShuffleStore:
    return ShuffleStore(spill_backends=[
        DiskBackend(),
        ObjectStoreBackend(latency_s=0.0, bw=None,
                           cost_per_request=0.0, cost_per_gb=0.0)])


@settings(deadline=None)
@given(ops=tier_ops_strategy)
def test_tiered_invariants_across_demote_promote_interleavings(ops):
    """Byte conservation, quota accounting, and tombstone invariants hold
    across arbitrary interleavings of writes, spills to colder tiers,
    promote-on-read (no quota: every cold read promotes), stage loss, and
    teardown: hot bytes live in resident/app accounting, cold bytes in
    ``tier_bytes``, and every blob is in exactly one of the two."""
    store = _make_tiered_store()
    try:
        _check_tiered_interleaving(store, ops)
    finally:
        store.close()


def _check_tiered_interleaving(store: ShuffleStore, ops) -> None:
    try:
        # model: (app, stage) -> part -> writer -> (nbytes, node, tier)
        model: dict = {}
        # (app, stage) -> tombstoned partition -> writers still owed: a lost
        # partition heals only once every writer whose slice was lost has
        # re-written it
        lost: dict = {}
        total_read = 0
        total_remote = 0
        for op in ops:
            if op[0] == "put":
                _, app, stage, part, writer, nbytes, node = op
                store.put(app, stage, part, FakeTable(nbytes, 1), node,
                          writer=writer)
                model.setdefault((app, stage), {}).setdefault(
                    part, {})[writer] = (nbytes, node, "memory")
                owed = lost.get((app, stage), {})
                if part in owed:
                    owed[part].discard(writer)
                    if not owed[part]:
                        del owed[part]
            elif op[0] == "delete":
                _, app, stage = op
                freed = store.delete_stage(app, stage)
                parts = model.pop((app, stage), {})
                lost.pop((app, stage), None)
                assert freed == sum(b for blobs in parts.values()
                                    for b, _, _ in blobs.values())
            elif op[0] == "seal":
                _, app, stage = op
                store.seal(app, stage)
            elif op[0] == "demote":
                _, app, stage, tier = op
                hot = sum(b for blobs in model.get((app, stage), {}).values()
                          for b, _, t in blobs.values() if t == "memory")
                freed = store.demote_stage(app, stage, tier)
                assert freed == hot      # only hot blobs spill
                for blobs in model.get((app, stage), {}).values():
                    for w, (b, n, t) in list(blobs.items()):
                        if t == "memory":
                            blobs[w] = (b, n, tier)
            elif op[0] == "lose":
                _, app, stage = op
                freed = store.lose_stage(app, stage)
                parts = model.pop((app, stage), {})
                # loss frees hot AND cold payloads (a lost spilled stage
                # recovers via lineage like any other)
                assert freed == sum(b for blobs in parts.values()
                                    for b, _, _ in blobs.values())
                for part, blobs in parts.items():
                    lost.setdefault((app, stage), {}).setdefault(
                        part, set()).update(blobs)
            else:   # get
                _, app, stage, part, reader = op
                blobs = model.get((app, stage), {}).get(part, {})
                if part in lost.get((app, stage), {}):
                    with pytest.raises(StageLostError):
                        store.get(app, stage, part, node=reader)
                else:
                    got = store.get(app, stage, part, node=reader)
                    if not blobs:
                        assert got is None
                    else:
                        assert got.nbytes == \
                            sum(b for b, _, _ in blobs.values())
                        total_read += got.nbytes
                        # only hot blobs are node-to-node traffic; cold
                        # reads are backend traffic
                        total_remote += sum(b for b, n, t in blobs.values()
                                            if t == "memory" and n != reader)
                        # no quota: every cold slice read promotes to hot
                        for w, (b, n, t) in list(blobs.items()):
                            blobs[w] = (b, n, "memory")

            # -- invariants after every operation -----------------------------
            hot_per_node: dict = {}
            hot_per_app: dict = {}
            cold: dict = {}      # tier -> app -> bytes
            for (app_k, _), parts in model.items():
                for blobs in parts.values():
                    for b, n, t in blobs.values():
                        if t == "memory":
                            hot_per_node[n] = hot_per_node.get(n, 0) + b
                            hot_per_app[app_k] = \
                                hot_per_app.get(app_k, 0) + b
                        else:
                            per = cold.setdefault(t, {})
                            per[app_k] = per.get(app_k, 0) + b
            assert all(v >= 0 for v in store.resident_bytes.values())
            assert {n: v for n, v in store.resident_bytes.items() if v} == \
                hot_per_node
            assert {a: v for a, v in store.app_bytes.items() if v} == \
                hot_per_app
            assert all(v >= 0 for per in store.tier_bytes.values()
                       for v in per.values())
            got_cold = {t: {a: v for a, v in per.items() if v}
                        for t, per in store.tier_bytes.items()}
            assert {t: per for t, per in got_cold.items() if per} == cold
            assert sum(store.read_bytes.values()) == total_read
            assert sum(store.sent_bytes.values()) == total_remote
            assert store.cross_node_bytes == total_remote
            for key_k, parts_k in lost.items():
                assert store.lost_partitions(*key_k) == set(parts_k)
    finally:
        store.close()


@settings(deadline=None)
@given(ops=st.lists(st.tuples(st.sampled_from(WRITERS), st.integers(1, 50)),
                    min_size=1, max_size=20))
def test_retry_overwrite_keeps_resident_at_last_write(ops):
    """Repeated retry-overwrites of one partition: resident bytes equal the
    sum of each writer's *last* slice, regardless of the retry history."""
    store = ShuffleStore()
    last: dict[str, int] = {}
    for writer, nbytes in ops:
        store.put("app", "s", 0, FakeTable(nbytes, 1), node=0, writer=writer)
        last[writer] = nbytes
    assert store.resident_bytes[0] == sum(last.values())
    assert store.app_bytes["app"] == sum(last.values())
    assert store.written_bytes[0] == sum(n for _, n in ops)


# -- quota machinery (always run) -------------------------------------------------


def test_quota_put_evicts_sealed_stage_lru():
    store = ShuffleStore(quotas={"app": 100})
    store.put("app", "old1", 0, FakeTable(40, 1), node=0, writer="w")
    store.put("app", "old2", 0, FakeTable(40, 1), node=0, writer="w")
    store.seal("app", "old1")
    store.seal("app", "old2")
    # 30 more bytes do not fit 100: the LRU sealed stage (old1) is evicted
    store.put("app", "new", 0, FakeTable(30, 1), node=0, writer="w")
    # evicted-but-was-written data reads as a typed loss (recoverable via
    # lineage), never as silently-absent None
    with pytest.raises(StageLostError):
        store.get("app", "old1", 0, node=0)
    assert store.get("app", "old2", 0, node=0) is not None
    assert store.app_bytes["app"] == 70
    assert store.evictions == [("app", "old1", 40)]
    assert store.peak_bytes["app"] <= 100


def test_sealed_stage_remains_readable_until_evicted():
    store = ShuffleStore(quotas={"app": 1000})
    store.put("app", "s", 0, FakeTable(10, 1), node=0, writer="w")
    store.seal("app", "s")
    assert store.get("app", "s", 0, node=0).nbytes == 10


def test_quota_blocks_until_concurrent_free():
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=5.0)
    store.put("app", "held", 0, FakeTable(90, 1), node=0, writer="w")

    def free_later():
        time.sleep(0.1)
        store.delete_stage("app", "held")

    t = threading.Thread(target=free_later)
    t.start()
    t0 = time.monotonic()
    store.put("app", "next", 0, FakeTable(50, 1), node=0, writer="w")
    waited = time.monotonic() - t0
    t.join()
    assert waited >= 0.05            # it really blocked for the free
    assert store.app_bytes["app"] == 50


def test_oversized_write_fails_fast_without_timeout():
    """A blob bigger than the quota itself can never be admitted: it must
    raise immediately, not pin the writer for quota_timeout seconds."""
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=10.0)
    t0 = time.monotonic()
    with pytest.raises(QuotaExceededError, match="can never fit"):
        store.put("app", "s", 0, FakeTable(101, 1), node=0, writer="w")
    assert time.monotonic() - t0 < 1.0


def test_quota_timeout_raises():
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=0.05)
    store.put("app", "held", 0, FakeTable(90, 1), node=0, writer="w")
    with pytest.raises(QuotaExceededError):
        store.put("app", "next", 0, FakeTable(50, 1), node=0, writer="w")
    # the held stage is untouched, the failed write landed nothing
    assert store.app_bytes["app"] == 90


def test_quota_retry_overwrite_charges_delta_not_sum():
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=0.05)
    store.put("app", "s", 0, FakeTable(80, 1), node=0, writer="w")
    # a retried invocation replaces its slice: 90 fits because 80 retracts
    store.put("app", "s", 0, FakeTable(90, 1), node=0, writer="w")
    assert store.app_bytes["app"] == 90
    assert store.peak_bytes["app"] == 90


def test_put_many_refused_batch_commits_nothing():
    """Regression: a quota refusal mid-batch must not leave the earlier
    partitions of the batch committed — admission covers the batch *total*
    up front, so a failed ``put_many`` is invisible (no partial commits,
    no tombstones, accounting untouched)."""
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=0.05)
    store.put("app", "held", 0, FakeTable(60, 1), node=0, writer="w")
    with pytest.raises(QuotaExceededError):
        # 30 + 30 = 60 > the 40 bytes of headroom; per-slice admission
        # would commit partition 0 before failing on partition 1
        store.put_many("app", "batch", {0: FakeTable(30, 1),
                                        1: FakeTable(30, 1)},
                       node=0, writer="w")
    assert store.partitions("app", "batch") == []
    assert store.lost_partitions("app", "batch") == set()
    assert store.app_bytes["app"] == 60
    assert store.resident_bytes[0] == 60


def test_put_many_oversized_batch_fails_fast():
    """A batch whose total can never fit fails fast even though every
    individual slice would fit — no trickle-in, no quota_timeout pin."""
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=10.0)
    t0 = time.monotonic()
    with pytest.raises(QuotaExceededError, match="can never fit"):
        store.put_many("app", "batch", {0: FakeTable(60, 1),
                                        1: FakeTable(60, 1)},
                       node=0, writer="w")
    assert time.monotonic() - t0 < 1.0
    assert store.partitions("app", "batch") == []
    assert store.app_bytes.get("app", 0) == 0


def test_eviction_never_targets_the_write_destination():
    """Regression: a sealed-then-rewritten stage must not evict *itself*
    to admit the new slice — that would tombstone peer writers' committed
    partitions of the very stage being written. With nothing else sealed
    the write times out; the destination's data survives untouched."""
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=0.05)
    store.put("app", "dest", 0, FakeTable(80, 1), node=0, writer="w0")
    store.seal("app", "dest")          # consumed once, now being rewritten
    with pytest.raises(QuotaExceededError):
        store.put("app", "dest", 1, FakeTable(40, 1), node=0, writer="w1")
    assert store.evictions == []
    assert store.lost_partitions("app", "dest") == set()
    assert store.get("app", "dest", 0, node=0).nbytes == 80


def test_eviction_reclaims_other_sealed_stage_not_destination():
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=0.05)
    store.put("app", "other", 0, FakeTable(50, 1), node=0, writer="w")
    store.put("app", "dest", 0, FakeTable(30, 1), node=0, writer="w")
    store.seal("app", "other")
    store.seal("app", "dest")
    # 40 more bytes need 20 of headroom: "other" is evicted, never "dest"
    store.put("app", "dest", 1, FakeTable(40, 1), node=0, writer="w")
    assert store.evictions == [("app", "other", 50)]
    assert store.get("app", "dest", 0, node=0).nbytes == 30
    assert store.app_bytes["app"] == 70


def test_admit_fail_fast_reports_write_size_and_net_delta():
    """Regression: the fail-fast error used to report only the raw write
    size; on the replace path the *net delta* (after retracting the
    replaced slice) is what the quota actually refused. Both appear."""
    store = ShuffleStore(quotas={"app": 100}, quota_timeout=10.0)
    store.put("app", "s", 0, FakeTable(40, 1), node=0, writer="w")
    t0 = time.monotonic()
    with pytest.raises(QuotaExceededError, match="can never fit") as ei:
        store.put("app", "s", 0, FakeTable(150, 1), node=0, writer="w")
    assert time.monotonic() - t0 < 1.0
    msg = str(ei.value)
    assert "150" in msg and "110" in msg     # raw size and net delta
    # the refused replace left the original slice in place
    assert store.app_bytes["app"] == 40
    assert store.get("app", "s", 0, node=0).nbytes == 40


def test_replace_admitted_on_delta_when_nbytes_exceeds_quota():
    """The replace path admits on the net delta: a shrinking rewrite is
    admitted instantly even though its raw size exceeds the quota and the
    app is already over the cap (lowered after the original write)."""
    store = ShuffleStore(quota_timeout=0.05)
    store.put("app", "s", 0, FakeTable(150, 1), node=0, writer="w")
    store.set_quota("app", 100)
    # delta is -30: admitted without blocking, raising, or evicting
    store.put("app", "s", 0, FakeTable(120, 1), node=0, writer="w")
    assert store.app_bytes["app"] == 120
    assert store.peak_bytes["app"] == 150
    assert store.evictions == []


def test_quota_is_per_app():
    store = ShuffleStore(quotas={"a": 50}, quota_timeout=0.05)
    store.put("a", "s", 0, FakeTable(50, 1), node=0, writer="w")
    # app b is uncapped; app a is at its limit
    store.put("b", "s", 0, FakeTable(500, 1), node=0, writer="w")
    with pytest.raises(QuotaExceededError):
        store.put("a", "s2", 0, FakeTable(1, 1), node=0, writer="w")


def test_reclaim_stage_seals_under_quota_deletes_otherwise():
    quota = ShuffleStore(quotas={"app": 1000})
    quota.put("app", "s", 0, FakeTable(10, 1), node=0, writer="w")
    assert quota.reclaim_stage("app", "s") == 0          # sealed, not freed
    assert quota.app_bytes["app"] == 10
    plain = ShuffleStore()
    plain.put("app", "s", 0, FakeTable(10, 1), node=0, writer="w")
    assert plain.reclaim_stage("app", "s") == 10         # dropped now
    assert plain.app_bytes["app"] == 0


def test_query_completes_under_quota_with_bounded_peak():
    """A full query under a per-app quota equal to its unconstrained peak:
    ephemeral stages get sealed instead of dropped, quota pressure evicts
    them, the result stays oracle-correct and the live footprint never
    exceeds the cap."""
    import jax.numpy as jnp
    import numpy as np

    from repro.analytics import (
        QueryStrategy,
        Table,
        execute_query_runtime,
        reference_query_numpy,
        synth_table,
    )
    from repro.analytics.table import distribute
    from repro.core.controllers import GlobalController
    from repro.runtime import Runtime

    fact = synth_table("f", 4096, 2048, seed=21)
    dimc = synth_table("d", 512, 2048, seed=22, unique_keys=True)
    dim = Table({**dimc.columns,
                 "cat": jnp.arange(512, dtype=jnp.int32) % 64})
    ref = reference_query_numpy(fact, dim)
    fd = distribute(fact, range(4), "A")
    dd = distribute(dim, range(2), "B")

    # measure the unconstrained high-water mark first
    got, rt = execute_query_runtime(fd, dd, QueryStrategy("static_merge"))
    np.testing.assert_allclose(got, ref, atol=1e-3)
    peak = rt.store.peak_bytes["query"]

    gc = GlobalController({n: 8 for n in range(4)})
    rt2 = Runtime(gc)
    rt2.store.set_quota("query", peak)
    got2, _ = execute_query_runtime(fd, dd, QueryStrategy("static_merge"),
                                    runtime=rt2)
    np.testing.assert_allclose(got2, ref, atol=1e-3)
    assert rt2.store.peak_bytes["query"] <= peak
    # sealing kept consumed shuffle state around until pressure reclaimed it
    assert rt2.store.evictions


def test_disagg_transfer_charged_only_after_quota_admission():
    """Regression: the emulated disaggregated-transfer sleep is paid only
    AFTER quota admission succeeds. A fail-fast oversized write must return
    immediately (no transfer for bytes that were never admitted), and an
    evict-then-retry admission pays the transfer exactly once — the same
    charge as a first-try admission of the same blob."""
    bw = 1000.0                      # bytes/s: a 200-byte blob "moves" in .2s
    store = ShuffleStore(net_bw=bw, disaggregated=True,
                         quotas={"a": 250})
    # fail-fast: delta > quota raises before any transfer is charged
    t0 = time.perf_counter()
    with pytest.raises(QuotaExceededError):
        store.put("a", "s0", 0, FakeTable(400, 4), node=0, writer="w")
    assert time.perf_counter() - t0 < 0.15
    # first-try admission: exactly one transfer
    t0 = time.perf_counter()
    store.put("a", "s0", 0, FakeTable(200, 2), node=0, writer="w")
    first = time.perf_counter() - t0
    store.seal("a", "s0")
    # evict-then-retry admission: evicts the sealed stage, then pays the
    # transfer once — accounting identical to the first-try path
    t0 = time.perf_counter()
    store.put("a", "s1", 0, FakeTable(200, 2), node=0, writer="w")
    second = time.perf_counter() - t0
    assert store.evictions == [("a", "s0", 200)]
    assert 0.2 <= first < 0.38 and 0.2 <= second < 0.38


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_hypothesis_present_marker():
    """Explicit marker so CI logs show whether the property suites really
    executed (they silently skip on bare environments)."""
    assert HAVE_HYPOTHESIS
