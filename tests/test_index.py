"""repro.index invariants: the acceptance property is that *any* sequence of
ingest/delete/seal/compact/save/load operations answers ``query`` identically
(values and tie-broken ids) to a dense ``knn`` over the equivalent live
corpus sketched in one shot — plus no-recompile ingest, micro-batching, and
the reservoir's ring semantics."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LpSketch, SketchConfig, knn, sketch
from repro.index import (
    IndexConfig,
    MicroBatcher,
    SketchIndex,
    SketchReservoir,
)
from repro.index.segment import _write_rows

CFG = SketchConfig(p=4, k=32, block_d=64)
D = 256


def make_index(capacity=100, seed=7):
    return SketchIndex(CFG, seed=seed,
                       index_cfg=IndexConfig(segment_capacity=capacity))


def rows_of(rng, n):
    return jnp.asarray(rng.uniform(0, 1, (n, D)).astype(np.float32))


def dense_reference(index, X_live, Q, top_k):
    """One-shot sketch + dense knn of the live corpus (positions ascending)."""
    corpus = sketch(jnp.asarray(X_live), index.key, CFG)
    qs = sketch(jnp.asarray(Q), index.key, CFG)
    return knn(qs, corpus, CFG, top_k=top_k)


def assert_matches_dense(index, X, live_mask, Q, top_k=7):
    """Index query == dense knn over live rows (values bitwise, ids mapped)."""
    d_idx, ids = index.query(jnp.asarray(Q), top_k=top_k)
    d_ref, pos_ref = dense_reference(index, X[live_mask], Q, top_k)
    live_ids = np.flatnonzero(live_mask)
    np.testing.assert_array_equal(np.asarray(d_idx), np.asarray(d_ref))
    np.testing.assert_array_equal(ids, live_ids[np.asarray(pos_ref)])


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


def test_query_matches_dense_across_segments(rng):
    X = np.asarray(rows_of(rng, 350))
    Q = np.asarray(rows_of(rng, 5))
    index = make_index(capacity=100)
    index.ingest(jnp.asarray(X))  # 3 sealed segments + half-full active
    assert index.stats()["sealed_segments"] == 3
    assert_matches_dense(index, X, np.ones(350, bool), Q)


def test_seal_boundary_matches_one_shot(rng):
    """Ingest across a segment-seal boundary == one-shot sketch of the rows."""
    X = np.asarray(rows_of(rng, 130))
    Q = np.asarray(rows_of(rng, 4))
    index = make_index(capacity=64)
    # batches deliberately straddle the 64-row seal boundary
    for lo, hi in ((0, 50), (50, 90), (90, 130)):
        index.ingest(jnp.asarray(X[lo:hi]))
    assert index.stats()["sealed_segments"] == 2
    assert_matches_dense(index, X, np.ones(130, bool), Q)


def test_query_after_delete_excludes_tombstones(rng):
    X = np.asarray(rows_of(rng, 250))
    Q = np.asarray(rows_of(rng, 6))
    index = make_index(capacity=100)
    ids = index.ingest(jnp.asarray(X))
    dead = np.concatenate([ids[10:60], ids[180:220]])
    assert index.delete(dead) == 90
    assert index.delete(dead) == 0  # idempotent
    live = np.ones(250, bool)
    live[10:60] = False
    live[180:220] = False
    assert index.n_live == live.sum()
    assert_matches_dense(index, X, live, Q)
    # tombstoned ids never surface even at top_k > live count of a segment
    _, got = index.query(jnp.asarray(Q), top_k=60)
    assert not np.isin(got, dead).any()


def test_compaction_is_bit_for_bit(rng):
    X = np.asarray(rows_of(rng, 300))
    Q = np.asarray(rows_of(rng, 5))
    index = make_index(capacity=100)
    ids = index.ingest(jnp.asarray(X))
    index.delete(ids[5:95])    # segment 0 nearly dead
    index.delete(ids[100:200])  # segment 1 fully dead
    before = index.query(jnp.asarray(Q), top_k=9)
    n = index.compact(min_live_frac=0.5)
    assert n == 2
    assert index.stats()["sealed_segments"] == 2  # fully-dead segment dropped
    after = index.query(jnp.asarray(Q), top_k=9)
    np.testing.assert_array_equal(np.asarray(before[0]), np.asarray(after[0]))
    np.testing.assert_array_equal(before[1], after[1])
    live = np.ones(300, bool)
    live[5:95] = False
    live[100:200] = False
    assert_matches_dense(index, X, live, Q)


def test_save_load_round_trip(rng, tmp_path):
    X = np.asarray(rows_of(rng, 230))
    Q = np.asarray(rows_of(rng, 5))
    index = make_index(capacity=100)
    ids = index.ingest(jnp.asarray(X))
    index.delete(ids[40:80])
    path = str(tmp_path / "idx")
    index.save(path)
    index.save(path)  # atomic replace of an existing save
    loaded = SketchIndex.load(path)
    assert loaded.n_live == index.n_live
    assert loaded.next_row_id == index.next_row_id
    d0, i0 = index.query(jnp.asarray(Q), top_k=8)
    d1, i1 = loaded.query(jnp.asarray(Q), top_k=8)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(i0, i1)
    # the reloaded index keeps serving: ingest + delete + query still coherent
    more = loaded.ingest(rows_of(rng, 30))
    assert more[0] == index.next_row_id
    live = np.ones(230, bool)
    live[40:80] = False
    d2, i2 = loaded.query(jnp.asarray(Q), top_k=loaded.n_live)
    assert i2.shape[1] == live.sum() + 30


def test_full_operation_sequence_matches_dense(rng, tmp_path):
    """The acceptance property over a mixed op sequence."""
    X = np.asarray(rows_of(rng, 300))
    Q = np.asarray(rows_of(rng, 4))
    live = np.zeros(300, bool)
    index = make_index(capacity=64)
    ids0 = index.ingest(jnp.asarray(X[:150]))
    live[:150] = True
    index.delete(ids0[20:70])
    live[20:70] = False
    index.compact(min_live_frac=0.9)
    ids1 = index.ingest(jnp.asarray(X[150:280]))
    live[150:280] = True
    index.delete(ids1[:30])
    live[150:180] = False
    index.save(str(tmp_path / "seq"))
    index = SketchIndex.load(str(tmp_path / "seq"))
    index.ingest(jnp.asarray(X[280:]))
    live[280:] = True
    index.seal_active()
    index.compact(min_live_frac=0.6)
    assert_matches_dense(index, X, live, Q, top_k=11)


def test_mle_estimator_close_to_dense(rng):
    X = np.asarray(rows_of(rng, 120))
    Q = np.asarray(rows_of(rng, 4))
    index = make_index(capacity=50)
    index.ingest(jnp.asarray(X))
    d, ids = index.query(jnp.asarray(Q), top_k=5, estimator="mle")
    corpus = sketch(jnp.asarray(X), index.key, CFG)
    qs = sketch(jnp.asarray(Q), index.key, CFG)
    d_ref, i_ref = knn(qs, corpus, CFG, top_k=5, mle=True)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-4)


def test_ingest_fixed_batch_no_recompile(rng):
    index = make_index(capacity=2048)
    batch = rows_of(rng, 32)
    index.ingest(batch)  # warmup compiles sketch + writer for this shape
    writes = _write_rows._cache_size()
    for _ in range(5):
        index.ingest(rows_of(rng, 32))
    assert _write_rows._cache_size() == writes  # offset is traced, not baked
    assert index.active.size == 6 * 32


def test_threshold_query_matches_dense(rng):
    """Index threshold scan == engine threshold over the live corpus,
    pair-for-pair (same exact-invariant contract as top-k), and tombstoned
    rows can never hit (they are masked to +inf, not merely unlikely)."""
    from repro import engine

    X = np.asarray(rows_of(rng, 150))
    Q = np.asarray(rows_of(rng, 20))
    index = make_index(capacity=64)
    ids = index.ingest(jnp.asarray(X))
    index.delete(ids[:10])
    qr, qids = index.query_threshold(jnp.asarray(Q), radius=0.1, relative=True)
    live_ids = np.arange(10, 150)
    qsk = sketch(jnp.asarray(Q), index.key, CFG)
    live_sk = sketch(jnp.asarray(X[10:]), index.key, CFG)
    rr, cc = engine.pairwise(qsk, live_sk, CFG, reduce="threshold",
                             radius=0.1, relative=True)
    np.testing.assert_array_equal(qr, rr)
    np.testing.assert_array_equal(qids, live_ids[cc])
    assert not np.isin(qids, ids[:10]).any()


def test_micro_batcher_coalesces(rng):
    X = np.asarray(rows_of(rng, 200))
    Q = np.asarray(rows_of(rng, 16))
    index = make_index(capacity=100)
    index.ingest(jnp.asarray(X))
    d_ref, i_ref = index.query(jnp.asarray(Q), top_k=5)

    mb = MicroBatcher(index, max_batch=16, max_wait_ms=200.0)
    results = [None] * 16
    def worker(i):
        results[i] = mb.query(Q[i], top_k=5)
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, (d, ids) in enumerate(results):
        np.testing.assert_array_equal(np.asarray(d[0]), np.asarray(d_ref[i]))
        np.testing.assert_array_equal(ids[0], i_ref[i])
    assert mb.rows_served == 16
    assert mb.batches_run < 16  # coalesced, not one engine pass per caller


def test_micro_batcher_hands_out_host_rows(rng):
    """Each caller gets its rows of the batch as host numpy arrays (the
    fan hands back the host copy it collects), the same bits as the direct
    query of the same batch."""
    X = np.asarray(rows_of(rng, 120))
    index = make_index(capacity=50)
    index.ingest(jnp.asarray(X))
    d_ref, i_ref = index.query(jnp.asarray(X[:2]), top_k=4)
    mb = MicroBatcher(index, max_batch=2, max_wait_ms=60_000.0)
    results = {}
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(i, mb.query(X[i], top_k=4)))
        for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert mb.batches_run == 1
    for i, (d, ids) in results.items():
        assert type(d) is np.ndarray and d.dtype == np.float32
        np.testing.assert_array_equal(d[0], np.asarray(d_ref[i]))
        np.testing.assert_array_equal(ids[0], i_ref[i])


def test_micro_batcher_timeout_flush(rng):
    X = np.asarray(rows_of(rng, 100))
    index = make_index(capacity=100)
    index.ingest(jnp.asarray(X))
    mb = MicroBatcher(index, max_batch=64, max_wait_ms=10.0)
    d, ids = mb.query(X[3], top_k=4)  # lone caller: flushed by timeout
    d_ref, i_ref = index.query(jnp.asarray(X[3:4]), top_k=4)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_ref))
    np.testing.assert_array_equal(ids, i_ref)
    assert mb.batches_run == 1


def test_reservoir_ring_eviction():
    res = SketchReservoir(CFG, capacity=8)
    key = jax.random.key(0)
    X = jax.random.uniform(jax.random.key(1), (20, D))
    sk = sketch(X, key, CFG)

    res.admit(LpSketch(U=sk.U[:5], moments=sk.moments[:5]))
    assert res.size == 5
    view, live = res.view()
    assert live.sum() == 5
    np.testing.assert_array_equal(np.asarray(view.U[:5]), np.asarray(sk.U[:5]))

    res.admit(LpSketch(U=sk.U[5:11], moments=sk.moments[5:11]))  # wraps
    assert res.size == 8 and res.count == 11
    view, live = res.view()
    assert live.all()
    # slots 0..2 were overwritten by rows 8, 9, 10 (FIFO eviction)
    np.testing.assert_array_equal(np.asarray(view.U[0]), np.asarray(sk.U[8]))
    np.testing.assert_array_equal(np.asarray(view.U[3]), np.asarray(sk.U[3]))

    # a batch larger than capacity keeps only its newest rows
    res.admit(LpSketch(U=sk.U[:20], moments=sk.moments[:20]))
    assert res.size == 8 and res.count == 31
    view, _ = res.view()
    got = {bytes(np.asarray(u).tobytes()) for u in view.U}
    want = {bytes(np.asarray(u).tobytes()) for u in sk.U[12:20]}
    assert got == want


def test_empty_and_edge_cases(rng):
    index = make_index(capacity=10)
    d, ids = index.query(rows_of(rng, 2), top_k=3)
    assert d.shape == (2, 0) and ids.shape == (2, 0)
    rid = index.ingest(rows_of(rng, 1))
    d, ids = index.query(rows_of(rng, 2), top_k=5)
    assert ids.shape == (2, 1) and (ids == rid[0]).all()
    index.delete(rid)
    d, ids = index.query(rows_of(rng, 2), top_k=5)
    assert ids.shape == (2, 0)


def test_one_row_save_load_bit_for_bit(rng, tmp_path):
    """A 1-row index must reload onto a padded (>= 2 row) segment: an
    unpadded width-1 strip lowers as a GEMV whose K-accumulation order
    differs from the GEMM every other path uses, breaking bit-equality."""
    Q = np.asarray(rows_of(rng, 3))
    index = make_index(capacity=10)
    index.ingest(rows_of(rng, 1))
    d0, i0 = index.query(jnp.asarray(Q), top_k=1)
    index.save(str(tmp_path / "one"))
    loaded = SketchIndex.load(str(tmp_path / "one"))
    assert loaded.sealed[0].n >= 2  # padded, dead-masked
    d1, i1 = loaded.query(jnp.asarray(Q), top_k=1)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(i0, i1)


def test_empty_index_save_load_round_trip(rng, tmp_path):
    """An index whose active segment has 0 written rows — fresh, or drained
    by deletes — must save → load → query to identical (empty) results on
    both index classes, not shape-error through the trimmed-segment /
    ``_pad_rows`` / ``_MIN_SEGMENT_ROWS`` path."""
    import jax

    from repro.index import ShardedSketchIndex

    Q = rows_of(rng, 3)

    # 0 rows ever written: no segment files at all in the save
    fresh = make_index(capacity=10)
    fresh.save(str(tmp_path / "fresh"))
    for loaded in (SketchIndex.load(str(tmp_path / "fresh")),
                   ShardedSketchIndex.load(str(tmp_path / "fresh"),
                                           devices=jax.devices())):
        d, ids = loaded.query(Q, top_k=5)
        assert d.shape == (3, 0) and ids.shape == (3, 0)
        qr, qi = loaded.query_threshold(Q, radius=0.5)
        assert qr.size == 0 and qi.size == 0
        # the restored index keeps serving
        rid = loaded.ingest(rows_of(rng, 2))
        _, ids = loaded.query(Q, top_k=5)
        assert set(ids.ravel()) == set(rid)

    # rows written then all tombstoned: live bitmaps all-False round-trip
    drained = make_index(capacity=10)
    rid = drained.ingest(rows_of(rng, 25))
    drained.delete(rid)
    drained.save(str(tmp_path / "drained"))
    loaded = SketchIndex.load(str(tmp_path / "drained"))
    assert loaded.n_live == 0
    assert loaded.next_row_id == drained.next_row_id
    d, ids = loaded.query(Q, top_k=5)
    assert d.shape == (3, 0) and ids.shape == (3, 0)
    qr, qi = loaded.query_threshold(Q, radius=0.5)
    assert qr.size == 0 and qi.size == 0


def test_micro_batcher_empty_batch_returns_early(rng):
    """A 0-row query batch answers immediately with empty shapes — it must
    not join a batch or push a degenerate 0-row strip through the engine."""
    X = np.asarray(rows_of(rng, 40))
    index = make_index(capacity=40)
    index.ingest(jnp.asarray(X))
    mb = MicroBatcher(index, max_batch=8, max_wait_ms=60_000.0)
    d, ids = mb.query(np.zeros((0, D), np.float32), top_k=5)
    assert d.shape == (0, 5) and ids.shape == (0, 5)
    assert ids.dtype == np.int64
    assert mb.batches_run == 0 and mb.rows_served == 0
    assert not mb._groups  # nothing enqueued, nothing left hanging
    # k still caps at the live count, mirroring index.query
    index.delete(index.query(jnp.asarray(X[:1]), top_k=40)[1][0, 3:])
    d, ids = mb.query(np.zeros((0, D), np.float32), top_k=5)
    assert d.shape == (0, 3) and ids.shape == (0, 3)


def test_background_compaction_replays_concurrent_deletes(rng):
    """Deletes that land while replacement segments are being built must be
    replayed at swap time: the driver walks the plan/build/swap steps by
    hand with a delete injected between snapshot and swap."""
    X = np.asarray(rows_of(rng, 200))
    Q = np.asarray(rows_of(rng, 4))
    index = make_index(capacity=50)
    ids = index.ingest(jnp.asarray(X))
    index.delete(ids[:30])  # segment 0 at 20/50 live: due for compaction

    plan = index._compaction_plan(0.5)
    assert len(plan) == 1
    seg, snap = plan[0]
    built = [(seg, snap, seg.compacted(live=snap))]
    # a delete lands after the snapshot, touching rows the replacement kept
    index.delete(ids[30:40])
    gen0 = index.generation
    assert index._swap_compacted(built) == 1
    assert index.generation == gen0 + 1

    live = np.ones(200, bool)
    live[:40] = False
    assert index.n_live == live.sum()
    assert_matches_dense(index, X, live, Q)
    _, got = index.query(jnp.asarray(Q), top_k=60)
    assert not np.isin(got, ids[:40]).any()


def test_compact_async_matches_blocking_compact(rng):
    """compact_async == compact: same rewrite count, bit-identical queries,
    one generation flip, and the handle is reusable/joinable twice."""
    X = np.asarray(rows_of(rng, 300))
    Q = np.asarray(rows_of(rng, 5))
    a, b = make_index(capacity=64), make_index(capacity=64)
    ids_a, ids_b = a.ingest(jnp.asarray(X)), b.ingest(jnp.asarray(X))
    a.delete(ids_a[10:100]); b.delete(ids_b[10:100])
    n_sync = a.compact(min_live_frac=0.6)
    h = b.compact_async(min_live_frac=0.6)
    assert h.join() == n_sync > 0
    assert h.join() == n_sync  # idempotent join
    assert h.done and b.stats()["compacting"] is False
    da, ia = a.query(jnp.asarray(Q), top_k=9)
    db, ib = b.query(jnp.asarray(Q), top_k=9)
    np.testing.assert_array_equal(np.asarray(da), np.asarray(db))
    np.testing.assert_array_equal(ia, ib)


def test_compaction_swap_skips_already_rewritten_segments(rng):
    """A stale build (its original already swapped out by a racing compact)
    must be skipped, not spliced over the newer segment list."""
    X = np.asarray(rows_of(rng, 100))
    index = make_index(capacity=50)
    ids = index.ingest(jnp.asarray(X))
    index.delete(ids[:30])
    plan = index._compaction_plan(0.5)
    built = [(seg, snap, seg.compacted(live=snap)) for seg, snap in plan]
    assert index.compact(min_live_frac=0.5) == 1  # the racing winner
    gen = index.generation
    assert index._swap_compacted(built) == 0  # stale: nothing to do
    assert index.generation == gen + 1  # flip still recorded
    live = np.ones(100, bool)
    live[:30] = False
    assert_matches_dense(index, X, live, np.asarray(rows_of(rng, 3)))


def test_micro_batcher_flush_survives_errors(rng):
    X = np.asarray(rows_of(rng, 50))
    index = make_index(capacity=50)
    index.ingest(jnp.asarray(X))
    mb = MicroBatcher(index, max_batch=64, max_wait_ms=60_000.0)

    results, errors = {}, {}
    def worker(i, estimator):
        try:
            results[i] = mb.query(X[i], top_k=2, estimator=estimator)
        except Exception as e:
            errors[i] = e
    # one poisoned group (bad estimator) + one good group, both pending
    threads = [threading.Thread(target=worker, args=(0, "bogus")),
               threading.Thread(target=worker, args=(1, "plain"))]
    for t in threads:
        t.start()
    while mb._groups.get((2, "plain", None)) is None or \
            mb._groups.get((2, "bogus", None)) is None:
        pass  # wait until both requests joined their groups
    mb.flush()  # must run the good batch despite the poisoned one
    for t in threads:
        t.join()
    assert isinstance(errors[0], ValueError)
    d_ref, i_ref = index.query(jnp.asarray(X[1:2]), top_k=2)
    np.testing.assert_array_equal(results[1][1], i_ref)


def test_top_k_validation_is_friendly(rng):
    """Malformed top_k fails with a contract error naming top_k, never a
    shape crash deep in the strip fan; over-asking is NOT an error."""
    X = np.asarray(rows_of(rng, 40))
    index = make_index(capacity=16)
    index.ingest(jnp.asarray(X))
    Q = jnp.asarray(X[:2])
    with pytest.raises(ValueError, match="top_k"):
        index.query(Q, top_k=-1)
    with pytest.raises(ValueError, match="top_k"):
        index.query(Q, top_k=2.5)
    d, ids = index.query(Q, top_k=0)  # explicit empty ask stays empty
    assert d.shape == (2, 0) and ids.shape == (2, 0)
    d, ids = index.query(Q, top_k=10_000)  # over-ask truncates to live
    assert d.shape == (2, 40) and ids.shape == (2, 40)


def test_micro_batcher_rejects_bad_top_k_without_poisoning(rng):
    """A caller's bad top_k raises before it joins a batch, so concurrent
    well-formed requests in other groups are unaffected."""
    X = np.asarray(rows_of(rng, 30))
    index = make_index(capacity=30)
    index.ingest(jnp.asarray(X))
    mb = MicroBatcher(index, max_batch=4, max_wait_ms=50.0)
    with pytest.raises(ValueError, match="top_k"):
        mb.query(X[0], top_k=-3)
    assert not mb._groups  # nothing enqueued
    d, ids = mb.query(X[0], top_k=5)
    d_ref, i_ref = index.query(jnp.asarray(X[:1]), top_k=5)
    np.testing.assert_array_equal(ids, i_ref)


def test_micro_batcher_over_ask_on_padded_sharded_index(rng):
    """MicroBatcher over a sharded index whose only corpus is a heavily
    tombstoned (padding-heavy after compaction) segment set: top_k beyond
    the live count returns min(top_k, live) columns from every path."""
    from repro.index import ShardedSketchIndex
    from repro.launch.mesh import make_serving_mesh

    X = np.asarray(rows_of(rng, 60))
    sh = ShardedSketchIndex(CFG, seed=7,
                            index_cfg=IndexConfig(segment_capacity=16),
                            mesh=make_serving_mesh(1))
    ids = sh.ingest(jnp.asarray(X))
    sh.delete(ids[:55])
    sh.compact(min_live_frac=0.9)  # padded stacked blocks everywhere
    mb = MicroBatcher(sh, max_batch=4, max_wait_ms=20.0)
    d, got = mb.query(X[:2], top_k=40)
    assert d.shape == (2, 5) and got.shape == (2, 5)
    assert not np.isin(got, ids[:55]).any()
    d_ref, i_ref = sh.query(jnp.asarray(X[:2]), top_k=40)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_ref))
    np.testing.assert_array_equal(got, i_ref)


def test_finite_k_clamps_racing_deletes():
    """A delete racing a query can leave fewer finite candidates than the
    live-count snapshot promised; the fan clamps instead of surfacing dead
    rows / sentinel positions (unit check of the shared clamp)."""
    from repro.index.query import _finite_k

    vals = np.array([[1.0, 2.0, np.inf, np.inf],
                     [0.5, np.inf, np.inf, np.inf]], np.float32)
    assert _finite_k(vals, 3) == 1  # worst row has one finite candidate
    assert _finite_k(vals, 1) == 1
    assert _finite_k(np.zeros((0, 4), np.float32), 3) == 3  # no query rows
    full = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    assert _finite_k(full, 2) == 2  # no clamp when the promise holds


def test_sharded_stack_cache_dropped_on_swap(rng):
    """Compaction swaps must release the stacked stage-1 operands (and the
    swapped-out segments they pin) immediately, not at the next query."""
    from repro.index import ShardedSketchIndex
    from repro.launch.mesh import make_serving_mesh

    X = np.asarray(rows_of(rng, 96))
    sh = ShardedSketchIndex(CFG, seed=7,
                            index_cfg=IndexConfig(segment_capacity=32),
                            mesh=make_serving_mesh(1))
    ids = sh.ingest(jnp.asarray(X))
    sh.query(jnp.asarray(X[:2]), top_k=3)  # builds the stack cache
    assert sh._stack is not None
    sh.delete(ids[:30])
    sh.compact(min_live_frac=0.5)
    assert sh._stack is None  # dropped at the swap, under the lock
    d, got = sh.query(jnp.asarray(X[:2]), top_k=3)  # rebuilds cleanly
    assert sh._stack is not None and got.shape == (2, 3)
