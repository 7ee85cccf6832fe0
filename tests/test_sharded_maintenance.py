"""Sharded-index maintenance invariants: the stacked-operand cache under
segment churn (uid keys, never ``id()``), device-side live-mask refresh on
tombstone deltas, and skew-aware segment rebalancing with its policy trigger.

The serving contract under test is always the same: maintenance moves bits —
stacks repack, masks scatter, segments migrate — but query answers stay
bit-identical to the single-host index over the same live rows.
"""

import gc
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import LpSketch, SketchConfig
from repro.index import (
    IndexConfig,
    RebalancePolicy,
    ShardedSketchIndex,
    SketchIndex,
)
from repro.index.segment import _TOMBSTONE_LOG_MAX, SealedSegment
from repro.launch.mesh import make_serving_mesh

CFG = SketchConfig(p=4, k=32, block_d=64)
D = 256


def _pair(rng, n=200, capacity=32, seed=3):
    X = rng.uniform(0, 1, (n, D)).astype(np.float32)
    icfg = IndexConfig(segment_capacity=capacity)
    ref = SketchIndex(CFG, seed=seed, index_cfg=icfg)
    sh = ShardedSketchIndex(CFG, seed=seed, index_cfg=icfg,
                            mesh=make_serving_mesh(1))
    ids_r = ref.ingest(jnp.asarray(X))
    ids_s = sh.ingest(jnp.asarray(X))
    np.testing.assert_array_equal(ids_r, ids_s)
    return ref, sh, X, ids_r


def _check(ref, sh, Q, tag, top_k=9, radius=0.12):
    d0, i0 = ref.query(Q, top_k=top_k)
    d1, i1 = sh.query(Q, top_k=top_k)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1), err_msg=tag)
    np.testing.assert_array_equal(i0, i1, err_msg=tag)
    r0, c0 = ref.query_threshold(Q, radius=radius, relative=True)
    r1, c1 = sh.query_threshold(Q, radius=radius, relative=True)
    np.testing.assert_array_equal(r0, r1, err_msg=tag)
    np.testing.assert_array_equal(c0, c1, err_msg=tag)


@pytest.fixture
def rng():
    return np.random.default_rng(17)


def _tiny_sealed(n=4):
    U = jnp.zeros((n, CFG.vectors_per_row, CFG.k), CFG.projection.dtype)
    M = jnp.zeros((n, CFG.p - 1), jnp.float32)
    return SealedSegment(LpSketch(U=U, moments=M),
                         np.arange(n, dtype=np.int64))


# ---------------------------------------------------------------- uid keys


def test_segment_uids_are_monotonic_across_id_reuse():
    """``id()`` of a freed segment is routinely handed to the next one — the
    collision that poisoned the old stacked-operand cache key.  ``uid`` is
    process-monotonic: fresh segments never repeat one, reused id or not."""
    seen_uids = []
    seen_ids = set()
    id_reused = False
    for _ in range(50):
        seg = _tiny_sealed()
        seen_uids.append(seg.uid)
        id_reused = id_reused or id(seg) in seen_ids
        seen_ids.add(id(seg))
        del seg
        gc.collect()
    assert sorted(set(seen_uids)) == seen_uids, "uids must never repeat"
    # CPython reliably reuses the freed allocation for same-shaped objects —
    # this is the premise of the regression, so record that it really happens
    assert id_reused, "expected CPython to reuse a freed segment id"


def test_stacked_cache_rebuilds_on_compaction_swap(rng):
    """Build → compact → query must serve stacks packed from the replacement
    segments: the cache key (segment uids) changes across the swap even
    though CPython may hand the replacements the dropped segments' ids."""
    ref, sh, X, ids = _pair(rng)
    Q = jnp.asarray(X[:4])
    _check(ref, sh, Q, "before compact")
    st_before = sh._stack
    assert st_before is not None
    key_before = st_before.key

    ref.delete(ids[10:120])
    sh.delete(ids[10:120])
    rewritten_uids = {seg.uid for seg in sh.sealed
                      if seg.live_fraction <= 0.9}
    assert rewritten_uids
    ref.compact(min_live_frac=0.9)
    sh.compact(min_live_frac=0.9)
    gc.collect()  # free the swapped-out segments: ids become reusable NOW

    _check(ref, sh, Q, "after compact")
    st_after = sh._stack
    assert st_after is not None and st_after is not st_before
    assert st_after.key != key_before
    # replacements carry fresh uids, so no stale-key match is possible
    assert {seg.uid for seg in sh.sealed}.isdisjoint(rewritten_uids)


def test_stacked_cache_key_never_uses_object_ids(rng):
    """The regression shape itself: craft a stale stack whose key is built
    from the CURRENT segments' ``id()``s — exactly what a freed-then-reused
    id would produce under the old keying — and assert the fan refuses it."""
    _ref, sh, X, _ids = _pair(rng, n=100)
    Q = jnp.asarray(X[:3])
    sh.query(Q, top_k=5)
    st = sh._stack
    assert st is not None
    st.key = (st.col_block,) + tuple(
        id(seg) for _s, g in st.groups for _b, seg in g)
    sh.query(Q, top_k=5)
    assert sh._stack is not st, "id()-shaped key must never match again"


# ------------------------------------------------- device-side mask refresh


def test_mask_refresh_is_device_side_scatter(rng):
    """Tombstone deltas scatter into the resident device mask (one full host
    build at stack creation, then O(deletes) updates), and every refreshed
    mask answers bit-identically to the single host."""
    ref, sh, X, ids = _pair(rng)
    Q = jnp.asarray(X[:4])
    _check(ref, sh, Q, "initial")
    st = sh._stack
    assert (st.mask_full_builds, st.mask_scatter_updates) == (1, 0)

    for round_, sl in enumerate([slice(5, 40), slice(60, 61),
                                 slice(100, 140)]):
        ref.delete(ids[sl])
        sh.delete(ids[sl])
        _check(ref, sh, Q, f"after delete round {round_}")
        assert sh._stack is st, "factor stacks must survive deletes"
        assert st.mask_full_builds == 1
        assert st.mask_scatter_updates == round_ + 1


def test_mask_refresh_falls_back_when_log_trimmed(rng):
    """A segment whose tombstone delta log was trimmed past the cached
    version forces one full rebuild — correctness never depends on the log."""
    ref, sh, X, ids = _pair(rng, capacity=100)
    Q = jnp.asarray(X[:4])
    _check(ref, sh, Q, "initial")
    st = sh._stack
    assert st.mask_full_builds == 1
    # overflow segment 0's delta log one tombstone at a time (no query in
    # between, so the cached mask version falls behind the trimmed floor)
    for k in range(_TOMBSTONE_LOG_MAX + 5):
        ref.delete(ids[k])
        sh.delete(ids[k])
    _check(ref, sh, Q, "after log overflow")
    assert sh._stack is st
    assert st.mask_full_builds == 2  # the fallback, exactly once
    assert st.mask_scatter_updates == 0


def test_bulk_delete_is_one_log_entry_per_segment(rng):
    """A single ``delete()`` batch larger than the delta-log cap must stay
    ONE log entry per segment — per-row entries would overflow the log and
    silently disable the device-side scatter for exactly the heavy-delete
    traffic it was built for."""
    ref, sh, X, ids = _pair(rng, capacity=100)
    Q = jnp.asarray(X[:4])
    _check(ref, sh, Q, "initial")
    st = sh._stack
    big = ids[: _TOMBSTONE_LOG_MAX + 10]  # all land in segment 0
    ref.delete(big)
    sh.delete(big)
    seg0 = sh.sealed[0]
    assert seg0.live_version == 1
    assert len(seg0._tombstone_log) == 1
    _check(ref, sh, Q, "after bulk delete")
    assert st.mask_full_builds == 1 and st.mask_scatter_updates == 1


def test_delete_batch_counts_duplicates_once(rng):
    ref, sh, X, ids = _pair(rng, n=60, capacity=100)
    dup = np.concatenate([ids[:5], ids[:5]])
    assert ref.delete(dup) == 5
    assert sh.delete(dup) == 5
    assert ref.n_live == sh.n_live == 55


def test_tombstones_since_contract():
    seg = _tiny_sealed(8)
    assert seg.tombstones_since(0).size == 0
    seg.delete_local(np.array([1, 2]))
    seg.delete_local(3)
    np.testing.assert_array_equal(seg.tombstones_since(0), [1, 2, 3])
    np.testing.assert_array_equal(seg.tombstones_since(1), [3])
    assert seg.tombstones_since(seg.live_version).size == 0
    # trim the log: deltas older than the floor are unreconstructible
    for k in range(_TOMBSTONE_LOG_MAX + 1):
        seg.delete_local(4 + (k % 4))
    assert seg.tombstones_since(0) is None
    assert seg.tombstones_since(seg.live_version - 1) is not None


def test_compaction_replay_keeps_mask_caches_consistent(rng):
    """Deletes that land while a replacement builds are replayed through
    ``delete_local`` at swap time, so the replacement's delta log matches its
    ``live_version`` and later mask refreshes stay incremental AND correct."""
    ref, sh, X, ids = _pair(rng)
    Q = jnp.asarray(X[:4])
    ref.delete(ids[0:80])
    sh.delete(ids[0:80])
    # mirror compact()'s internals so deletes land between snapshot and swap
    plan = sh._compaction_plan(0.9)
    assert plan
    built = [(seg, snap, sh._build_replacement(seg, snap))
             for seg, snap in plan]
    ref.compact(min_live_frac=0.9)
    ref.delete(ids[85:90])
    sh.delete(ids[85:90])  # lands on a planned original, post-snapshot
    sh._swap_compacted(built)
    _check(ref, sh, Q, "after replayed swap")
    # a replacement that received replayed tombstones still has the complete
    # delta log the device-side mask refresh depends on
    replayed = [seg for seg in sh.sealed if seg.live_version > 0]
    assert replayed
    for seg in replayed:
        assert seg.tombstones_since(0) is not None
    # and the refresh after the swap stays incremental on the fresh stack
    st = sh._stack
    ref.delete(ids[150])
    sh.delete(ids[150])
    _check(ref, sh, Q, "post-swap delete")
    assert sh._stack is st
    assert st.mask_scatter_updates == 1


# ------------------------------------------------------------- rebalancing


def test_rebalance_levels_skew_and_keeps_answers(rng):
    """Greedy bin-pack on live rows levels max/mean stacked height; answers
    stay bit-identical through the migration (placement moves bits only).

    Multi-shard placement is modeled with shard *tags* over a repeated
    device list (the planner runs on tags and row counts; real multi-device
    migration runs in the nightly subprocess lifecycle)."""
    ref, sh, X, ids = _pair(rng, n=512, capacity=64, seed=7)
    Q = jnp.asarray(X[:5])
    kill = np.concatenate([np.arange(64, 256), np.arange(320, 512)])
    kill = np.setdiff1d(kill, kill[::16])
    ref.delete(ids[kill])
    sh.delete(ids[kill])
    ref.compact(min_live_frac=0.9)
    sh.compact(min_live_frac=0.9)
    _check(ref, sh, Q, "pre-rebalance")

    sh.devices = sh.devices * 4
    sh._fan_mesh = None  # tags no longer match a mesh: dispatch fan
    for seg in sh.sealed:
        seg.shard = 0  # pile everything on one shard: max/mean == 4
    assert sh.stats()["shard_skew"] == 4.0
    gen = sh.generation
    moved = sh.rebalance(skew_trigger=1.2)
    assert moved > 0
    assert sh.generation == gen + 1
    assert sh.stats()["shard_skew"] < 4.0
    _check(ref, sh, Q, "post-rebalance")
    # below trigger: a huge trigger declines without touching placement
    gen = sh.generation
    assert sh.rebalance(skew_trigger=1e9) == 0
    assert sh.generation == gen


def test_rebalance_declines_no_progress_plans(rng):
    """Live counts and physical rows diverge on un-compacted tombstones; a
    live-row plan that would not improve the PHYSICAL height skew (what pads
    the stacked blocks) must not run — a no-progress migration flips the
    generation and rebuilds every stack for nothing, repeatedly under an
    auto policy."""
    ref, sh, X, ids = _pair(rng, n=256, capacity=64)
    # 4 segments; tombstone most of segments 0-2 WITHOUT compacting: physical
    # heights stay 64 each, live counts become [4, 4, 4, 64]
    kill = np.setdiff1d(np.arange(192), np.arange(192)[::16])
    ref.delete(ids[kill])
    sh.delete(ids[kill])
    sh.devices = sh.devices * 4
    sh._fan_mesh = None
    for i, seg in enumerate(sh.sealed):
        seg.shard = i % 4  # physically balanced: 64 rows per shard
    assert sh.stats()["shard_skew"] == 1.0
    gen = sh.generation
    # force=True skips the trigger but NOT the no-progress guard: any
    # migration from here can only hold or worsen physical skew
    assert sh.rebalance(force=True) == 0
    assert sh.generation == gen
    _check(ref, sh, jnp.asarray(X[:4]), "after declined rebalance")


def test_rebalance_skew_math():
    assert ShardedSketchIndex._shard_skew([0, 0, 0, 0]) == 1.0
    assert ShardedSketchIndex._shard_skew([64, 0, 0, 0]) == 4.0
    assert ShardedSketchIndex._shard_skew([32, 32]) == 1.0


def test_rebalance_policy_trigger_and_rate_limit(rng):
    clock = [0.0]
    pol = RebalancePolicy(skew_trigger=1.2, min_interval_s=30.0, auto=False,
                          clock=lambda: clock[0])
    X = rng.uniform(0, 1, (64, D)).astype(np.float32)
    sh = ShardedSketchIndex(CFG, seed=1,
                            index_cfg=IndexConfig(segment_capacity=16),
                            mesh=make_serving_mesh(1), rebalance_policy=pol)
    sh.ingest(jnp.asarray(X))
    # a 1-wide mesh is never skewed: the policy declines on skew — and a
    # declined check must NOT arm the rate limiter
    assert sh.maybe_rebalance() == 0
    assert sh.auto_rebalances == 0
    # craft skew with tags (planner-level, as above)
    sh.devices = sh.devices * 2
    sh._fan_mesh = None
    for seg in sh.sealed:
        seg.shard = 0
    assert sh.maybe_rebalance() > 0, "clock never advanced: a declined check "\
        "must not have armed the limiter"
    assert sh.auto_rebalances == 1
    # rate limited now that a pass actually started
    for seg in sh.sealed:
        seg.shard = 0
    assert sh.maybe_rebalance() == 0
    clock[0] = 100.0  # window elapsed: the skewed fleet heals again
    assert sh.maybe_rebalance() > 0
    assert sh.auto_rebalances == 2


def test_rebalance_transfers_run_off_the_index_lock(rng):
    """The rebalance pass stages its ``device_put`` transfers with the index
    lock RELEASED (compact_async-style copy-then-flip): a query issued while
    a transfer is parked mid-flight must be served immediately, and the
    trace must show the transfer span outside the lock-held commit span."""
    ref, sh, X, ids = _pair(rng, n=256, capacity=64, seed=7)
    Q = jnp.asarray(X[:3])
    want_d, want_i = sh.query(Q, top_k=5)  # also warms compile caches
    sh.devices = sh.devices * 4
    sh._fan_mesh = None  # shard tags over a repeated device list (as above)
    for seg in sh.sealed:
        seg.shard = 0

    in_transfer = threading.Event()
    release = threading.Event()
    real = ShardedSketchIndex._transfer_sketch

    def parked_transfer(seg, shard):
        in_transfer.set()
        assert release.wait(10.0), "test deadlock: release never set"
        return real(sh, seg, shard)

    sh._transfer_sketch = parked_transfer
    roots = []
    obs.enable()
    obs.trace.add_sink(roots.append)
    moved = []
    try:
        t = threading.Thread(target=lambda: moved.append(
            sh.rebalance(force=True)))
        t.start()
        assert in_transfer.wait(10.0), "rebalance never reached a transfer"
        # the transfer is parked RIGHT NOW; a lock-holding pass would block
        # this query until release — it must answer while the copy is open
        d, i = sh.query(Q, top_k=5)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
        np.testing.assert_array_equal(i, want_i)
        release.set()
        t.join(10.0)
        assert not t.is_alive()
    finally:
        release.set()
        obs.trace.remove_sink(roots.append)
        obs.disable()
    assert moved == [3]  # 4 segments piled on shard 0: 3 migrate off it

    reb = [r for r in roots if r.name == "index.rebalance"]
    qry = [r for r in roots if r.name == "index.query"]
    assert len(reb) == 1 and len(qry) == 1
    transfer, = reb[0].find("index.rebalance.transfer")
    commit, = reb[0].find("index.rebalance.commit")
    # the commit (the only lock-held phase) starts after every transfer
    # ended, and no transfer span nests inside it
    assert commit.t0 >= transfer.t1
    assert not commit.find("index.rebalance.transfer")
    # the mid-pass query ran entirely INSIDE the transfer window: the span
    # overlap is the proof the lock was free while bits streamed
    during = qry[0]
    assert transfer.t0 <= during.t0 and during.t1 <= transfer.t1
    # answers unchanged by the migration (bits moved, never recomputed)
    d, i = sh.query(Q, top_k=5)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(want_d))
    np.testing.assert_array_equal(i, want_i)


def test_rebalance_policy_validation():
    with pytest.raises(ValueError):
        RebalancePolicy(skew_trigger=0.5)
    with pytest.raises(ValueError):
        RebalancePolicy(min_interval_s=-1)
    with pytest.raises(ValueError):
        ShardedSketchIndex(CFG, mesh=make_serving_mesh(1)).rebalance(
            skew_trigger=0.3)


# ------------------------------------------------------------- stage1 stats


def test_stage1_stats_per_estimator_and_last_mode(rng):
    """``stage1`` reports the mode PER estimator — mle always dispatches even
    when a stack exists — plus the mode the last query actually used."""
    ref, sh, X, _ids = _pair(rng, n=80)
    Q = jnp.asarray(X[:3])
    s = sh.stats()["stage1"]
    assert s == {"plain": "parallel", "mle": "dispatch", "last": None}

    sh.query(Q, top_k=5)
    assert sh.stats()["stage1"]["last"] == "parallel"
    sh.query(Q, top_k=5, estimator="mle")
    assert sh.stats()["stage1"]["last"] == "dispatch"
    sh.query_threshold(Q, radius=0.12, relative=True)
    assert sh.stats()["stage1"]["last"] == "parallel"
    sh.query_threshold(Q, radius=0.12, relative=True, estimator="mle")
    assert sh.stats()["stage1"]["last"] == "dispatch"

    # no mesh: every estimator dispatches, and the readings say so
    sh2 = ShardedSketchIndex(CFG, seed=1,
                             index_cfg=IndexConfig(segment_capacity=32),
                             devices=[sh.devices[0]] * 2)
    sh2.ingest(jnp.asarray(X))
    sh2.query(Q, top_k=5)
    s2 = sh2.stats()["stage1"]
    assert s2 == {"plain": "dispatch", "mle": "dispatch", "last": "dispatch"}


def test_declined_stacked_fan_is_counted_and_visible(rng):
    """A stacked fan that cannot be built is never silent: stats() names
    the reason and the process-wide counter moves."""
    from repro.obs.metrics import REGISTRY

    ok = ShardedSketchIndex(CFG, seed=1, mesh=make_serving_mesh(1))
    assert ok.stats()["stacked_fan_declined"] is None
    counter = REGISTRY.counter("index.stacked_fan_declined")
    before = counter.value
    dup = ShardedSketchIndex(CFG, seed=1, devices=[ok.devices[0]] * 2)
    assert "duplicate" in dup.stats()["stacked_fan_declined"]
    other = ShardedSketchIndex(CFG, seed=1, mesh=make_serving_mesh(1),
                               devices=ok.devices, data_axes="model")
    assert "no data axes" in other.stats()["stacked_fan_declined"]
    assert counter.value == before + 2
