"""The segment fan's compiled per-segment fold.

``_fold_segment_topk`` folds a segment's full-width strips in one compiled
loop (the engine's ``scan_topk``) and its one narrower (or absorbed wider)
strip after it.  It must give bit-for-bit the ``(vals, idx)`` of the plain
per-strip loop, for every estimator, strip plan and merge grouping, and
compile once per shape whatever the segment's ``base``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import LpSketch, SketchConfig, registry, sketch
from repro.core.pairwise import pack_sketch
from repro.engine import EngineConfig, strip_distances
from repro.engine.reduce import merge_group, merge_topk, scan_topk, strip_bounds
from repro.index import IndexConfig, SketchIndex
from repro.index.query import _fold_segment_topk, _fold_strips, fan_topk
from repro.index.segment import SealedSegment

CFG = SketchConfig(p=4, k=16, block_d=32)
D = 64
COL_BLOCK = 16
SENTINEL = np.iinfo(np.int32).max

ESTIMATORS = [
    pytest.param(registry.PLAIN, "xla", id="plain-xla"),
    pytest.param(registry.PLAIN, "interpret", id="plain-interpret"),
    pytest.param(registry.MARGIN_MLE, "xla", id="mle"),
]
SIZES = [
    pytest.param(3 * COL_BLOCK, id="exact"),
    pytest.param(3 * COL_BLOCK + 5, id="remainder"),
    pytest.param(2 * COL_BLOCK + 1, id="absorbed-tail"),
]


def _strip_loop(vals, idx, qsk, seg, spec, backend, base, k):
    """The plain per-strip fold, one eager dispatch per strip op."""
    n = seg.n
    mask = seg.mask()
    if spec.uses_packed:
        Aq, _, nq = pack_sketch(qsk, CFG)
        B, nb = seg.packed(CFG)
    c = min(k, n)
    for c0, c1 in strip_bounds(n, COL_BLOCK):
        if spec.uses_packed:
            Dm = strip_distances(Aq, B[c0:c1], nq, nb[c0:c1],
                                 backend=backend, clip=True)
        else:
            Dm = spec.pairwise(
                qsk, LpSketch(U=seg.sketch.U[c0:c1],
                              moments=seg.sketch.moments[c0:c1]),
                CFG, clip=True)
        Dm = jnp.where(mask[c0:c1][None, :], Dm, jnp.inf)
        neg, j = jax.lax.top_k(-Dm, min(c, c1 - c0))
        vals, idx = merge_topk(vals, idx, -neg,
                               (j + (base + c0)).astype(jnp.int32), k)
    return vals, idx


def _sketches(n, q, seed):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, (n, D)).astype(np.float32)
    # duplicated corpus rows give exact ties inside and across strips
    X[n // 2:n // 2 + 3] = X[:3]
    Q = np.concatenate([X[:2], rng.uniform(0, 1, (q - 2, D))]).astype(
        np.float32)
    key = jax.random.key(seed)
    return sketch(jnp.asarray(Q), key, CFG), sketch(jnp.asarray(X), key, CFG)


@pytest.mark.parametrize("case", ["tombstones", "base", "grouped"])
@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("estimator,backend", ESTIMATORS)
def test_compiled_fold_matches_the_strip_loop(estimator, backend, n, case):
    spec = registry.resolve(estimator, p=CFG.p,
                            projection=CFG.projection.family)
    q = 5
    qsk, seg_sk = _sketches(n, q, seed=n)
    live = np.ones(n, bool)
    if case == "tombstones":
        # dead rows in every strip, the first and last columns among them;
        # k above the strip width: a merge after every strip
        live[::3] = False
        live[-1] = False
        k, base = COL_BLOCK + 4, 0
    elif case == "base":
        k, base = 7, n
    else:
        # several strips' candidates merged at once
        k, base = 3, 0
    seg = SealedSegment(seg_sk, np.arange(n), live)
    vals = jnp.full((q, k), jnp.inf, jnp.float32)
    idx = jnp.full((q, k), SENTINEL, jnp.int32)
    if case == "base":
        # a running list already holding the same segment at base 0: every
        # candidate of the fold at ``base`` ties one in the list
        vals, idx = _strip_loop(vals, idx, qsk, seg, spec, backend, 0, k)
    q_packed = None
    if spec.uses_packed:
        Aq, _, nq = pack_sketch(qsk, CFG)
        q_packed = (Aq, nq)

    want = _strip_loop(vals, idx, qsk, seg, spec, backend, base, k)
    got = _fold_segment_topk(vals, idx, qsk, q_packed, seg, CFG, spec,
                             backend, COL_BLOCK, base, k)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert np.isfinite(np.asarray(got[0])).all()


def test_equal_segments_share_one_compiled_fold():
    obs.enable()
    roots = []
    obs.trace.add_sink(roots.append)
    try:
        capacity = 4 * COL_BLOCK
        index = SketchIndex(CFG, seed=3,
                            index_cfg=IndexConfig(segment_capacity=capacity),
                            engine=EngineConfig(backend="xla",
                                                col_block=COL_BLOCK))
        rng = np.random.default_rng(3)
        index.ingest(jnp.asarray(
            rng.uniform(0, 1, (3 * capacity, D)).astype(np.float32)))
        segments = index._segments()
        assert len(segments) >= 3
        qsk = sketch(jnp.asarray(rng.uniform(0, 1, (3, D)).astype(np.float32)),
                     index.key, CFG)
        before = _fold_strips._cache_size()
        first = fan_topk(qsk, segments, CFG, top_k=9, engine=index.engine)
        assert _fold_strips._cache_size() == before + 1
        # a second fan, its segments at other bases, compiles nothing new
        second = fan_topk(qsk, segments[1:], CFG, top_k=9,
                          engine=index.engine)
        assert _fold_strips._cache_size() == before + 1
    finally:
        obs.trace.remove_sink(roots.append)
        obs.disable()
    assert np.isfinite(np.asarray(first[0])).all()
    assert np.isfinite(np.asarray(second[0])).all()
    stage1 = [sp for r in roots for sp in r.find("index.fan.stage1")]
    assert [sp.attrs["fold_programs"] for sp in stage1] == [
        len(segments), len(segments) - 1]
    assert [sp.attrs["eager_strips"] for sp in stage1] == [0, 0]
    assert [sp.attrs["strips"] for sp in stage1] == [
        4 * len(segments), 4 * (len(segments) - 1)]


@pytest.mark.parametrize("n_strips,c,group", [
    (6, 3, 3),     # two merges of three strips
    (8, 2, 8),     # one merge of every strip
    (5, 4, 1),     # a prime strip count: a merge after every strip
    (4, 16, 1),    # c = width: a merge after every strip
])
def test_scan_topk_merges_groups_like_a_merge_per_strip(n_strips, c, group):
    width, rows, k = 16, 4, max(c, 5)
    assert merge_group(n_strips, c, width) == group
    rng = np.random.default_rng(n_strips * 100 + c)
    # few distinct values: ties inside strips, across strips and with the
    # running list
    Ds = jnp.asarray(rng.integers(0, 6, (n_strips, rows, width)).astype(
        np.float32))
    lives = jnp.asarray(rng.random((n_strips, width)) > 0.2)
    vals0 = jnp.asarray(np.sort(rng.integers(0, 6, (rows, k)), axis=1).astype(
        np.float32))
    idx0 = jnp.asarray(np.tile(np.arange(k, dtype=np.int32), (rows, 1)))
    offset = k

    want = (vals0, idx0)
    for i in range(n_strips):
        Dm = jnp.where(lives[i][None, :], Ds[i], jnp.inf)
        neg, j = jax.lax.top_k(-Dm, c)
        want = merge_topk(*want, -neg,
                          (j + offset + i * width).astype(jnp.int32), k)

    @jax.jit
    def fold(vals, idx, Ds, lives):
        return scan_topk(
            lambda i: (Ds[i], lives[i],
                       lambda j: (j + offset + i * width).astype(jnp.int32)),
            n_strips, (vals, idx), width=width, c=c, k=k)

    got = fold(vals0, idx0, Ds, lives)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
