"""Fractional-p conformance: α-stable sketches + the geometric-mean estimator.

Parity matrix for the registry's new (p, projection, estimator) scenarios
(p in {1.0, 1.5}, families ``stable`` / ``stable_sparse``, estimator ``gm``):

  * engine strips vs the dense ``pairwise_geometric_mean`` reference — bit
    identical for every reduce (the data fits one strip, so the engine's
    strip IS the dense call on the same operands);
  * the sparse ingest path (gather over (indices, values) pairs) vs the
    dense scatter-materialized tile — the same matrix by construction;
  * the fused kernel path (``sketch_via_kernel``) vs the streamed sketch;
  * the acceptance round-trip: a fractional-p corpus served through
    ``SketchIndex`` → ``ShardedSketchIndex`` (dispatch fan) → ``FrontDoor``
    returns bit-identical values and ids at every tier;
  * statistical accuracy vs the exact fractional l_p^p distance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import (
    ProjectionSpec,
    SketchConfig,
    exact_fractional_lp,
    gm_relative_variance,
    pairwise_geometric_mean,
    sketch,
)
from repro.core import registry
from repro.index import IndexConfig, ShardedSketchIndex, SketchIndex
from repro.kernels.power_project.ops import sketch_via_kernel
from repro.serve import FrontDoor

KEY = jax.random.key(23)

# the new parity-matrix axes: fractional orders x stable families
PS = [1.0, 1.5]
FAMILIES = ["stable", "stable_sparse"]


def _cfg(p, family, k=48, block_d=64, density=0.25):
    return SketchConfig(
        p=p, k=k, block_d=block_d,
        projection=ProjectionSpec(family=family, block_d=block_d,
                                  density=density))


def _data(n=24, m=16, d=96):
    X = jax.random.uniform(jax.random.key(3), (n, d))
    Y = jax.random.uniform(jax.random.key(4), (m, d))
    return X, Y


def _dense_ref(sa, sb, cfg):
    return np.asarray(pairwise_geometric_mean(sa, sb, cfg))


# ------------------------------------------------------------ engine parity


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("reduce", ["full", "topk", "threshold"])
def test_gm_engine_matches_dense_reference(p, family, reduce):
    """Every engine reduce over gm strips reproduces the dense reference —
    values bit for bit, ids/pairs with the dense tie-break."""
    cfg = _cfg(p, family)
    X, Y = _data()
    sa, sb = sketch(X, KEY, cfg), sketch(Y, KEY, cfg)
    dense = _dense_ref(sa, sb, cfg)

    if reduce == "full":
        out = engine.pairwise(sa, sb, cfg, reduce="full",
                              estimator=registry.GEOMETRIC_MEAN)
        np.testing.assert_array_equal(out, dense)
    elif reduce == "topk":
        k = 5
        vals, idx = engine.pairwise(sa, sb, cfg, reduce="topk", top_k=k,
                                    estimator=registry.GEOMETRIC_MEAN)
        vals, idx = np.asarray(vals), np.asarray(idx)
        for i in range(dense.shape[0]):
            order = np.lexsort((np.arange(dense.shape[1]), dense[i]))[:k]
            np.testing.assert_array_equal(vals[i], dense[i][order])
            np.testing.assert_array_equal(idx[i], order)
    else:
        radius = float(np.quantile(dense, 0.3))
        rows, cols = engine.pairwise(sa, sb, cfg, reduce="threshold",
                                     radius=radius,
                                     estimator=registry.GEOMETRIC_MEAN)
        rr, cc = np.nonzero(dense < np.float32(radius))
        np.testing.assert_array_equal(rows, rr)
        np.testing.assert_array_equal(cols, cc)


# -------------------------------------------------- sparse / kernel parity


@pytest.mark.parametrize("p", PS)
def test_sparse_gather_ingest_matches_dense_tile(p):
    """The stable_sparse gather ingest and the dense scatter-materialized
    tiles describe the same R: sketching with either path agrees (to fp
    re-association) across a multi-block D axis."""
    cfg = _cfg(p, "stable_sparse", block_d=64)
    X, _ = _data(n=16, d=192)  # 3 blocks of 64
    gather = sketch(X, KEY, cfg)                      # einsum over (idx, vals)
    dense = sketch_via_kernel(X, KEY, cfg, interpret=True)  # scatter-add tiles
    np.testing.assert_allclose(np.asarray(gather.U), np.asarray(dense.U),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(gather.moments),
                                  np.asarray(dense.moments))


@pytest.mark.parametrize("family", FAMILIES)
def test_kernel_path_matches_streamed_sketch(family):
    """The fused-kernel sketch consumes the same streamed stable R tiles as
    the core path — one block and many."""
    for d in (64, 192):
        cfg = _cfg(1.5, family, block_d=64)
        X, _ = _data(n=8, d=d)
        a = sketch(X, KEY, cfg)
        b = sketch_via_kernel(X, KEY, cfg, interpret=True)
        np.testing.assert_allclose(np.asarray(a.U), np.asarray(b.U),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(a.moments),
                                      np.asarray(b.moments))


# --------------------------------------------------------- serving parity


@pytest.mark.parametrize("family", FAMILIES)
def test_fractional_round_trip_bit_identical_across_tiers(family):
    """The acceptance gate: a fractional-p (p=1.5, α-stable, gm) corpus
    served through the single-host index, the sharded dispatch fan, and the
    SLO front door returns bit-identical distances and ids at every tier."""
    cfg = _cfg(1.5, family, block_d=64)
    icfg = IndexConfig(segment_capacity=32)
    X, Q = _data(n=96, m=8)

    idx1 = SketchIndex(cfg, seed=5, index_cfg=icfg)
    idx1.ingest(X)
    idx2 = ShardedSketchIndex(cfg, seed=5, index_cfg=icfg,
                              devices=jax.devices())
    idx2.ingest(X)
    assert idx1.next_row_id == idx2.next_row_id

    d1, i1 = idx1.query(Q, top_k=5, estimator=registry.GEOMETRIC_MEAN)
    d2, i2 = idx2.query(Q, top_k=5, estimator=registry.GEOMETRIC_MEAN)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))

    fd = FrontDoor(idx2, max_wait_ms=0.0)
    d3, i3 = fd.query(Q, top_k=5, estimator=registry.GEOMETRIC_MEAN)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d3))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i3))

    # the planner keeps gm on the exact dispatch fan (no stacked program)
    assert idx2.stats()["stage1"][registry.GEOMETRIC_MEAN] == "dispatch"

    # threshold reduce rides the same strips: pair-for-pair identity
    dense = d1
    radius = float(np.asarray(dense)[:, 2].mean())
    r1, id1 = idx1.query_threshold(Q, radius, estimator=registry.GEOMETRIC_MEAN)
    r2, id2 = idx2.query_threshold(Q, radius, estimator=registry.GEOMETRIC_MEAN)
    np.testing.assert_array_equal(r1, r2)
    np.testing.assert_array_equal(id1, id2)


def test_fractional_cfg_rejects_even_p_estimators():
    cfg = _cfg(1.5, "stable")
    X, Q = _data(n=8, m=2)
    idx = SketchIndex(cfg, seed=0, index_cfg=IndexConfig(segment_capacity=8))
    idx.ingest(X)
    with pytest.raises(ValueError, match="requires even p"):
        idx.query(Q, top_k=2, estimator=registry.PLAIN)


# ------------------------------------------------------------ accuracy gate


@pytest.mark.parametrize("p", PS)
def test_gm_tracks_exact_fractional_distance(p):
    """Statistical accuracy: per-pair gm estimates sit on the exact
    fractional l_p^p distances with the closed-form relative spread."""
    cfg = _cfg(p, "stable", k=256, block_d=128)
    X, Y = _data(n=24, m=16, d=128)
    sa, sb = sketch(X, KEY, cfg), sketch(Y, KEY, cfg)
    est = _dense_ref(sa, sb, cfg)
    exact = np.asarray(exact_fractional_lp(X, Y, p))
    rel = est / exact - 1.0
    sd = float(np.sqrt(gm_relative_variance(p, cfg.k)))
    # pairs share one R draw, so their errors are correlated and the batch
    # mean fluctuates like a single draw — gate at a couple of per-pair
    # sigmas (a wrong gm constant shows up as an O(1) multiplicative bias)
    assert abs(rel.mean()) < 2 * sd, f"bias {rel.mean():.4f} vs sd {sd:.4f}"
    assert rel.std() < 2 * sd, f"spread {rel.std():.4f} vs sd {sd:.4f}"
