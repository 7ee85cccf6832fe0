"""Packed all-pairs path == per-pair estimators; KNN retrieval quality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SketchConfig,
    estimate,
    estimate_margin_mle,
    exact_pairwise_lp,
    knn,
    pack_sketch,
    pairwise_distances,
    pairwise_margin_mle,
    sketch,
    variance_plain,
)
from repro.core.pairwise import pack_right

KEY = jax.random.key(3)


def _sk(X, cfg):
    return sketch(X, KEY, cfg)


@pytest.mark.parametrize("strategy", ["basic", "alternative"])
@pytest.mark.parametrize("p", [4, 6])
def test_pairwise_equals_per_pair(strategy, p):
    cfg = SketchConfig(p=p, k=128, strategy=strategy, block_d=64)
    X = jax.random.uniform(jax.random.key(1), (6, 128))
    sk = _sk(X, cfg)
    D = np.asarray(pairwise_distances(sk, None, cfg, clip=False))
    for i in range(6):
        for j in range(6):
            e = float(estimate(sk.row(i), sk.row(j), cfg, clip=False)[0])
            np.testing.assert_allclose(D[i, j], e, rtol=2e-3, atol=1e-3)


@pytest.mark.parametrize("strategy", ["basic", "alternative"])
@pytest.mark.parametrize("p", [4, 6])
def test_pack_right_is_pack_sketch_right_factor(strategy, p):
    # stored segments pack with pack_right, queries with pack_sketch: the
    # right factor and norms must carry the same bits either way
    cfg = SketchConfig(p=p, k=32, strategy=strategy, block_d=64)
    sk = _sk(jax.random.uniform(jax.random.key(2), (40, 128)), cfg)
    _, B, nb = pack_sketch(sk, cfg)
    B_r, nb_r = pack_right(sk, cfg)
    np.testing.assert_array_equal(np.asarray(B_r), np.asarray(B))
    np.testing.assert_array_equal(np.asarray(nb_r), np.asarray(nb))


def test_pairwise_symmetry_and_diag():
    cfg = SketchConfig(p=4, k=64, block_d=64)
    X = jax.random.uniform(jax.random.key(2), (8, 128))
    sk = _sk(X, cfg)
    D = np.asarray(pairwise_distances(sk, None, cfg, clip=False))
    np.testing.assert_allclose(D, D.T, rtol=1e-4, atol=1e-4)
    Dz = np.asarray(pairwise_distances(sk, None, cfg, zero_diag=True))
    assert np.all(np.diag(Dz) == 0)


def test_pairwise_mle_equals_per_pair():
    cfg = SketchConfig(p=4, k=128, block_d=64)
    X = jax.random.uniform(jax.random.key(4), (5, 128))
    sk = _sk(X, cfg)
    D = np.asarray(pairwise_margin_mle(sk, None, cfg, clip=False))
    for i in range(5):
        for j in range(5):
            e = float(estimate_margin_mle(sk.row(i), sk.row(j), cfg, clip=False)[0])
            np.testing.assert_allclose(D[i, j], e, rtol=5e-3, atol=1e-3)


def test_cross_set_pairwise():
    cfg = SketchConfig(p=4, k=256, block_d=64)
    A = jax.random.uniform(jax.random.key(5), (4, 128))
    B = jax.random.uniform(jax.random.key(6), (7, 128))
    D = np.asarray(pairwise_distances(_sk(A, cfg), _sk(B, cfg), cfg))
    exact = np.asarray(exact_pairwise_lp(A, B, 4))
    assert D.shape == (4, 7)
    # The right tolerance is not a constant: Lemma 1 gives Var(d_hat) per
    # pair, and on this data sigma is comparable to the distances themselves
    # (relative error O(1) at k=256 is expected, not a bug).  Bound the
    # z-scores instead: every pair within a few sigma, bulk well inside.
    An, Bn = np.asarray(A), np.asarray(B)
    sigma = np.sqrt([
        [float(variance_plain(An[i], Bn[j], cfg.p, cfg.k, cfg.strategy))
         for j in range(B.shape[0])]
        for i in range(A.shape[0])
    ])
    z = np.abs(D - exact) / sigma
    assert np.all(z < 4.0), z
    assert np.median(z) < 2.0, z


def test_knn_recovers_clusters():
    """Well-separated clusters: sketch-KNN must retrieve same-cluster points."""
    rng = np.random.default_rng(0)
    centers = rng.uniform(0, 10, size=(4, 64))
    pts = np.concatenate([c + 0.01 * rng.standard_normal((8, 64)) for c in centers])
    X = jnp.asarray(pts, jnp.float32)
    cfg = SketchConfig(p=4, k=512, block_d=64)
    sk = _sk(X, cfg)
    dists, idx = knn(sk, sk, cfg, top_k=8)
    idx = np.asarray(idx)
    for q in range(32):
        cluster = q // 8
        neighbors = idx[q]
        frac = np.mean((neighbors // 8) == cluster)
        assert frac >= 0.9, (q, neighbors)


def test_knn_mle_mode():
    X = jax.random.uniform(jax.random.key(8), (16, 64))
    cfg = SketchConfig(p=4, k=128, block_d=64)
    sk = _sk(X, cfg)
    d, i = knn(sk, sk, cfg, top_k=3, mle=True)
    assert d.shape == (16, 3) and i.shape == (16, 3)
    # self is (almost always) the nearest under MLE too
    assert np.mean(np.asarray(i)[:, 0] == np.arange(16)) > 0.8
