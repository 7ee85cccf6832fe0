"""Streaming engine == dense paths, bit for bit (CPU, xla backend).

Shapes are chosen so the strip count is > 1 in BOTH dimensions
(70 rows / row_block 32 -> 3 strips; 45 cols / col_block 16 -> 3 strips) and
the final strips are ragged.  The xla backend on CPU must reproduce the dense
``pairwise_distances``/``knn`` results exactly — values AND tie-breaking.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import (
    SketchConfig,
    knn,
    pairwise_distances,
    pairwise_margin_mle,
    sketch,
)
from repro.engine import EngineConfig, strip_bounds

KEY = jax.random.key(3)
ENG = EngineConfig(backend="xla", row_block=32, col_block=16)


def _sketches(p, strategy, n=70, m=45, d=96, k=64):
    cfg = SketchConfig(p=p, k=k, strategy=strategy, block_d=64)
    X = jax.random.uniform(jax.random.key(1), (n, d))
    Y = jax.random.uniform(jax.random.key(2), (m, d))
    return sketch(X, KEY, cfg), sketch(Y, KEY, cfg), cfg


def test_strip_count_is_multi_dim():
    # the acceptance shape: > 1 strip in both dimensions, ragged tails
    assert len(strip_bounds(70, 32)) == 3
    assert len(strip_bounds(45, 16)) == 3


@pytest.mark.parametrize("strategy", ["basic", "alternative"])
@pytest.mark.parametrize("p", [4, 6])
def test_full_matches_dense_bitwise(strategy, p):
    sa, sb, cfg = _sketches(p, strategy)
    dense = np.asarray(pairwise_distances(sa, sb, cfg))
    got = engine.pairwise(sa, sb, cfg, reduce="full", engine=ENG)
    np.testing.assert_array_equal(got, dense)


@pytest.mark.parametrize("strategy", ["basic", "alternative"])
@pytest.mark.parametrize("p", [4, 6])
def test_topk_matches_dense_bitwise(strategy, p):
    sa, sb, cfg = _sketches(p, strategy)
    dense = pairwise_distances(sa, sb, cfg)
    neg, idx = jax.lax.top_k(-dense, 7)
    vals, got_idx = engine.pairwise(sa, sb, cfg, reduce="topk", top_k=7, engine=ENG)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(-neg))
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(idx))


@pytest.mark.parametrize("strategy", ["basic", "alternative"])
@pytest.mark.parametrize("p", [4, 6])
def test_threshold_matches_dense_mask(strategy, p):
    sa, sb, cfg = _sketches(p, strategy)
    dense = np.asarray(pairwise_distances(sa, sb, cfg))
    radius = float(np.median(dense))
    rows, cols = engine.pairwise(
        sa, sb, cfg, reduce="threshold", radius=radius, engine=ENG
    )
    want_r, want_c = np.nonzero(dense < radius)
    np.testing.assert_array_equal(rows, want_r)
    np.testing.assert_array_equal(cols, want_c)


def test_knn_is_engine_backed():
    """Public knn() == dense formula after the engine rewire."""
    sa, sb, cfg = _sketches(4, "basic")
    dense = pairwise_distances(sa, sb, cfg)
    neg, idx = jax.lax.top_k(-dense, 10)
    vals, got_idx = knn(sa, sb, cfg, top_k=10, engine_cfg=ENG)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(-neg))
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(idx))


def test_self_pairs_and_zero_diag():
    cfg = SketchConfig(p=4, k=32, block_d=64)
    X = jax.random.uniform(jax.random.key(4), (33, 96))
    sa = sketch(X, KEY, cfg)
    eng = EngineConfig(backend="xla", row_block=16, col_block=16)
    dense = np.asarray(pairwise_distances(sa, None, cfg, zero_diag=True))
    got = engine.pairwise(sa, None, cfg, reduce="full", zero_diag=True, engine=eng)
    np.testing.assert_array_equal(got, dense)
    # self top-k: the ragged 33-row corpus has a width-1 tail the tiling
    # must absorb (a width-1 XLA strip is a GEMV with a different K order)
    neg, idx = jax.lax.top_k(-pairwise_distances(sa, None, cfg), 5)
    vals, gidx = engine.pairwise(sa, None, cfg, reduce="topk", top_k=5, engine=eng)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(-neg))
    np.testing.assert_array_equal(np.asarray(gidx), np.asarray(idx))


@pytest.mark.parametrize("reduce", ["full", "topk"])
def test_mle_epilogue_matches_dense(reduce):
    sa, sb, cfg = _sketches(4, "alternative")
    dense = pairwise_margin_mle(sa, sb, cfg)
    if reduce == "full":
        got = engine.pairwise(sa, sb, cfg, reduce="full", estimator="mle", engine=ENG)
        np.testing.assert_array_equal(got, np.asarray(dense))
    else:
        neg, idx = jax.lax.top_k(-dense, 5)
        vals, gidx = engine.pairwise(
            sa, sb, cfg, reduce="topk", top_k=5, estimator="mle", engine=ENG
        )
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(-neg))
        np.testing.assert_array_equal(np.asarray(gidx), np.asarray(idx))


def test_threshold_relative_scale():
    """The dedup criterion: D < radius * (||x||_p^p + ||y||_p^p)."""
    sa, sb, cfg = _sketches(4, "basic")
    dense = np.asarray(pairwise_distances(sa, sb, cfg))
    na = np.asarray(sa.norm_pp(cfg.p))
    nb = np.asarray(sb.norm_pp(cfg.p))
    radius = 0.5
    rows, cols = engine.pairwise(
        sa, sb, cfg, reduce="threshold", radius=radius, relative=True, engine=ENG
    )
    want_r, want_c = np.nonzero(dense < radius * (na[:, None] + nb[None, :]))
    np.testing.assert_array_equal(rows, want_r)
    np.testing.assert_array_equal(cols, want_c)


def test_interpret_backend_matches_xla():
    """The Pallas kernel program (interpreted) agrees with the xla strips."""
    sa, sb, cfg = _sketches(4, "basic", n=34, m=21)
    eng = EngineConfig(backend="interpret", row_block=16, col_block=16)
    got = engine.pairwise(sa, sb, cfg, reduce="full", engine=eng)
    dense = np.asarray(pairwise_distances(sa, sb, cfg))
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-5)


def test_topk_caps_at_corpus_size():
    sa, sb, cfg = _sketches(4, "basic", n=10, m=6)
    vals, idx = engine.pairwise(
        sa, sb, cfg, reduce="topk", top_k=50,
        engine=EngineConfig(backend="xla", row_block=4, col_block=4),
    )
    assert vals.shape == (10, 6) and idx.shape == (10, 6)
    # every corpus index present exactly once per row
    np.testing.assert_array_equal(np.sort(np.asarray(idx), axis=1),
                                  np.tile(np.arange(6), (10, 1)))


def test_engine_validates_arguments():
    sa, sb, cfg = _sketches(4, "basic", n=8, m=8)
    with pytest.raises(ValueError):
        engine.pairwise(sa, sb, cfg, reduce="nope")
    with pytest.raises(ValueError):
        engine.pairwise(sa, sb, cfg, reduce="threshold")  # no radius
    with pytest.raises(ValueError):
        engine.pairwise(sa, sb, cfg, estimator="bogus")
    with pytest.raises(ValueError):
        EngineConfig(backend="cuda")
    with pytest.raises(ValueError):
        EngineConfig(row_block=0)


def test_platform_defaults_never_fall_back_to_cpu():
    assert EngineConfig().resolve("tpu") == ("pallas", 1024, 1024)
    assert EngineConfig(col_block=64).resolve("cpu") == ("xla", 512, 64)
    for platform in ("gpu", "rocm", "metal"):
        with pytest.raises(ValueError, match="no engine defaults"):
            EngineConfig().resolve(platform)
        with pytest.raises(ValueError, match="no engine defaults"):
            engine.default_backend(platform)
