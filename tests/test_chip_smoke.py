"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The smoke itself refuses to run without a TPU; these tests drive the same
phases and checks through the Pallas interpreter, so a broken phase or a
checker that cannot fail shows up before any chip time is spent.
"""

import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.engine import EngineConfig

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    return smoke.Deployment(rows=3000, d=192, k=128, ingest_batch=512,
                            segment_rows=1024, query_batches=2,
                            query_rows=16, deletes=50)


def test_smoke_phases_pass_at_a_tiny_size(smoke, tiny):
    out = smoke.run_one_chip(
        tiny, seed=0, engine=EngineConfig(backend="interpret", col_block=512))
    for est in smoke.ESTIMATORS:
        assert out[f"recall_{est}"] >= smoke.RECALL_FLOOR
        assert out[f"check_{est}"]["max_err_over_tol"] <= 1.0
        assert out[f"check_{est}_after_delete"]["max_err_over_tol"] <= 1.0
    assert max(out["check_sketch"].values()) <= 1.0
    # on CPU the default matmul precision is already full float32
    assert out["default_precision"]["top_k_ids_differ"] == 0.0


def test_smoke_sketch_check_rejects_a_bf16_ingest(smoke, tiny):
    """The stored-sketch check passes a float32 sketch and refuses one whose
    operands went through bfloat16, as one bf16 MXU pass would leave them."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    X = rng.uniform(size=(16, tiny.d)) * (rng.uniform(size=(16, tiny.d)) < 0.25)
    X = jnp.asarray(X, jnp.float32)
    R = jnp.asarray(rng.normal(size=(tiny.d, tiny.k)), jnp.float32)
    U, M = smoke.ref_sketch(tiny, R, X)
    assert smoke.check_sketch(U, M, U, M) == {"U_err_over_tol": 0.0,
                                              "moments_err_over_tol": 0.0}
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    U_bf = jnp.stack([bf(X ** j) @ bf(R) for j in range(1, tiny.p)], axis=1)
    with pytest.raises(AssertionError, match="stored rows off"):
        smoke.check_sketch(U_bf, M, U, M)
    with pytest.raises(AssertionError, match="stored rows off"):
        smoke.check_sketch(U, M * (1 + 1e-3), U, M)


def test_smoke_check_rejects_wrong_answers(smoke):
    rng = np.random.default_rng(0)
    D = rng.uniform(1.0, 2.0, (4, 50)).astype(np.float32)
    live = np.arange(100, 150)
    order = np.argsort(D, axis=1)[:, :5]
    vals = np.take_along_axis(D, order, axis=1)
    ids = live[order]
    rep, ref_vals, ref_ids = smoke.check_topk("exact", vals, ids, D, live,
                                              1.0, 5)
    assert rep == {"max_err_over_tol": 0.0, "id_swaps_in_ties": 0}
    np.testing.assert_array_equal(ref_ids, ids)
    np.testing.assert_array_equal(ref_vals, vals)
    with pytest.raises(AssertionError, match="values off"):
        smoke.check_topk("values", vals + 1e-2, ids, D, live, 1.0, 5)
    swapped = ids.copy()
    swapped[:, [0, 4]] = swapped[:, [4, 0]]
    with pytest.raises(AssertionError, match="without a tie"):
        smoke.check_topk("ids", vals, swapped, D, live, 1.0, 5)
    with pytest.raises(AssertionError, match="not live"):
        smoke.check_topk("dead", vals, ids + 1000, D, live, 1.0, 5)


def test_smoke_refuses_to_run_without_a_tpu(smoke):
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.main([])
