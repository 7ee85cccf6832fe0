"""Numerical correctness of the §Perf shard_map paths on a real 8-device
mesh (subprocess, like test_distributed): vocab-sharded embedding lookup,
vocab-sharded cross-entropy, and the Megatron-SP psum_scatter projection must
match their naive single-device references — including GRADIENTS, since the
whole point of these paths is reshaping the backward collectives."""

import os
import subprocess
import sys
import textwrap

import pytest

_CHILD = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.parallel.sharding import Parallel, ShardingRules, tp_out_project
    from repro.models.embed_sharded import sharded_ce_loss, sharded_embed_lookup
    from repro.compat import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    par = Parallel(mesh=mesh, rules=ShardingRules.default(sequence_parallel=True),
                   constrain=True)
    B, S, E, V, F = 4, 16, 32, 64, 48
    key = jax.random.key(0)

    # ---- embedding lookup fwd + grad
    emb = jax.random.normal(key, (V, E))
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, V)
    with jax.set_mesh(mesh):
        got = jax.jit(lambda e: sharded_embed_lookup(par, e, toks))(emb)
    want = jnp.take(emb, toks, axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def esum(e):
        return jnp.sum(sharded_embed_lookup(par, e, toks) ** 2)
    def esum_ref(e):
        return jnp.sum(jnp.take(e, toks, axis=0) ** 2)
    with jax.set_mesh(mesh):
        g1 = jax.jit(jax.grad(esum))(emb)
    g2 = jax.grad(esum_ref)(emb)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-4)
    print("EMBED_OK")

    # ---- cross entropy fwd + grads (wrt hidden and weights)
    hid = jax.random.normal(jax.random.key(2), (B, S, E))
    w = jax.random.normal(jax.random.key(3), (E, V)) * 0.2
    lb = jax.random.randint(jax.random.key(4), (B, S), 0, V)
    lb = lb.at[0, 0].set(-1)  # padding path

    def ce_ref(h, w_):
        logits = (h @ w_).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None], -1)[..., 0]
        return jnp.sum((lse - ll) * (lb >= 0))

    with jax.set_mesh(mesh):
        loss = jax.jit(lambda h, w_: sharded_ce_loss(par, h, w_, lb))(hid, w)
    np.testing.assert_allclose(float(loss), float(ce_ref(hid, w)), rtol=1e-5)
    with jax.set_mesh(mesh):
        gh, gw = jax.jit(jax.grad(
            lambda h, w_: sharded_ce_loss(par, h, w_, lb), argnums=(0, 1)))(hid, w)
    gh_r, gw_r = jax.grad(ce_ref, argnums=(0, 1))(hid, w)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), rtol=2e-4, atol=2e-4)
    print("CE_OK")

    # ---- tp_out_project fwd + grads
    h = jax.random.normal(jax.random.key(5), (B, S, F))
    wd = jax.random.normal(jax.random.key(6), (F, E)) * 0.1

    def proj(h_, w_):
        return jnp.sum(tp_out_project(par, h_, w_) ** 2)
    def proj_ref(h_, w_):
        return jnp.sum((h_ @ w_) ** 2)

    with jax.set_mesh(mesh):
        out = jax.jit(lambda h_, w_: tp_out_project(par, h_, w_))(h, wd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(h @ wd),
                               rtol=1e-4, atol=1e-4)
    with jax.set_mesh(mesh):
        gh, gw = jax.jit(jax.grad(proj, argnums=(0, 1)))(h, wd)
    gh_r, gw_r = jax.grad(proj_ref, argnums=(0, 1))(h, wd)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(gh_r), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_r), rtol=1e-4, atol=1e-4)
    print("TPPROJ_OK")
    """
)


@pytest.mark.slow
def test_perf_shard_map_paths_match_references():
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr[-3000:]
    for tag in ("EMBED_OK", "CE_OK", "TPPROJ_OK"):
        assert tag in res.stdout, res.stdout + res.stderr[-2000:]
