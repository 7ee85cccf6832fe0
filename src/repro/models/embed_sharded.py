"""Vocab-sharded embedding lookup + cross-entropy under shard_map.

Buffer-assignment analysis on llama3-405b (EXPERIMENTS.md §Perf) showed the
naive paths materialize the FULL fp32 vocab matrix several times per step
(~50 GB/device): XLA partitions jnp.take's backward scatter and the CE
matmul's weight cotangent by replicating the (V, E) table.

Here both ops run under shard_map with the vocab axis pinned to ``model``:

  * lookup: each shard gathers rows it owns (masked) and psums the (B,S,E)
    activation — backward is a LOCAL scatter into the (V/16, E) shard plus
    one (V/16, E) all-reduce over ``data`` (16x less traffic, no full table).
  * CE: local (B,S,V/16) logits, log-sum-exp combined with a psum (same
    pattern as flash-decode), label pick by local index masking — no one-hot,
    no full-vocab tensor anywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import Parallel

__all__ = ["sharded_embed_lookup", "sharded_ce_loss"]


def _batch_axes(par: Parallel):
    return tuple(a for a in ("pod", "data") if a in par.mesh.shape)


def _enabled(par: Parallel, V: int, B: int) -> bool:
    import math
    if not par.constrain or "model" not in par.mesh.shape:
        return False
    if V % par.mesh.shape["model"] != 0:
        return False
    bx = _batch_axes(par)
    bsz = math.prod(par.mesh.shape[a] for a in bx) if bx else 1
    return bx != () and B % bsz == 0


def sharded_embed_lookup(par: Parallel, emb: jax.Array, tokens: jax.Array):
    """emb (V, E) [vocab->model], tokens (B, S) [batch-sharded] -> (B, S, E)."""
    V, E = emb.shape
    if not _enabled(par, V, tokens.shape[0]):
        emb_ = par.use_weight(emb, ("vocab", "embed"))
        return par.shard(jnp.take(emb_, tokens, axis=0),
                         ("batch", "seq", "embed"))
    bx = _batch_axes(par)

    # weights enter in STORAGE layout (vocab x embed sharded over
    # model x data) and are all-gathered over data IN-REGION: an outside
    # reshard P('data','model')->P(None,'model') takes XLA's replicate-full
    # fallback (4.3 GB on llama3; buffer-assignment measured), and the
    # in-region gather also forces the weight grad onto a reduce-scatter.
    emb_spec = par.param_spec(("vocab", "embed"), emb.shape)
    gather_data = len(emb_spec) > 1 and emb_spec[1] is not None

    def local(emb_l, tok):
        if gather_data:
            emb_l = jax.lax.all_gather(emb_l, "data", axis=1, tiled=True)
        vloc = emb_l.shape[0]
        off = jax.lax.axis_index("model") * vloc
        idx = tok - off
        mask = (idx >= 0) & (idx < vloc)
        safe = jnp.clip(idx, 0, vloc - 1)
        x = jnp.take(emb_l, safe, axis=0) * mask[..., None].astype(emb_l.dtype)
        return jax.lax.psum(x, "model")

    return shard_map(
        local, mesh=par.mesh,
        in_specs=(emb_spec, P(bx, None)),
        out_specs=P(bx, None, None),
        check_vma=False,
    )(emb, tokens)


def sharded_ce_loss(par: Parallel, hidden: jax.Array, w: jax.Array,
                    labels: jax.Array) -> jax.Array:
    """sum over (B, S) of softmax CE with w (E, V) [vocab->model].

    hidden (B, S, E) batch-sharded; labels (B, S) with -1 = padding."""
    E, V = w.shape
    if not _enabled(par, V, hidden.shape[0]):
        logits = par.shard(hidden @ par.use_weight(w, ("embed", "vocab")),
                           ("batch", "seq", "vocab")).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, -1)
        oh = par.shard(jax.nn.one_hot(jnp.maximum(labels, 0), V,
                                      dtype=logits.dtype),
                       ("batch", "seq", "vocab"))
        ll = jnp.einsum("bsv,bsv->bs", logits, oh)
        valid = (labels >= 0).astype(jnp.float32)
        return jnp.sum((lse - ll) * valid)
    bx = _batch_axes(par)
    w_spec = par.param_spec(("embed", "vocab"), w.shape)
    gather_data = w_spec and w_spec[0] is not None

    def local(h_l, w_l, lb):
        if gather_data:
            w_l = jax.lax.all_gather(w_l, "data", axis=0, tiled=True)
        vloc = w_l.shape[1]
        off = jax.lax.axis_index("model") * vloc
        logits = (h_l @ w_l).astype(jnp.float32)          # (B_l, S, V_loc)
        # the max shift is pure numerical stabilization — constant wrt grads
        m_loc = jnp.max(jax.lax.stop_gradient(logits), -1)
        m = jax.lax.stop_gradient(jax.lax.pmax(m_loc, "model"))
        se = jnp.sum(jnp.exp(logits - m[..., None]), -1)
        lse = m + jnp.log(jax.lax.psum(se, "model"))
        idx = lb - off
        mask = (idx >= 0) & (idx < vloc)
        safe = jnp.clip(idx, 0, vloc - 1)
        ll_loc = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
        ll = jax.lax.psum(ll_loc * mask.astype(jnp.float32), "model")
        valid = (lb >= 0).astype(jnp.float32)
        return jax.lax.psum(jnp.sum((lse - ll) * valid), (*bx, "model")) / \
            jax.lax.psum(1.0, "model")  # psum over model double-counts rows

    # note: lse/ll are replicated over model after psums; summing locally and
    # psumming over (bx, model) counts each row model_size times -> divide.
    return shard_map(
        local, mesh=par.mesh,
        in_specs=(P(bx, None, None), w_spec, P(bx, None)),
        out_specs=P(),
        check_vma=False,
    )(hidden, w, labels)
