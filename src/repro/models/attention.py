"""Attention: chunked-causal (train/prefill), sliding-window, GQA/MQA,
and sequence-sharded flash-decode with LSE combine over the model axis.

Memory discipline (probe-measured, DESIGN.md §4):
  * train/prefill never materialize (S, S) scores — a lax.scan over query
    chunks bounds live scores at (B, H, q_chunk, S) in fp32.
  * decode caches shard their sequence axis over ``model``; attention over
    the cache runs under shard_map with a local log-sum-exp + psum combine,
    so a 32k x 126-layer cache never leaves its shard.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.parallel.sharding import Parallel

from .layers import Param
from .rope import apply_mrope, apply_rope

__all__ = ["attn_desc", "attention", "decode_attention", "init_kv_cache"]

NEG_INF = -2.0e38


def attn_desc(cfg: ModelConfig, cross: bool = False):
    E, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": Param((E, H, hd), ("embed", "heads", "head_dim")),
        "wk": Param((E, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Param((E, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Param((H, hd, E), ("heads", "head_dim", "embed")),
    }


def _soft_cap(s, cap: float):
    return jnp.tanh(s / cap) * cap if cap else s


def _qkv(x, w, cfg: ModelConfig, par: Parallel, positions, kv_x=None):
    wq = par.use_weight(w["wq"], ("embed", "heads", "head_dim"))
    wk = par.use_weight(w["wk"], ("embed", "kv_heads", "head_dim"))
    wv = par.use_weight(w["wv"], ("embed", "kv_heads", "head_dim"))
    q = jnp.einsum("bsd,dhk->bshk", x, wq)
    k = jnp.einsum("bsd,dhk->bshk", kv_x if kv_x is not None else x, wk)
    v = jnp.einsum("bsd,dhk->bshk", kv_x if kv_x is not None else x, wv)
    q = par.shard(q, ("batch", "seq", "heads", "head_dim"))
    # NOTE: k/v are deliberately NOT constrained pre-GQA-repeat: kv_heads
    # rarely divides the model axis, and a seq-sharded constraint here forces
    # an "involuntary full rematerialization" reshard when the repeat maps
    # them onto head sharding (SPMD warning observed on llama3-405b).
    if positions is not None and cfg.rope_style == "standard":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif positions is not None and cfg.rope_style == "mrope":
        if positions.ndim == 2:  # text-only stream: t = h = w = position
            positions = jnp.broadcast_to(positions[None], (3, *positions.shape))
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def attention(
    x: jax.Array,
    w,
    cfg: ModelConfig,
    par: Parallel,
    *,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 1024,
    kv_x: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunked attention over a full sequence (train / prefill / encoder /
    cross).  ``kv_x`` != None gives cross-attention (no causal mask)."""
    B, S, E = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q, k, v = _qkv(x, w, cfg, par, positions, kv_x=kv_x)
    if kv_x is not None and kv_positions is not None:
        pass  # cross-attn: rope already applied per-side if requested
    Skv = k.shape[1]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    k = par.shard(k, ("batch", "seq", "heads", "head_dim"))
    v = par.shard(v, ("batch", "seq", "heads", "head_dim"))
    scale = hd ** -0.5
    qc = min(q_chunk, S)
    pad = (-S) % qc
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nchunks = q.shape[1] // qc
    kpos = jnp.arange(Skv)

    def chunk(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        s = jnp.einsum("bqhk,bshk->bhqs", qi, k).astype(jnp.float32) * scale
        s = _soft_cap(s, cfg.logit_softcap)
        qpos = i * qc + jnp.arange(qc)
        mask = jnp.ones((qc, Skv), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return _, jnp.einsum("bhqs,bshk->bqhk", p, v)

    _, oc = jax.lax.scan(chunk, 0, jnp.arange(nchunks))
    o = jnp.moveaxis(oc, 0, 1).reshape(B, S + pad, H, hd)[:, :S]
    o = par.shard(o, ("batch", "seq", "heads", "head_dim"))
    wo = par.use_weight(w["wo"], ("heads", "head_dim", "embed"))
    from repro.parallel.sharding import tp_out_project
    of = o.reshape(B, S, H * hd)   # heads-sharded contraction dim
    wof = wo.reshape(H * hd, E)
    return tp_out_project(par, of, wof)


def init_kv_cache(cfg: ModelConfig, n_layers: int, B: int, S: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, B, S, KV, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_logical(seq_name: str = "decode_seq"):
    lg = ("layers", "batch", seq_name, "kv_heads", "head_dim")
    return {"k": lg, "v": lg}


def decode_attention(
    x1: jax.Array,
    w,
    cache_k: jax.Array,
    cache_v: jax.Array,
    index: jax.Array,
    cfg: ModelConfig,
    par: Parallel,
    *,
    update_cache: bool = True,
    causal: bool = True,
    window: int = 0,
    ring: bool = False,
):
    """One decode step against a (B, S, KV, hd) cache.

    When the cache's seq axis is sharded over ``model``, runs a shard_map
    flash-decode: local scores + LSE-combine via psum, and the new (k, v) is
    written only by the owning shard.  Returns (out (B,1,E), cache_k, cache_v).
    """
    B = x1.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pos = jnp.full((B, 1), index, jnp.int32)  # rope always at absolute position
    q, k_new, v_new = _qkv(x1, w, cfg, par, pos)
    q = q[:, 0]  # (B, H, hd)
    k_new, v_new = k_new[:, 0], v_new[:, 0]  # (B, KV, hd)
    scale = hd ** -0.5
    S = cache_k.shape[1]
    mesh = par.mesh
    seq_axes = par.rules.act.get("decode_seq")
    seq_sharded = (
        not ring
        and seq_axes is not None
        and par.constrain
        and par.axis_ok(seq_axes, S)
    )
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    write_idx = (index % S) if ring else index

    if not seq_sharded:
        # small / ring cache path: plain masked attention, cache replicated
        if update_cache:
            cache_k = jax.lax.dynamic_update_slice_in_dim(
                cache_k, k_new[:, None].astype(cache_k.dtype), write_idx, axis=1)
            cache_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v, v_new[:, None].astype(cache_v.dtype), write_idx, axis=1)
        qg = q.reshape(B, KV, H // KV, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k.astype(q.dtype)) * scale
        s = _soft_cap(s.astype(jnp.float32), cfg.logit_softcap)
        kpos = jnp.arange(S)
        if ring:
            # ring slot j holds the latest position == j (mod S) and <= index:
            # once index >= S the whole ring is a valid sliding window.
            valid = (kpos[None] <= index) | jnp.full((1, S), index >= S)
        else:
            valid = kpos[None] <= index if causal else jnp.ones((1, S), bool)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(cache_v.dtype)
        o = jnp.einsum("bkgs,bskd->bkgd", p, cache_v).reshape(B, H, hd)
    else:
        mdl = seq_axes if isinstance(seq_axes, str) else seq_axes[0]

        def shard_fn(q_, kn, vn, ck, cv, idx):
            Bl = q_.shape[0]  # local batch shard
            sloc = ck.shape[1]
            off = jax.lax.axis_index(mdl) * sloc
            li = jnp.clip(idx - off, 0, sloc - 1)
            owns = (idx >= off) & (idx < off + sloc)
            if update_cache:
                ck_u = jax.lax.dynamic_update_slice_in_dim(
                    ck, kn[:, None].astype(ck.dtype), li, axis=1)
                cv_u = jax.lax.dynamic_update_slice_in_dim(
                    cv, vn[:, None].astype(cv.dtype), li, axis=1)
                ck = jnp.where(owns, ck_u, ck)
                cv = jnp.where(owns, cv_u, cv)
            qg = q_.reshape(Bl, KV, H // KV, hd)
            s = jnp.einsum("bkgd,bskd->bkgs", qg, ck.astype(q_.dtype)) * scale
            s = _soft_cap(s.astype(jnp.float32), cfg.logit_softcap)
            gpos = off + jnp.arange(sloc)
            valid = gpos[None] <= idx if causal else jnp.ones((1, sloc), bool)
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
            m_loc = jnp.max(s, axis=-1)                       # (B,KV,G)
            m_glob = jax.lax.pmax(m_loc, mdl)
            e = jnp.exp(s - m_glob[..., None])
            l_loc = jnp.sum(e, axis=-1)
            o_loc = jnp.einsum("bkgs,bskd->bkgd", e.astype(cv.dtype), cv)
            l_glob = jax.lax.psum(l_loc, mdl)
            o_glob = jax.lax.psum(o_loc.astype(jnp.float32), mdl)
            o_ = (o_glob / jnp.maximum(l_glob, 1e-30)[..., None]).astype(q_.dtype)
            return o_.reshape(Bl, H, hd), ck, cv

        bspec = P(batch_axes) if batch_axes else P()
        qspec = P(batch_axes, None, None) if batch_axes else P(None, None, None)
        cspec = P(batch_axes, mdl, None, None) if batch_axes else P(None, mdl, None, None)
        o, cache_k, cache_v = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(qspec, qspec, qspec, cspec, cspec, P()),
            out_specs=(qspec, cspec, cspec),
            check_vma=False,
        )(q, k_new, v_new, cache_k, cache_v, index)

    o = par.shard(o, ("batch", "heads", "head_dim"))
    wo = par.use_weight(w["wo"], ("heads", "head_dim", "embed"))
    out = jnp.einsum("bhk,hkd->bd", o, wo)[:, None, :]
    return par.shard(out, ("batch", "seq", "embed")), cache_k, cache_v
