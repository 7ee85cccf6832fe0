"""Logical-axis sharding rules (MaxText-style), with divisibility guards.

Every parameter and activation dimension carries a *logical* name; a rules
table maps logical names to mesh axes.  A mapping is silently dropped when
the dimension is not divisible by the mesh axis size (e.g. vocab=256206 on a
16-way model axis, or kv_heads=1) — the dimension stays replicated, which is
always correct, and the dry-run log records the drop.

Probe-measured rationale (see DESIGN.md §4): without explicit activation
constraints XLA replicates the residual stream (68 GB/device on llama3-405b);
with them + sequence parallelism the same forward fits.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["ShardingRules", "Parallel", "logical_to_spec", "shard_act"]

# logical axis -> mesh axis (or tuple of mesh axes) -- None = replicated
DEFAULT_PARAM_RULES: dict[str, object] = {
    "embed": "data",          # FSDP: weights' embed dim sharded over data
    "embed_r": None,          # replicated variant (small models)
    "heads": "model",         # tensor parallelism
    "kv_heads": "model",
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",       # expert parallelism
    "expert_ff": None,
    "layers": None,
    "groups": None,
    "conv": None,
    "state": None,
    "lru": "model",
    "norm": None,
}

DEFAULT_ACT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,              # "model" under sequence parallelism
    "seq_kv": None,
    "embed": None,
    "heads": "model",
    "kv_heads": None,
    "head_dim": None,
    "ff": "model",
    "vocab": "model",
    "experts": "model",
    "capacity": None,
    "state": None,
    "lru": "model",
    "decode_seq": "model",    # KV caches: seq dim sharded over model
    "expert_ff": None,
    "conv": None,
    "norm": None,
    "embed_r": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    param: Mapping[str, object]
    act: Mapping[str, object]

    @classmethod
    def default(cls, *, sequence_parallel: bool = False, fsdp: bool = True):
        act = dict(DEFAULT_ACT_RULES)
        if sequence_parallel:
            act["seq"] = "model"
        param = dict(DEFAULT_PARAM_RULES)
        if not fsdp:
            param["embed"] = None
        return cls(param=param, act=act)


@dataclasses.dataclass(frozen=True)
class Parallel:
    """Everything model code needs to shard itself on the current mesh."""

    mesh: Mesh
    rules: ShardingRules
    constrain: bool = True  # disable for tiny CPU smoke tests

    def axis_ok(self, axes, dim: int) -> bool:
        if axes is None:
            return True
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        # axes absent from this mesh (e.g. "pod" on the single-pod mesh) are
        # simply dropped — the remaining axes must divide the dimension
        axes = tuple(a for a in axes if a in self.mesh.shape)
        if not axes:
            return False
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        return dim % size == 0

    def resolve(self, logical: Sequence[Optional[str]], shape: Sequence[int],
                table: Mapping[str, object]) -> P:
        out = []
        for name, dim in zip(logical, shape):
            axes = table.get(name) if name else None
            if axes is None or not self.axis_ok(axes, dim):
                out.append(None)
            else:
                # drop mesh axes absent from this mesh (e.g. no "pod" single-pod)
                if isinstance(axes, tuple):
                    axes = tuple(a for a in axes if a in self.mesh.shape)
                    axes = axes if axes else None
                out.append(axes)
        # a mesh axis may appear at most once: later (feature) dims win, so
        # under sequence parallelism ("seq" -> model) an ff/heads dim already
        # on "model" silently reverts seq to replicated (Megatron-SP regions)
        used: set = set()
        for i in range(len(out) - 1, -1, -1):
            axes = out[i]
            if axes is None:
                continue
            aset = set(axes) if isinstance(axes, tuple) else {axes}
            if aset & used:
                out[i] = None
            else:
                used |= aset
        return P(*out)

    def param_spec(self, logical, shape) -> P:
        return self.resolve(logical, shape, self.rules.param)

    def act_spec(self, logical, shape) -> P:
        return self.resolve(logical, shape, self.rules.act)

    def shard(self, x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
        """with_sharding_constraint by logical names (no-op when disabled)."""
        if not self.constrain:
            return x
        spec = self.act_spec(logical, x.shape)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def use_weight(self, w: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
        """Constrain an FSDP-stored weight to its COMPUTE layout before use.

        Storage shards the embed dim over ``data`` (ZeRO); naive use would
        make XLA partial-sum the activation and all-reduce the (much larger)
        output — dry-run measured an 8.4 GB full-vocab logits all-reduce on
        gemma-2b.  Constraining to the act rules instead all-gathers the
        weight shard (64 MB there) and reduce-scatters its gradient."""
        if not self.constrain:
            return w
        spec = self.resolve(logical, w.shape, self.rules.act)
        return jax.lax.with_sharding_constraint(w, NamedSharding(self.mesh, spec))


def tp_out_project(par: Parallel, h: jax.Array, w: jax.Array) -> jax.Array:
    """Megatron-SP output projection: h (B, S, F) [F sharded over model]
    @ w (F, E) [F sharded] -> out (B, S, E) with S sharded over model,
    reduced by an explicit psum_scatter instead of all-reduce + slice.

    XLA's partitioner on this path emits a FULL-SEQ all-reduce followed by a
    dynamic-slice (measured 134 MB/layer/microbatch on llama3-405b; the
    AR->RS rewrite pass is not in the CPU pipeline and is fragile on TPU
    for scanned bodies).  The explicit reduce-scatter halves ring traffic
    and never materializes the full-seq tensor.  Falls back to a plain
    matmul + constraint when SP is off or shapes don't divide."""
    mdl = "model"
    seq_axes = par.rules.act.get("seq")
    ok = (
        par.constrain
        and seq_axes == mdl
        and mdl in par.mesh.shape
        and h.shape[1] % par.mesh.shape[mdl] == 0
        and h.shape[2] % par.mesh.shape[mdl] == 0
    )
    if not ok:
        out = h @ w
        return par.shard(out, ("batch", "seq", "embed"))
    import math
    batch_axes = tuple(a for a in ("pod", "data") if a in par.mesh.shape)
    bsize = math.prod(par.mesh.shape[a] for a in batch_axes) if batch_axes else 1
    bspec = batch_axes if (batch_axes and h.shape[0] % bsize == 0) else None

    w_spec = par.param_spec(("ff", "embed"), w.shape)
    gather_data = len(w_spec) > 1 and w_spec[1] is not None

    def local(h_l, w_l):
        if gather_data:  # weight enters in storage layout; gather in-region
            w_l = jax.lax.all_gather(w_l, "data", axis=1, tiled=True)
        part = jax.numpy.einsum("bsf,fd->bsd", h_l, w_l)
        return jax.lax.psum_scatter(part, mdl, scatter_dimension=1, tiled=True)

    return shard_map(
        local, mesh=par.mesh,
        in_specs=(P(bspec, None, mdl), w_spec, ),
        out_specs=P(bspec, mdl, None),
        check_vma=False,
    )(h, w)


def logical_to_spec(par: Parallel, logical, shape) -> NamedSharding:
    return NamedSharding(par.mesh, par.param_spec(logical, shape))


def shard_act(par: Parallel, x, logical):
    return par.shard(x, logical)
