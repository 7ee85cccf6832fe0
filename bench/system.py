"""The system under test, built from a configuration through the program's
public entry points: ``SketchIndex`` for ingest, ``FrontDoor`` for queries.
A configuration whose ``index`` names ``shards`` builds a
``ShardedSketchIndex`` on a serving mesh of that many of the cell's chips
instead, and refuses to serve unless ingest left every row on a shard, the
shards equal and the stacked fan accepted.

Set-up warms every program the window will run before the window opens:
ingest on a throwaway index, and each batch size the traffic forms (its
``batch_sizes``) on the served index, through a front door that closes a
batch at exactly that size.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sketch import SketchConfig
from repro.index import IndexConfig, ShardedSketchIndex, SketchIndex
from repro.launch.mesh import make_serving_mesh
from repro.serve import FrontDoor


class System:
    def __init__(self, config: dict, traffic: dict, gen, *, index_seed: int,
                 data_key, devices: Optional[list] = None):
        self.config = config
        self.traffic = traffic
        self.gen = gen
        self.gen_params = {k: v for k, v in config["data"].items()
                           if k != "generator"}
        self.index_seed = index_seed
        self.data_key = data_key
        sk = config["sketch"]
        self.cfg = SketchConfig(p=sk["p"], k=sk["k"], block_d=sk["block_d"])
        ix = config["index"]
        self.batch_rows = ix["ingest_batch"]
        self.index_cfg = IndexConfig(segment_capacity=ix["segment_rows"])
        self.shards = ix.get("shards")
        if self.shards is not None and not (
                devices and 1 <= self.shards <= len(devices)):
            raise ValueError(f"{self.shards} shards need as many of the "
                             f"cell's devices; have {devices}")
        self.devices = devices
        self.rows = config["rows"]
        self.dim = config["dim"]
        self.index = None
        self.front_door = None

    # ------------------------------------------------------------ building

    def new_index(self) -> SketchIndex:
        if self.shards is None:
            return SketchIndex(self.cfg, seed=self.index_seed,
                               index_cfg=self.index_cfg)
        mesh = make_serving_mesh(self.shards,
                                 devices=self.devices[:self.shards])
        return ShardedSketchIndex(self.cfg, seed=self.index_seed,
                                  index_cfg=self.index_cfg, mesh=mesh)

    def corpus_batch(self, b: int, n: Optional[int] = None):
        X = self.gen(self.data_key, jnp.int32(b), n=self.batch_rows,
                     d=self.dim, **self.gen_params)
        return X if n is None or n == self.batch_rows else X[:n]

    def _batch_sizes(self) -> List[int]:
        B = self.batch_rows
        sizes = [min(B, self.rows - b * B) for b in range(-(-self.rows // B))]
        return sizes

    def warm_ingest(self) -> None:
        """Every ingest program, on a throwaway index: enough full batches
        to seal one segment, then the corpus's last, partial batch."""
        scratch = self.new_index()
        full = -(-self.index_cfg.segment_capacity // self.batch_rows) + 1
        for b in range(full):
            scratch.ingest(self.corpus_batch(b))
        last = self._batch_sizes()[-1]
        if last != self.batch_rows:
            scratch.ingest(self.corpus_batch(0, last))
        jax.block_until_ready(scratch.sealed[-1].sketch.U)
        del scratch
        gc.collect()

    def ingest(self) -> float:
        """Build the served index from the whole corpus; returns seconds,
        to ``block_until_ready`` on the last rows written."""
        self.index = self.new_index()
        t0 = time.perf_counter()
        for b, n in enumerate(self._batch_sizes()):
            self.index.ingest(self.corpus_batch(b, n))
        last = (self.index.active if self.index.active.size
                else self.index.sealed[-1].sketch)
        jax.block_until_ready(last.U)
        secs = time.perf_counter() - t0
        if self.index.n_live != self.rows:
            raise RuntimeError(f"{self.index.n_live} live rows after ingest, "
                               f"want {self.rows}")
        if self.shards is not None:
            self.check_placement()
        return secs

    def check_placement(self) -> None:
        """The sharded deployment as configured: every row sealed onto a
        shard, the shards equal, and the stacked fan accepted by the mesh."""
        st = self.index.stats()
        rows = st["rows_per_shard"]
        faults = []
        if st["stacked_fan_declined"] is not None:
            faults.append(f"stacked fan declined: {st['stacked_fan_declined']}")
        if len(set(rows)) != 1 or rows[0] == 0:
            faults.append(f"rows per shard {rows}, want equal and non-zero")
        if self.index.active.size:
            faults.append(f"{self.index.active.size} rows left in the active "
                          "segment")
        unplaced = sum(1 for seg in self.index.sealed if seg.shard is None)
        if unplaced:
            faults.append(f"{unplaced} sealed segments on no shard")
        if faults:
            raise RuntimeError("sharded ingest: " + "; ".join(faults))

    def serve(self) -> FrontDoor:
        fd = self.config["front_door"]
        self.front_door = FrontDoor(
            self.index, max_batch=fd["max_batch"],
            max_wait_ms=fd["max_wait_ms"],
            max_queued_rows=fd["max_queued_rows"])
        return self.front_door

    # ------------------------------------------------------------- warm-up

    def _one_batch(self, index, pool: np.ndarray, q: int) -> None:
        """One batch of exactly q one-row requests through a front door."""
        fd = FrontDoor(index, max_batch=q, max_wait_ms=600_000.0,
                       max_queued_rows=max(q, 1024))
        top_k, est = self.traffic["top_k"], self.traffic["estimator"]
        errors = []

        def one(i):
            try:
                v, ids = fd.query(pool[i:i + 1], top_k=top_k, estimator=est)
                np.asarray(v), np.asarray(ids)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i % len(pool),))
                   for i in range(q)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def warm_batches(self, pool: np.ndarray) -> None:
        """Each batch size the traffic forms (``batch_sizes``), one after
        another on the served index (warming sizes side by side compiles
        shared programs more than once)."""
        for q in self.traffic["batch_sizes"]:
            self._one_batch(self.index, pool, q)

    # ---------------------------------------------------------- read-outs

    def stored_sketch(self, ids: np.ndarray):
        """The index's stored (U, moments) rows for these ids.  Ids are
        ingest positions here: nothing is deleted, segments fill and seal in
        order (a sharded index too), which each segment's ``row_ids``
        confirm."""
        cap = self.index_cfg.segment_capacity
        ids = np.asarray(ids, np.int64)
        U = np.empty((len(ids), self.cfg.vectors_per_row, self.cfg.k),
                     np.float32)
        M = np.empty((len(ids), self.cfg.num_moments), np.float32)
        for s in np.unique(ids // cap):
            sel = np.flatnonzero(ids // cap == s)
            if s < len(self.index.sealed):
                sk = self.index.sealed[s].sketch
                Us, Ms = sk.U, sk.moments
            else:
                Us, Ms = self.index.active.U, self.index.active.moments
            seg_ids = (self.index.sealed[s].row_ids if s < len(
                self.index.sealed) else self.index.active.row_ids)
            if not np.array_equal(seg_ids[ids[sel] % cap], ids[sel]):
                raise RuntimeError(f"segment {s} does not hold ids "
                                   f"{ids[sel][:4]}... at ingest positions")
            loc = jnp.asarray(ids[sel] % cap, jnp.int32)
            U[sel] = np.asarray(jnp.take(Us, loc, axis=0))
            M[sel] = np.asarray(jnp.take(Ms, loc, axis=0))
        return U, M

    def close(self) -> None:
        self.front_door = None
        self.index = None
        gc.collect()
