"""Plain reference of the l_p sketch index (Li & Mahoney, p > 2, even p).

Straightforward jax.numpy, independent of the program: the projection R is
drawn again from the index seed, the corpus is made again block by block
from the data seed, and every row is sketched here.  Nothing the program
made (sketches, packed factors, tables) is read.

The index's published contract, restated:

- R (D, k) is standard normal; rows [i*bd, (i+1)*bd) are
  ``normal(fold_in(fold_in(key(seed), 0), i), (bd, k))``.
- A row x is sketched as U_j = (x^j)^T R for j = 1..p-1, and the moments
  M_j = sum_i x_i^(2j); ||x||_p^p is M_(p/2).
- plain:  D(q, x) = ||q||_p^p + ||x||_p^p + sum_m c_m/k <U^q_(p-m), U^x_m>,
  c_m = (-1)^m C(p, m), clipped at 0.
- mle:    each term's inner product is the margin-MLE root (Lemma 4): two
  safeguarded Newton steps on the cubic from the plain t/k, clamped to
  |a| <= sqrt(M^q_(p-m) M^x_m).
- top-k: ascending distance, ties to the lowest row id.

``precision`` is ``"highest"`` (float32 matmuls, what the configuration
states) or ``"high"``: three bf16 passes, the control one step below it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def _dot(a, b, precision: str):
    """a (..., K) @ b (K, N) in float32, at ``highest`` or ``high``.

    ``high`` is written out as its three bf16 passes (hi*hi + hi*lo +
    lo*hi, float32 accumulation), so it means the same on every backend.
    The split rounds with ``reduce_precision``: a bf16 round trip by
    ``astype`` may be elided by XLA's excess-precision rewrite, which
    would leave lo = 0 and one pass."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")

    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)

    def mm(x, y):  # bf16-exact operands: one MXU pass is exact per product
        return jnp.matmul(x, y, precision=jax.lax.Precision.DEFAULT)

    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def projection(index_seed: int, d: int, k: int, block_d: int):
    """R as (ceil(d / bd) * bd, k); rows past d multiply zero padding."""
    bd = min(block_d, d)
    mkey = jax.random.fold_in(jax.random.key(index_seed), 0)
    blocks = [jax.random.normal(jax.random.fold_in(mkey, i), (bd, k),
                                jnp.float32)
              for i in range(-(-d // bd))]
    return jnp.concatenate(blocks, axis=0)


@partial(jax.jit, static_argnames=("p", "precision"))
def sketch(X, R, *, p: int, precision: str):
    """(U (n, p-1, k), M (n, p-1)) of the rows X (n, d)."""
    X = X.astype(jnp.float32)
    pad = R.shape[0] - X.shape[1]
    if pad:
        X = jnp.pad(X, ((0, 0), (0, pad)))
    U = jnp.stack([_dot(X ** j, R, precision) for j in range(1, p)], axis=1)
    M = jnp.stack([jnp.sum(X ** (2 * j), axis=1) for j in range(1, p)],
                  axis=1)
    return U, M


def _orders(p: int):
    return tuple((p - m, m, (-1) ** m * math.comb(p, m)) for m in range(1, p))


def _mle_root(t, nu, nv, Mx, My, k: int, steps: int = 2):
    MxMy = Mx * My
    bound = jnp.sqrt(MxMy)
    cross = (Mx * nv + My * nu) / k
    a = jnp.clip(t / k, -bound, bound)
    for _ in range(steps):
        f = a ** 3 - (a ** 2 / k) * t - (MxMy / k) * t - a * MxMy + a * cross
        fp = 3 * a ** 2 - (2 * a / k) * t - MxMy + cross
        a = jnp.clip(a - f / jnp.where(jnp.abs(fp) < 1e-30, 1e-30, fp),
                     -bound, bound)
    return a


def distances(Uq, Mq, U, M, *, p: int, k: int, estimator: str,
              precision: str):
    """(q, n) estimated ||q - x||_p^p, clipped at 0."""
    norm = p // 2 - 1
    D = Mq[:, norm][:, None] + M[:, norm][None, :]
    for a, c, coef in _orders(p):
        u, v = Uq[:, a - 1], U[:, c - 1]
        t = _dot(u, v.T, precision)
        if estimator == "plain":
            D = D + (coef / k) * t
        elif estimator == "mle":
            nu = jnp.sum(u * u, axis=1)[:, None]
            nv = jnp.sum(v * v, axis=1)[None, :]
            D = D + coef * _mle_root(t, nu, nv, Mq[:, a - 1][:, None],
                                     M[:, c - 1][None, :], k)
        else:
            raise ValueError(f"no reference for estimator {estimator!r}")
    return jnp.maximum(D, 0.0)


@partial(jax.jit, static_argnames=("gen", "n", "d", "gen_params", "p", "k",
                                   "estimator", "precision", "top_k"))
def _fold_block(carry, key, b, n_rows, R, Uq, Mq, served, *, gen, n, d,
                gen_params, p, k, estimator, precision, top_k):
    """Fold corpus block b (rows [b*n, b*n+n)) into the running top-k and
    into the reference's value at every served id."""
    vals, ids, at_served = carry
    X = gen(key, b, n=n, d=d, **dict(gen_params))
    U, M = sketch(X, R, p=p, precision=precision)
    D = distances(Uq, Mq, U, M, p=p, k=k, estimator=estimator,
                  precision=precision)
    row0 = b * n
    col = jnp.arange(n, dtype=jnp.int32)
    D = jnp.where((row0 + col < n_rows)[None, :], D, jnp.inf)
    rel = served - row0
    inside = (rel >= 0) & (rel < n) & (served < n_rows)
    got = jnp.take_along_axis(D, jnp.clip(rel, 0, n - 1), axis=1)
    at_served = jnp.where(inside, got, at_served)
    v = jnp.concatenate([vals, D], axis=1)
    i = jnp.concatenate([ids, jnp.broadcast_to(row0 + col, D.shape)], axis=1)
    neg, pos = jax.lax.top_k(-v, top_k)
    return -neg, jnp.take_along_axis(i, pos, axis=1), at_served


GATHER_ROWS = 32


def rows_at(gen, key, ids: np.ndarray, *, n: int, d: int, gen_params):
    """The corpus rows with these ids (any order), made again on device,
    ``GATHER_ROWS`` at a time from each block that holds some: one compiled
    gather, whatever the ids."""
    width = GATHER_ROWS
    ids = np.asarray(ids, np.int64)
    blocks = ids // n
    take = _take_rows(gen, n, d, tuple(sorted(gen_params.items())), width)
    parts, where = [], np.empty(len(ids), np.int64)
    for b in np.unique(blocks):
        sel = np.flatnonzero(blocks == b)
        for c0 in range(0, len(sel), width):
            part = sel[c0:c0 + width]
            local = np.zeros(width, np.int32)
            local[:len(part)] = ids[part] % n
            where[part] = len(parts) * width + np.arange(len(part))
            parts.append(take(key, jnp.int32(b), jnp.asarray(local)))
    rows = np.concatenate(jax.device_get(parts))
    return jnp.asarray(rows[where])


_TAKES: dict = {}


def _take_rows(gen, n, d, gen_params, width):
    """One compiled gather per (generator, width)."""
    key = (gen, n, d, gen_params, width)
    if key not in _TAKES:
        def take(k, b, local):
            return gen(k, b, n=n, d=d, **dict(gen_params))[local]
        _TAKES[key] = jax.jit(take)
    return _TAKES[key]


def knn(queries, served_ids, *, gen, data_key, index_seed: int,
        n_rows: int, batch_rows: int, d: int, gen_params: dict, p: int,
        k: int, block_d: int, estimator: str, precision: str, top_k: int):
    """Exact top-k of the estimate over the whole corpus, streamed block by
    block so it fits beside nothing else.

    Returns (values (q, top_k), ids (q, top_k), value at each served id
    (q, s) -- NaN where the id is not a corpus row, ||q||_p^p (q,))."""
    R = projection(index_seed, d, k, block_d)
    Uq, Mq = sketch(queries, R, p=p, precision=precision)
    served = jnp.asarray(served_ids, jnp.int32)
    q = queries.shape[0]
    carry = (jnp.full((q, top_k), jnp.inf, jnp.float32),
             jnp.full((q, top_k), -1, jnp.int32),
             jnp.full(served.shape, jnp.nan, jnp.float32))
    params = tuple(sorted(gen_params.items()))
    for b in range(-(-n_rows // batch_rows)):
        carry = _fold_block(carry, data_key, jnp.int32(b), jnp.int32(n_rows),
                            R, Uq, Mq, served, gen=gen, n=batch_rows, d=d,
                            gen_params=params, p=p, k=k, estimator=estimator,
                            precision=precision, top_k=top_k)
    vals, ids, at_served = (np.asarray(x) for x in carry)
    return vals, ids, at_served, np.asarray(Mq[:, p // 2 - 1])
