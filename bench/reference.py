"""The plain reference and the comparison that decides ``correct``.

The reference is exact k-nearest-neighbour search under squared L2: every
query against every base vector, in float32 at ``Precision.HIGHEST``, in
blocks of queries so that it fits beside the index.  It imports nothing of
the program and takes nothing the program made: the base set and the
queries come from :mod:`bench.data`.

What the client received is judged answer by answer:

* ``d2_rel_err`` — the widest relative gap between a served distance and
  the true float32 distance of the id served with it.  A walk that scores
  neighbours in a lower precision, an id altered after it was scored, or a
  front door that hands one request another's answer all show here.
* ``malformed_rows`` — answers with an id outside the index, a repeated id,
  a non-finite distance, or distances out of order.  Exact: limit 0.
* ``recall_miss`` — 1 - recall@10 against the reference's top 10.

:func:`control_answers` is the reference in the program's place one
precision lower (bfloat16 inputs, float32 accumulation): the answers a
program that took that step would serve.  It has to fail the comparison.
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _topk_block(k: int, low: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(q, x, xn):
        if low:
            qb, xb = q.astype(jnp.bfloat16), x.astype(jnp.bfloat16)
            dot = jnp.matmul(qb, xb.T, preferred_element_type=jnp.float32)
            qf = qb.astype(jnp.float32)
            qn = jnp.sum(qf * qf, axis=1)
        else:
            dot = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
            qn = jnp.sum(q * q, axis=1)
        d2 = qn[:, None] + xn[None, :] - 2.0 * dot
        neg, ids = jax.lax.top_k(-d2, k)
        return ids, -neg

    return block


@functools.lru_cache(maxsize=None)
def _norms(low: bool):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(x):
        if low:
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.sum(x * x, axis=1)

    return norms


def _topk(base, queries, k: int, low: bool, block: int):
    import jax.numpy as jnp

    q = np.asarray(queries, np.float32)
    nq = q.shape[0]
    xn = _norms(low)(base)
    fn = _topk_block(k, low)
    ids = np.empty((nq, k), np.int64)
    d2 = np.empty((nq, k), np.float32)
    for s in range(0, nq, block):
        qb = q[s:s + block]
        pad = block - qb.shape[0]
        if pad:
            qb = np.concatenate([qb, np.repeat(qb[:1], pad, axis=0)])
        i, d = fn(jnp.asarray(qb), base, xn)
        n = block - pad
        ids[s:s + n] = np.asarray(i)[:n]
        d2[s:s + n] = np.asarray(d)[:n]
    return ids, d2


def exact_topk(base, queries, k: int, block: int = 256, extra: int = 16):
    """Reference top-k ids and squared distances, (Q, k) each.  The matmul
    form |q|^2 + |x|^2 - 2 q.x finds ``k + extra`` candidates; their
    distances are then taken elementwise, sum((x - q)^2), which has no
    cancellation, and the best ``k`` kept."""
    cand, _ = _topk(base, queries, k + extra, low=False, block=block)
    d2 = true_d2(base, queries, cand)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(cand, order, axis=1),
            np.take_along_axis(d2, order, axis=1))


def control_answers(base, queries, k: int, block: int = 256):
    """The reference one precision lower: (ids, d2) a program computing its
    distances from bfloat16 inputs would serve."""
    return _topk(base, queries, k, low=True, block=block)


@functools.lru_cache(maxsize=None)
def _true_d2_block():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(x, q, ids):
        rows = x[jnp.maximum(ids, 0)]                  # (A, k, d)
        diff = rows - q[:, None, :]
        return jnp.sum(diff * diff, axis=-1)

    return block


def true_d2(base, queries, ids, block: int = 2048) -> np.ndarray:
    """float32 squared L2 between each query and each id served for it."""
    import jax.numpy as jnp

    q = np.asarray(queries, np.float32)
    ids = np.asarray(ids)
    out = np.empty(ids.shape, np.float32)
    fn = _true_d2_block()
    for s in range(0, ids.shape[0], block):
        qb, ib = q[s:s + block], ids[s:s + block]
        pad = block - qb.shape[0]
        if pad:
            qb = np.concatenate([qb, np.repeat(qb[:1], pad, axis=0)])
            ib = np.concatenate([ib, np.repeat(ib[:1], pad, axis=0)])
        d = np.asarray(fn(base, jnp.asarray(qb), jnp.asarray(ib, np.int32)))
        out[s:s + block] = d[:block - pad]
    return out


def malformed(ids: np.ndarray, d2: np.ndarray, n: int) -> np.ndarray:
    """(A,) bool: rows with an id outside [0, n), a repeated id, a
    non-finite distance, or distances that decrease."""
    ids = np.asarray(ids)
    d2 = np.asarray(d2, np.float64)
    out_of_range = ((ids < 0) | (ids >= n)).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    nonfinite = ~np.isfinite(d2).all(axis=1)
    with np.errstate(invalid="ignore"):
        unordered = (np.diff(d2, axis=1) < 0).any(axis=1)
    return out_of_range | repeated | nonfinite | unordered


def recall(ids: np.ndarray, gt: np.ndarray) -> float:
    """Mean share of each row's reference top-k found in the served row."""
    ids, gt = np.asarray(ids), np.asarray(gt)
    if ids.shape[0] == 0:
        return float("nan")
    hit = (gt[:, :, None] == ids[:, None, :]).any(axis=2)
    return float(hit.sum(axis=1).mean() / gt.shape[1])


def compare(base, pool, qidx, ids, d2, k: int) -> dict:
    """Every number the comparison can read, for answers (ids, d2) served
    to pool rows ``qidx``.  Returns {"d2_rel_err", "malformed_rows",
    "recall_miss", "recall_at_10", "answers"}."""
    qidx = np.asarray(qidx)
    ids = np.asarray(ids)
    d2 = np.asarray(d2, np.float32)
    n = int(base.shape[0])
    bad = malformed(ids, d2, n)
    pool_np = np.asarray(pool, np.float32)
    uniq, inv = np.unique(qidx, return_inverse=True)
    gt_u, _ = exact_topk(base, pool_np[uniq], k)
    gt = gt_u[inv]
    truth = true_d2(base, pool_np[qidx], ids)
    valid = (ids >= 0) & (ids < n) & np.isfinite(d2)
    floor = 1e-6 * float(np.median(truth[valid])) if valid.any() else 0.0
    rel = np.abs(d2.astype(np.float64) - truth) / np.maximum(truth, floor)
    err = float(rel[valid].max()) if valid.any() else float("inf")
    rec = recall(ids, gt)
    return {"d2_rel_err": err, "malformed_rows": int(bad.sum()),
            "recall_miss": 1.0 - rec, "recall_at_10": rec,
            "answers": int(ids.shape[0])}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the numbers the
    configuration limits; a number above its limit, or missing, fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        compared[name] = {"value": v, "limit": limit}
        if v is None or not np.isfinite(v) or v > limit:
            ok = False
    return ok, compared
