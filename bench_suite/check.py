"""The comparison that decides ``correct``.

What the timed path answered is compared with the plain reference
(:mod:`bench_suite.reference`) once the window has closed. Each number
is judged against a limit from the configuration file (``check``):

- ``dist_err``: the widest gap between a distance the system reported
  and the true distance of the id it reported with it, over every
  checked (row, rank), relative to the row's exact k-th distance. An id
  outside the base, an id twice in one row or a distance that is not
  finite reads +inf.
- ``rank_gap``: the widest gap, rank by rank, by which the true
  distances of the served ids (sorted) lie above those of the
  reference's ids, relative to the row's exact k-th distance. It reads
  0 where the served set is the exact top-k or differs by ties alone,
  and catches wrong ids reported with their true distances, which
  ``dist_err`` cannot see. A bad id reads +inf.
- ``recall``: mean share of the reference's top-k ids among the served
  ids (approximate search: the configuration's stated floor).

True distances are taken in the difference form on the device, which
has no cancellation; the reference's ids come from
:func:`reference.exact_topk` at ``Precision.HIGHEST``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from bench_suite import reference


@dataclasses.dataclass
class Answers:
    """Checked rows: pool index of each row and what was served."""
    rows: np.ndarray        # [n] pool indices
    dist: np.ndarray        # [n, k]
    ids: np.ndarray         # [n, k]


def gather(requests, n_pool: int) -> Answers:
    """The answers of every request that kept them."""
    rows, dist, ids = [], [], []
    for r in requests:
        if r.ids is None:
            continue
        rows.append((r.start + np.arange(r.rows)) % n_pool)
        dist.append(np.asarray(r.dist, np.float64))
        ids.append(np.asarray(r.ids, np.int64))
    if not rows:
        return Answers(np.zeros(0, np.int64), np.zeros((0, 0)),
                       np.zeros((0, 0), np.int64))
    return Answers(np.concatenate(rows), np.concatenate(dist),
                   np.concatenate(ids))


def reference_answers(base, pool, rows: np.ndarray, k: int,
                      precision: str = "highest"):
    """Exact top-k of the pool rows ``rows`` (each distinct row once)."""
    uniq, inv = np.unique(rows, return_inverse=True)
    d, i = reference.exact_topk(jnp.take(pool, jnp.asarray(uniq), axis=0),
                                base, k, precision)
    return d[inv], i[inv]


def numbers(base, pool, ans: Answers, ref_ids: np.ndarray,
            names: List[str]) -> Dict[str, float]:
    """Each named number of :mod:`bench_suite.check` for ``ans``."""
    n, k = ans.ids.shape
    if n == 0:
        return {name: math.nan for name in names}
    queries = jnp.take(pool, jnp.asarray(ans.rows), axis=0)
    t_srv = reference.true_distances(queries, base, ans.ids)
    t_ref = reference.true_distances(queries, base, ref_ids)
    scale = np.maximum(t_ref[:, -1:], 1e-30)
    srt = np.sort(ans.ids, axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    out = {}
    if "dist_err" in names:
        gap = np.abs(ans.dist - t_srv) / scale
        gap[~np.isfinite(gap)] = np.inf
        gap[dup] = np.inf
        out["dist_err"] = float(np.max(gap))
    if "rank_gap" in names:
        gap = (np.sort(t_srv, axis=1) - np.sort(t_ref, axis=1)) / scale
        gap[dup] = np.inf
        out["rank_gap"] = max(float(np.max(gap)), 0.0)
    if "recall" in names:
        hits = [len(np.intersect1d(a, b)) for a, b in zip(ans.ids, ref_ids)]
        out["recall"] = float(np.sum(hits)) / float(n * k)
    return out


def judge(values: Dict[str, float], limits: Dict[str, dict]) -> bool:
    """Every number inside its limit (``max`` or ``min``); NaN fails."""
    ok = True
    for name, lim in limits.items():
        v = values.get(name, math.nan)
        if "max" in lim:
            ok &= bool(v <= lim["max"])
        if "min" in lim:
            ok &= bool(v >= lim["min"])
    return ok


def limit_of(lim: dict) -> str:
    return (f"<= {lim['max']}" if "max" in lim else f">= {lim['min']}")


def evaluate(cfg: dict, base, pool, requests):
    """(values, limits, correct, answers, ref_ids) of a run's answers."""
    limits = cfg["check"]["limits"]
    ans = gather(requests, pool.shape[0])
    k = int(cfg["k"])
    if ans.rows.size:
        _, ref_ids = reference_answers(base, pool, ans.rows, k)
    else:
        ref_ids = np.zeros((0, k), np.int64)
    values = numbers(base, pool, ans, ref_ids, list(limits))
    correct = judge(values, limits) and all(r.error is None
                                            for r in requests)
    return values, limits, correct, ans, ref_ids
