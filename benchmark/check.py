"""The comparison that decides ``correct``, and recall.

Each answer is one query's ``k`` (distance, id) pairs as the timed path
returned them. The numbers compared, each against its limit from
``limits/<cell>.json``:

- ``missing``: answers due in the window that never came (an error, a
  shed request, or one not back a minute after the window closed);
- ``bad_ids``: returned ids that are not rows of the base, and ids
  repeated within one answer;
- ``dist_gap``: the widest relative gap between a returned distance and
  the reference's distance of the same row to the same query,
  ``|d - d_ref| / d_ref``, over a sample of the answers drawn from the
  seed. Both configurations return exact float32 distances (a flat
  scan, or an exact re-rank), so a wrong row, a row paired with another
  query's answer, or distances computed at a lower precision all show
  here;
- ``recall_miss``: one minus the mean recall@k of every answer of the
  window against the reference's exact top-k. A select that drops
  neighbours, an index that lost rows or probes the wrong lists answer
  with true distances of the wrong rows, which only this number sees.
  For an approximate index its limit is the one the configuration
  states (``recall_at_10_floor``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: answers of a run whose distances are compared with the reference
SAMPLE_ANSWERS = 4096


def sample(n_answers: int, seed: int, size: int = SAMPLE_ANSWERS
           ) -> np.ndarray:
    """Indices of the answers to compare, drawn from the seed."""
    rng = np.random.default_rng([seed, 0xC4EC])
    if n_answers <= size:
        return np.arange(n_answers)
    return np.sort(rng.choice(n_answers, size, replace=False))


def bad_ids(ids: np.ndarray, n_rows: int) -> int:
    ids = np.asarray(ids)
    outside = int(np.sum((ids < 0) | (ids >= n_rows)))
    ordered = np.sort(ids, axis=1)
    repeated = int(np.sum((ordered[:, 1:] == ordered[:, :-1])
                          & (ordered[:, 1:] >= 0)))
    return outside + repeated


def dist_gap(dist: np.ndarray, ref_row_dist: np.ndarray) -> float:
    """Widest ``|d - d_ref| / d_ref`` over pairs whose id is a row."""
    dist = np.asarray(dist, np.float64)
    ref = np.asarray(ref_row_dist, np.float64)
    ok = np.isfinite(ref)
    if not ok.any():
        return float("inf")
    gap = np.abs(dist[ok] - ref[ok]) / np.maximum(ref[ok], 1e-30)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return float(gap.max())


def recall(ids: np.ndarray, ref_ids: np.ndarray) -> float:
    """Mean recall@k: the share of each row's reference ids found."""
    ids = np.asarray(ids)
    ref_ids = np.asarray(ref_ids)
    k = ref_ids.shape[1]
    hits = (ids[:, :, None] == ref_ids[:, None, :]).any(axis=1)
    return float(hits.sum() / (ids.shape[0] * k))


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each number beside its limit. A number that is
    not finite, or has no limit, fails."""
    checks = {}
    ok = True
    for name, value in numbers.items():
        limit = limits.get(name)
        passed = (limit is not None and np.isfinite(value)
                  and value <= limit)
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
