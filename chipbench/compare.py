"""The comparison that decides `correct`.

Every answered query of the window is checked against the plain
reference (`reference.py`), and a sample drawn from the seed is checked
for recall against the reference's exact top-k. Each number is compared
with its limit as `value <= limit`:

- `unanswered`: requests due in the window that never got an answer
  (FAILED, shed, or still missing a minute after the close). Limit 0.
- `bad_answers`: answers with an id outside the rows that were
  searchable, a repeated id, a distance that is not finite, or one that
  falls below its predecessor by more than `dist_gap`'s limit (relative):
  two distances within the comparison's own tolerance are a tie. Limit 0.
- `dist_gap`: the widest relative gap between a served distance and the
  reference's distance of the same id under the request's own p.
- `recall_deficit`: 1 - mean recall@k over the sample. Its limit is the
  configuration's stated recall floor, as 1 - floor.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference


def bad_answer_rows(ids: np.ndarray, dists: np.ndarray,
                    visible: np.ndarray, tie: float = 0.0) -> np.ndarray:
    """(B,) bool: rows whose answer breaks the answer contract; a
    distance may fall below its predecessor by `tie` (relative)."""
    ids = np.asarray(ids)
    dists = np.asarray(dists, np.float64)
    out_of_range = ((ids < 0) | (ids >= visible[:, None])).any(axis=1)
    s = np.sort(ids, axis=1)
    repeated = (s[:, 1:] == s[:, :-1]).any(axis=1)
    not_finite = ~np.isfinite(dists).all(axis=1)
    unsorted = (dists[:, 1:] < dists[:, :-1] * (1.0 - tie)).any(axis=1)
    return out_of_range | repeated | not_finite | unsorted


def dist_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest |served - ref| / ref (0 where both are 0)."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    gap = np.abs(served - ref) / np.maximum(np.abs(ref), 1e-30)
    gap = np.where((served == 0) & (ref == 0), 0.0, gap)
    return float(gap.max()) if gap.size else 0.0


def recall(ids: np.ndarray, dists: np.ndarray, true_ids: np.ndarray,
           true_dists: np.ndarray, visible: np.ndarray) -> np.ndarray:
    """(B,) recall@k against the exact top-k over the rows visible at
    admission. A returned id newer than admission (an insert that landed
    while the request waited) counts as a hit when it is no farther than
    the exact k-th distance."""
    k = true_ids.shape[1]
    hits = np.zeros(len(ids))
    for i in range(len(ids)):
        truth = set(int(v) for v in true_ids[i])
        newer = (ids[i] >= visible[i]) & (dists[i] <= true_dists[i, -1])
        hits[i] = sum(int(v) in truth for v in ids[i]) + int(newer.sum())
    return hits / k


def compare(x, queries: np.ndarray, answered: dict, unanswered: int,
            sample: list, limits: dict) -> tuple[dict, dict]:
    """(checks, extras): each check is {"value", "limit"}.

    `x` is the device corpus the reference searches (rows in id order,
    inserts included), `queries` the query pool. `answered` maps request
    id -> (pool row, p, visible rows, ids, dists); `sample` lists the
    request ids whose recall is checked. `limits` is the configuration's
    `limits`: `dist_gap` and `recall_floor`.
    """
    rids = sorted(answered)
    checks = {"unanswered": {"value": int(unanswered), "limit": 0}}
    if not rids:
        checks["bad_answers"] = {"value": 0, "limit": 0}
        return checks, {"recall": float("nan")}
    qrow = np.array([answered[r][0] for r in rids])
    ps = np.array([answered[r][1] for r in rids], np.float32)
    visible = np.array([answered[r][2] for r in rids])
    ids = np.stack([answered[r][3] for r in rids]).astype(np.int32)
    dists = np.stack([answered[r][4] for r in rids]).astype(np.float32)
    bad = bad_answer_rows(ids, dists, visible, float(limits["dist_gap"]))
    checks["bad_answers"] = {"value": int(bad.sum()), "limit": 0}
    bad_examples = [{"request": rids[i], "p": float(ps[i]),
                     "visible": int(visible[i]), "ids": ids[i].tolist(),
                     "dists": dists[i].tolist()}
                    for i in np.flatnonzero(bad)[:3]]
    ok = ~bad
    ref_d = reference.distances_of(x, queries[qrow[ok]], ps[ok], ids[ok])
    gap = dist_gap(dists[ok], ref_d)
    pos = {r: i for i, r in enumerate(rids)}
    sel = np.array([pos[r] for r in sample if r in pos], np.int64)
    k = ids.shape[1]
    # each sampled request is held to the rows visible when it was admitted
    if np.all(visible[sel] >= x.shape[0]):
        true_ids, true_d = reference.exact_topk(x, queries[qrow[sel]],
                                                ps[sel], k)
        rec = recall(ids[sel], dists[sel], true_ids, true_d, visible[sel])
    else:
        rec = _recall_visible(x, queries, qrow[sel], ps[sel], visible[sel],
                              ids[sel], dists[sel], k)
    mean_recall = float(rec.mean()) if rec.size else 0.0
    checks["dist_gap"] = {"value": gap, "limit": float(limits["dist_gap"])}
    checks["recall_deficit"] = {
        "value": 1.0 - mean_recall,
        "limit": round(1.0 - float(limits["recall_floor"]), 10)}
    return checks, {"recall": mean_recall, "sampled": int(sel.size),
                    "compared": int(ok.sum()), "bad_examples": bad_examples}


def _recall_visible(x, queries, qrow, ps, visible, ids, dists, k):
    """Recall where requests saw different corpus sizes (inserts): one
    exact search per distinct visible size."""
    rec = np.zeros(len(qrow))
    for v in np.unique(visible):
        m = visible == v
        t_ids, t_d = reference.exact_topk(x[:int(v)], queries[qrow[m]],
                                          ps[m], k)
        rec[m] = recall(ids[m], dists[m], t_ids, t_d, visible[m])
    return rec


def verdict(checks: dict) -> bool:
    """True when every number is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
