"""Candidate rows the verification kernels copied per query, over the
traced window: the engine's `verify_rows` over its `queries`. The first k
candidates' valid ids, plus the valid ids of every kappa batch in which a
query ran its abandoning scan; sentinel ids and the kernels' lane padding
copy nothing. A program without the counter reports nothing."""


def read(m):
    rows = m.stats.get("verify_rows")
    if rows is None or m.queries <= 0:
        return None
    return rows / m.queries
