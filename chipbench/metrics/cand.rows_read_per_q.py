"""Corpus rows the level-0 beam loop read per query (all segments), over
the traced window: the engine's `beam_rows_read` over its `queries`. Every
frontier row of every trip where the loop gathers the frontier whole, only
the rows its visited test marks new where it fetches them by kernel. A
program without the counter reports nothing."""


def read(m):
    rows = m.stats.get("beam_rows_read")
    if rows is None or m.queries <= 0:
        return None
    return rows / m.queries
