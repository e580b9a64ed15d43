"""Share of the rows the engine executed that carried a real query:
1 - padded rows / (queries + padded rows), over the traced window."""


def read(m):
    rows = m.stats.get("queries", 0) + m.stats.get("padded_rows", 0)
    if rows <= 0:
        return None
    return 100.0 * (1.0 - m.stats["padded_rows"] / rows)
