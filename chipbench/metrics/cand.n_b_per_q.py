"""Base-metric distances computed per query by candidate generation (N_b,
all segments), over the traced window."""


def read(m):
    if m.queries <= 0:
        return None
    return m.stats["n_b"] / m.queries
