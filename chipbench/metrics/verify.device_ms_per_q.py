"""Device milliseconds per query of the verification programs
(`_verify_*`: per-row-p exact re-ranking with early termination)."""

PROGRAM = r"_verify_"


def read(m):
    if m.trace is None or m.queries <= 0:
        return None
    s = m.trace.program_s(PROGRAM)
    return s * 1e3 / m.queries if s > 0 else None
