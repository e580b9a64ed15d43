"""Device milliseconds per query of the candidate-generation programs
(`segmented_knn_search`, every segment's beams and the merge)."""

PROGRAM = r"segmented_knn_search"


def read(m):
    if m.trace is None or m.queries <= 0:
        return None
    s = m.trace.program_s(PROGRAM)
    return s * 1e3 / m.queries if s > 0 else None
