"""Mean queue wait (admission to dispatch) of the queries answered in the
traced window, from the engine's own latency records."""


def read(m):
    recs = m.stats.get("latency_records") or []
    if not recs:
        return None
    return sum(r[1] for r in recs) / len(recs)
