"""Host milliseconds per wave that stage B's dispatch held the engine,
from its stage clock over the traced window: eng_s_dispatch_finish *
1e3 / batches. Near a whole stage A, the one-wave lookahead overlaps
nothing. A program without the counter reports nothing."""


def read(m):
    waves = m.stats.get("batches", 0)
    if waves <= 0 or "eng_s_dispatch_finish" not in m.stats:
        return None
    return m.stats["eng_s_dispatch_finish"] * 1e3 / waves
