"""Host milliseconds the engine spent per wave outside its two waits
(stage B's dispatch and the blocking read), from its stage clocks over
the traced window: (poll + make_waves + dispatch_search + collect +
other) * 1e3 / batches. A program without the counters reports
nothing."""

WORK = ("eng_s_poll", "eng_s_make_waves", "eng_s_dispatch_search",
        "eng_s_collect", "eng_s_other")


def read(m):
    waves = m.stats.get("batches", 0)
    if waves <= 0 or any(k not in m.stats for k in WORK):
        return None
    return sum(m.stats[k] for k in WORK) * 1e3 / waves
