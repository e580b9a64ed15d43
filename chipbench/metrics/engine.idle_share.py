"""Share of the engine's time in which no wave was in flight, from its
own pipeline-state clocks over the traced window: 100 * (no work + held
+ dispatch) / (those + in flight). The engine's counterpart of
`device.idle_share`. A program without the counters reports nothing."""

IDLE = ("eng_s_idle_no_work", "eng_s_idle_held", "eng_s_idle_dispatch")


def read(m):
    if "eng_s_inflight" not in m.stats or m.stats.get("batches", 0) <= 0:
        return None
    idle = sum(m.stats[k] for k in IDLE)
    total = idle + m.stats["eng_s_inflight"]
    if total <= 0:
        return None
    return 100.0 * idle / total
