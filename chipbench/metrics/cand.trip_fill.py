"""Share of the level-0 beam loop's lane-trips in which the lane still
had work: 100 * beam_lane_trips / beam_lane_slots, the engine's counters
over the traced window. A batched loop runs every (segment, row) lane
until its slowest lane finishes; the rest is lanes idling in lockstep.
A program without the counters reports nothing."""


def read(m):
    slots = m.stats.get("beam_lane_slots", 0)
    if slots <= 0:
        return None
    return 100.0 * m.stats["beam_lane_trips"] / slots
