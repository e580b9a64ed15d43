"""Mean dimension blocks the abandoning scan entered per verified
candidate: scan_blocks_w / n_p, the engine's counters over the traced
window. A candidate abandoned at entry counts 0 blocks and one scored
whole ceil(d / block_d), so each block past the first is one mid-scan
abandonment check. A program without the counter, or a window that
verified nothing, reports nothing."""


def read(m):
    n_p = m.stats.get("n_p", 0)
    if "scan_blocks_w" not in m.stats or n_p <= 0:
        return None
    return m.stats["scan_blocks_w"] / n_p
