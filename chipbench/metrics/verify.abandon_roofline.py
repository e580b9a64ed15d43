"""Share of the HBM roofline reached by the verification kernels (the
Pallas gather and abandoning-scan kernels).

Bytes are those the algorithm needs: every verified row's scanned
dimensions, sum(dim_frac_w) * d * 4 from the engine's counters. The least
time is bytes / peak HBM bandwidth (no peak is published for the unit
that runs a fractional-p pow, so the bytes bound it); the share is that
over the device time of the kernels' events in the trace.
"""

KERNELS = r"^%_(gather|abandon)_impl\."


def read(m):
    if m.trace is None:
        return None
    t = m.trace.op_s(KERNELS)
    nbytes = m.stats.get("dim_frac_w", 0.0) * int(m.cfg["d"]) * 4
    if t <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / m.peak("hbm_bytes_per_s") / t
