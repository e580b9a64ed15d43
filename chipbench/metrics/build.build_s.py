"""Seconds of `UniversalVectorService.build` (partition, both base graphs
of every segment, placement), on the host clock and ending in
`block_until_ready`."""


def read(m):
    return m.build_s
