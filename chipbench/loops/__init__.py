"""Load generators, one module per loop kind, found by the mix's `loop`."""
