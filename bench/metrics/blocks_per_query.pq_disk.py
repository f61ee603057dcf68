"""Slow-tier layer: I/O blocks read from the block store over the window
(``BlockSlowTier.stats()["io_blocks"]``) per answered query.  The store is
read through the OS page cache, not from an SSD."""


def read(rec):
    st = rec["slow_tier"]
    if st is None or rec["answered"] == 0:
        return None
    return st["io_blocks"] / rec["answered"]
