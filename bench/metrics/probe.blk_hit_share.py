"""Lanes the packed-block probe answered (``blk_hits``, which both backends
count) per op completed in the traced window."""


def read(rec):
    if rec.ops_done <= 0:
        return None
    return 100.0 * rec.counters.get("blk_hits", 0) / rec.ops_done
