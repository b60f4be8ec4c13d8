"""Rows the round's serial loop executed, summed over the shards, per
round in the traced window (``serial_rows``, which both backends count):
the rows no pre-pass answered."""


def read(rec):
    if rec.rounds <= 0 or "serial_rows" not in rec.counters:
        return None
    return rec.counters["serial_rows"] / rec.rounds
