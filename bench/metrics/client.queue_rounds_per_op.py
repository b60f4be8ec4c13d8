"""Rounds an op waited in the client before admission, per op completed in
the traced window: ``queue_rounds`` (the client's queue length after each
admission, summed) advanced over the ops completed. The queue holds an op
for its key's order, the pacing budget or the per-shard feed bound."""


def read(rec):
    if rec.ops_done <= 0 or "queue_rounds" not in rec.counters:
        return None
    return rec.counters["queue_rounds"] / rec.ops_done
