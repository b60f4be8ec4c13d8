"""Live rows the SPMD round's ``all_to_all`` delivered, summed over the
chips, per round in the traced window: the ``routed_rows`` counter of
``ShardMapBackend`` (delegated ops, replies and the background engine's
messages). A program without the counter reads nothing."""


def read(rec):
    if rec.rounds <= 0 or "routed_rows" not in rec.counters:
        return None
    return rec.counters["routed_rows"] / rec.rounds
