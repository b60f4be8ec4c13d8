"""Packed blocks the round's rebuild walked, summed over the shards, per
round in the traced window (``blk_rows``, which both backends count): the
lanes of ``refresh_blocks``' compacted walk. Under 128 a round, no
shard-round took a second chunk of 128 lanes."""


def read(rec):
    if rec.rounds <= 0 or "blk_rows" not in rec.counters:
        return None
    return rec.counters["blk_rows"] / rec.rounds
