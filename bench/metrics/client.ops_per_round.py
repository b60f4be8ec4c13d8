"""Ops completed per round in the traced window: how full the client keeps
the rounds (admission, per-key ordering, route cache)."""


def read(rec):
    if rec.rounds <= 0:
        return None
    return rec.ops_done / rec.rounds
