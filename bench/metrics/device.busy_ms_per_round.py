"""Device milliseconds per round: the union of a chip's operation intervals
over the traced window, averaged over the chips, per round."""


def read(rec):
    tr = rec.trace
    if tr is None or rec.rounds <= 0:
        return None
    return 1e3 * tr.busy_s / rec.rounds
