"""Share of the traced window in which a chip runs no operation: one minus
the union of its operations' intervals over the window, averaged over the
chips."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
