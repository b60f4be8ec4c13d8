"""Share of the traced window spent in the balance policy's passes
(``balancer.step`` spans)."""


def read(rec):
    tr = rec.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * tr.span_seconds.get("balancer.step", 0.0) / tr.window_s
