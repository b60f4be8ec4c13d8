"""Host milliseconds per round in ``backend.step`` (feed, launch, harvest,
routing), from the harness's span round each call, over the traced window."""


def read(rec):
    tr = rec.trace
    n = tr.span_counts.get("backend.step", 0) if tr else 0
    if n == 0:
        return None
    return 1e3 * tr.span_seconds["backend.step"] / n
