"""Device milliseconds per round in the exchange's collective, per chip:
the ``all_to_all`` that ends the SPMD round (``make_dili_round``, scope
``round.exchange``), over the traced window's rounds.

On a TPU v5e the collective is one HLO instruction named after the JAX
primitive, whose trace op reads ``%all_to_all.3 = s32[4,512,15]{...}
all-to-all(%copy.522), ...``; an asynchronous lowering would show as
``all-to-all-start`` and ``all-to-all-done``, which match too. The layout
copies around it (``%copy.*``, ``%copy_bitcast_fusion.*``) and
``bucket_by_dst``'s loop are not the collective and are left out. The
trace's op times are already averaged over the chips. A round with no
all_to_all (``LocalBackend``) reads nothing.
"""


def _is_all_to_all(name):
    head, _, rest = name.partition(" = ")
    return (head.startswith(("%all_to_all", "%all-to-all"))
            or " all-to-all(" in rest or " all-to-all-start(" in rest
            or " all-to-all-done(" in rest)


def read(rec):
    tr = rec.trace
    if tr is None or rec.rounds <= 0:
        return None
    secs = [s for n, s in tr.op_seconds.items() if _is_all_to_all(n)]
    if not secs:
        return None
    return 1e3 * sum(secs) / rec.rounds
