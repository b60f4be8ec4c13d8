"""``hybrid_search``'s share of its roofline over the traced window.

The least time is the lookup's bytes over the chip's HBM bandwidth (the
probe does no arithmetic to speak of). The bytes are counted as the
lookup's work, over the lanes that carried a query the lookup answered:
``blk_hits`` advanced over the traced window, which both backends count.
Each such lane reads one packed block row of ``block_cap`` int32 keys and
its int32 key, and writes two int32 outputs. The call's padding lanes
(the probe sweeps ``max(2 * batch_size, 64)`` lanes whatever rides in
them) and stage 1's sweep over ``keymin`` (the caller already holds each
lane's registry entry) are the implementation's work, not the lookup's.

On a TPU each of the lookup's Pallas calls is an op named after the
kernel (``%hybrid_search.3 = (s32[128,1,1]..., ...) custom-call(...)``).
The probe runs inside the round's gate (a ``cond``), and the profiler
shows its ops nested in the gate whenever the gate opens, so the trace
holds every call that the counter counts. The kernel time is that of all
of them, summed over the chips.
"""


def _is_kernel(name):
    return name.startswith("%hybrid_search")


def read(rec):
    tr = rec.trace
    if tr is None or "hbm_bytes_per_s" not in rec.peaks:
        return None
    secs = tr.chips * sum(s for n, s in tr.op_seconds.items()
                          if _is_kernel(n))
    lanes = rec.counters.get("blk_hits", 0)
    if secs <= 0 or lanes <= 0:
        return None
    bytes_per_lane = 4 * rec.cfg.block_cap + 4 + 2 * 4
    least = lanes * bytes_per_lane / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
