"""Chip smoke run: DiLi's client path end to end on a TPU.

DiLi's users run it as an ordered key-value index, so this drives a store
at the paper's §7.2 protocol through the public entry points — a
``DiLiClient`` over a 4-shard backend with the §7.1 balancer live (Split
and Move run under both phases):

  1. load: ``--keys`` distinct keys over a key space twice that size;
  2. mix: ``--ops`` ops of fig3a's 50%-read mix, writes split evenly
     between insert and remove, scrambled Zipfian keys at θ=0.99;
  3. scan: 100 RANGE scans of uniform length 1..100 (YCSB-E's
     ``maxscanlength``) from Zipfian start keys.

The defaults (131,072 keys, 20,000 mixed ops) are cut from a 1M-key
load (the paper's §7.2 scale) and a 200,000-op mix to fit about ten minutes
on one TPU v5e, where a round of four shards takes about 0.11 s. The load enters through the
home shard at one batch per round (about 2,050 rounds), and the mix
completes only 17-36 ops per round: the client keeps each key's ops in
order, and the hottest Zipfian key carries 7% of them.

The sequential oracle (``core/oracle.OracleList``) referees every
completed op and every scan, op for op in the client's per-key order, and
the final key set. The round must run the compiled Pallas probe
(``tpu_custom_call`` in its compiled text) and the probe must answer
lanes (``blk_hits`` > 0).

    python chip_smoke.py              # one chip: LocalBackend, 4 shards
    python chip_smoke.py --chips 4    # four chips: ShardMapBackend, one
                                      # shard per device, all_to_all routing

Information lines go first; the last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``. Exits nonzero, without that
line, on any failure — including a platform other than TPU, and
``REPRO_INTERPRET`` being set (a kernel must not run interpreted here).
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DEFAULT_KEYS = 1 << 17
N_SCANS = 100
MAX_SCAN_LEN = 100


class SmokeFailure(RuntimeError):
    """The run finished but its output is wrong."""


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def smoke_config(n_keys: int = DEFAULT_KEYS):
    """The smoke deployment: 4 shards sized to an ``n_keys`` load, the
    paper's split threshold, packed-block probe and RANGE scans on.

    The load lands on the home shard first, so each pool holds twice the
    keys; the registry (and the packed-block table it indexes) has room
    for sublists of 32 keys on average, about twice as many as a load
    split at 125 leaves. Per-round work scales with both.

    The default 2 background slots are kept: the balancer issues Splits
    before Moves, so during the load the Splits hold both slots and keep
    pace with it, and the Moves spread the sublists afterwards. With
    more slots, Moves of small sublists start mid-load, those sublists
    keep growing under the Move, and the Splits that would bound them
    stall."""
    from repro.core.types import DiLiConfig
    max_sublists = _pow2_at_least(max(64, n_keys // 32))
    return DiLiConfig(num_shards=4,
                      pool_capacity=_pow2_at_least(2 * n_keys),
                      max_sublists=max_sublists, max_ctrs=max_sublists,
                      max_scan=4096, batch_size=64, mailbox_cap=512,
                      split_threshold=125, move_batch=32,
                      block_probe=True, range_scan=True)


class _TimedPolicy:
    """Host seconds spent in the balance policy (information only)."""

    def __init__(self, policy):
        self.policy = policy
        self.seconds = 0.0

    def step(self):
        t0 = time.perf_counter()
        try:
            return self.policy.step()
        finally:
            self.seconds += time.perf_counter() - t0


def _run_phase(client, oracle, kinds, keys, log, name):
    """Submit ``kinds``/``keys`` in feed-sized chunks (keeping the client's
    queue short), pump to completion, and check every result against the
    oracle applied in submission order."""
    t0 = time.perf_counter()
    r0 = client.stats["rounds"]
    b0 = client.balance.seconds
    chunk = client.cfg.batch_size * client.backend.n
    checks = []
    i, n = 0, len(kinds)
    while i < n or client.pending:
        if i < n and client.pending < 2 * client.max_inflight:
            j = min(i + chunk, n)
            k, x = kinds[i:j].tolist(), keys[i:j].tolist()
            checks.append((client.submit(k, x), oracle.apply_batch(k, x)))
            i = j
        client.pump()
        if (client.stats["rounds"] - r0) % 500 == 0:
            log(f"{name}: {i - client.pending}/{n} done after "
                f"{client.stats['rounds'] - r0} rounds, "
                f"{time.perf_counter() - t0:.1f} s")
    r1, t1 = client.stats["rounds"], time.perf_counter()
    client.drain()
    wrong = sum(f.result(wait=False) != exp
                for batch, exps in checks for f, exp in zip(batch, exps))
    dt = time.perf_counter() - t0
    log(f"{name}: {n} ops, {client.stats['rounds'] - r0} rounds "
        f"({client.stats['rounds'] - r1} draining), {dt:.1f} s "
        f"({time.perf_counter() - t1:.1f} s draining, "
        f"{client.balance.seconds - b0:.1f} s in the balancer), "
        f"{wrong} oracle mismatches")
    if wrong:
        raise SmokeFailure(f"{name}: {wrong} ops disagree with the oracle")
    return dt


def run_workload(backend, *, n_keys: int, n_ops: int,
                 n_scans: int = N_SCANS, seed: int = 0, log=print) -> dict:
    """Drive load, mix and scan phases through a ``DiLiClient`` over
    ``backend``, refereed by the oracle. Raises ``SmokeFailure`` on any
    disagreement; returns the run's counts."""
    import numpy as np

    from repro.api import DiLiClient
    from repro.core.balancer import Balancer
    from repro.core.oracle import OracleList
    from repro.data.ycsb import load_phase, mixed_phase, zipf_keys

    key_space = 2 * n_keys
    client = DiLiClient(backend, balance=_TimedPolicy(Balancer(backend)))
    oracle = OracleList()
    times = {}

    kinds, keys = load_phase(n_keys, key_space, seed)
    times["load_s"] = _run_phase(client, oracle, kinds, keys, log, "load")
    kinds, keys = mixed_phase(n_ops, key_space, 0.5, seed=seed,
                              theta=0.99, scrambled=True)
    times["mix_s"] = _run_phase(client, oracle, kinds, keys, log, "mix")

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 2)
    starts = zipf_keys(rng, n_scans, key_space, theta=0.99, scrambled=True)
    lengths = rng.integers(1, MAX_SCAN_LEN + 1, n_scans)
    # A scan can put range_batch + 2 rows on its shard's outbox each
    # round, but DiLiClient charges that to its pacing budget only in the
    # pump that admits it, so a burst of scans overflows the outbox
    # (ROADMAP C1, whose fix waits on C2). Submit them in waves the budget
    # covers until C1 is fixed, then all at once.
    wave = max(1, client.max_inflight // (client.cfg.range_batch + 2))
    scans = []
    for w in range(0, n_scans, wave):
        scans += [client.range(int(lo), key_space + 1, limit=int(ln))
                  for lo, ln in zip(starts[w:w + wave], lengths[w:w + wave])]
        client.drain()
    snap = oracle.snapshot()
    bad = 0
    for f in scans:
        at = bisect.bisect_left(snap, f.lo)
        if f.keys(wait=False) != list(snap[at:at + f.limit]):
            bad += 1
    times["scan_s"] = time.perf_counter() - t0
    items = sum(f.count(wait=False) for f in scans)
    log(f"scan: {n_scans} scans, {items} items, "
        f"{times['scan_s']:.1f} s, {bad} oracle mismatches")
    if bad:
        raise SmokeFailure(f"scan: {bad} scans disagree with the oracle")

    final = client.all_keys()
    if final != list(snap):
        raise SmokeFailure(f"final key set: {len(final)} keys vs the "
                           f"oracle's {len(snap)}")
    st = client.stats
    counts = {k: int(st[k]) for k in ("rounds", "fast_hits", "mut_hits",
                                      "blk_hits", "range_hits", "move_hits",
                                      "delegated", "max_hops")}
    counts.update(final_keys=len(final), items_scanned=items, **times)
    log("counts: " + json.dumps(counts))
    if counts["blk_hits"] == 0:
        raise SmokeFailure("blk_hits == 0: the packed-block probe never "
                           "answered a lane")
    return counts


def _round_text(backend, cfg, chips: int) -> str:
    """Compiled text of the round the backend runs."""
    import jax.numpy as jnp
    from repro.core import messages as M
    if chips == 1:
        from repro.core.shard import shard_round
        cl = backend.cluster
        inbox = jnp.zeros((cl.in_cap, M.FIELDS), jnp.int32)
        client = jnp.zeros((0, M.FIELDS), jnp.int32)
        low = shard_round.lower(cl.states[0], cl.bgs[0], 0, inbox, client,
                                cfg)
    else:
        client = jnp.zeros((cfg.num_shards, cfg.batch_size, M.FIELDS),
                           jnp.int32)
        low = backend._rnd.lower(backend._states, backend._bgs,
                                 backend._inbox, client)
    return low.compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--keys", type=int, default=DEFAULT_KEYS,
                    help="load-phase keys (key space is twice this)")
    ap.add_argument("--ops", type=int, default=20_000,
                    help="mixed-phase ops")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(f"# {msg}", flush=True)

    if "REPRO_INTERPRET" in os.environ:
        log(f"REPRO_INTERPRET={os.environ['REPRO_INTERPRET']!r} is set: "
            f"kernels must not run interpreted on the chip")
        return 2
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"no TPU: JAX found {len(devices)} {platform} device(s)")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} TPU devices, found "
            f"{len(devices)}")
        return 2

    from repro.api import LocalBackend, ShardMapBackend
    from repro.jax_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devices[0].device_kind} x{len(devices)}; "
        f"keys={args.keys} ops={args.ops} scans={N_SCANS}")

    cfg = smoke_config(args.keys)
    t0 = time.perf_counter()
    if args.chips == 1:
        backend = LocalBackend(cfg, seed=args.seed)
    else:
        backend = ShardMapBackend(cfg, seed=args.seed)
    backend.step()
    log(f"first round (compile included): {time.perf_counter() - t0:.1f} s")
    if args.chips == 4:
        spread = backend._states.pool.key.sharding.device_set
        log(f"stacked state on {len(spread)} devices")
        if len(spread) != 4:
            log("state is not spread over 4 devices")
            return 1

    text = _round_text(backend, cfg, args.chips)
    if "tpu_custom_call" not in text:
        log("compiled round has no tpu_custom_call: the probe kernel is "
            "not compiled")
        return 1
    log("compiled round contains tpu_custom_call"
        + (", all-to-all" if "all-to-all" in text else ""))

    try:
        run_workload(backend, n_keys=args.keys, n_ops=args.ops,
                     seed=args.seed, log=log)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    log(f"total wall: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
