"""What the program itself writes into a profiler trace, reduced.

``trace_reduce`` keeps the device ops and the harness's own spans. This
module keeps, besides, what the program writes into the same
``.xplane.pb``, on the same clock:

  * its host spans, named in ``PROGRAM_SPANS`` (the benchmark's own copy
    of ``repro.core.spans.SPANS``, so the yardstick does not move with
    the program): ``cluster.*`` inside ``Cluster.step``, ``shardmap.*``
    inside ``ShardMapBackend.step``, ``client.*`` inside
    ``DiLiClient.pump`` and ``balancer.*`` inside ``Balancer.step``;
  * the ``round.*`` ``jax.named_scope`` of each device op: the round's
    top-level phases in ``shard_round`` (``ROUND_SCOPES``), and
    ``round.exchange`` around the SPMD round's ``all_to_all``. A TPU
    trace's op event carries no scope: its name is the op's HLO text
    without metadata, and its stats are only device offsets and
    durations. So the scope comes from the compiled round's HLO text
    (``compiled_text``): ``scope_map`` reads each instruction's
    ``metadata={op_name="..."}``, the JAX name stack
    (``jit(shard_round)/round.serial/while/...``), and ``load_dir`` looks
    each op up by its instruction name, the head of its event name. Where
    the trace has an ``XLA Modules`` line, only ops inside a run of that
    module are looked up.

``summarize`` reduces such events over the trace's ``window`` span:

  * ``scope_seconds``: each instant of a chip's busy time goes to the
    scope of the outermost op running at that instant, so ops nested in
    a ``while`` or a ``cond`` count once and the scopes' seconds sum to
    the busy time; an op with no ``round.*`` scope counts as ``other``.
    Per chip, averaged over the chips;
  * the seconds in each program span, and the share of the harness's
    ``backend.step`` and ``balancer.step`` time that program spans cover;
  * the longest idle gaps, each named after the innermost harness or
    program span open at its middle;
  * the per-layer numbers these give, under the names a benchmark entry
    would give them.

Run as a script, it makes one ``--trace 1`` run of a cell through the
harness and prints the harness's result line and then this reduction::

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        [--slice PATH [--slice-ms MS]]

``--slice`` writes the window's events over ``--slice-ms`` from the
first ``backend.step`` after its first half second (op names cut to 48
characters) as gzipped JSON, the form of the recorded trace in
``tests/bench/``. It reads a cell on ``LocalBackend``.
"""
from __future__ import annotations

import gzip
import json
import re
from bisect import bisect_right
from pathlib import Path
from typing import Dict, List, NamedTuple, Tuple

if __package__ in (None, ""):              # run as a script
    import sys
    _root = Path(__file__).resolve().parents[1]
    sys.path[0] = str(_root)
    sys.path.insert(1, str(_root / "src"))

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import (DEVICE_LINE, HOST_SPANS, NAME_CHARS,  # noqa
                                TOP, WINDOW_SPAN, _union)

PROGRAM_SPANS = (
    "cluster.launch", "cluster.harvest", "cluster.rates", "cluster.route",
    "shardmap.launch", "shardmap.harvest", "shardmap.route",
    "client.admit", "client.resolve",
    "balancer.inspect", "balancer.plan",
)
ROUND_SCOPES = ("round.blocks", "round.range", "round.prepass",
                "round.replay", "round.replica", "round.order",
                "round.serial", "round.bg", "round.finish", "round.exchange")
OTHER = "other"
STEP_SPANS = ("backend.step", "balancer.step")
MODULE_LINE = "XLA Modules"
_SCOPE = re.compile(r"(?:^|/)(round\.[A-Za-z_]+)(?=/|$)")
_INSTR = re.compile(r'^\s*(?:ROOT )?(%\S+) = [^\n]*?op_name="([^"]*)"',
                    re.M)
_MODULE = re.compile(r"^HloModule (\S+?),", re.M)


class Event(NamedTuple):
    """``trace_reduce.Event``'s fields, and a device op's scope."""
    kind: str
    plane: str
    name: str
    start: float     # ns
    end: float       # ns
    scope: str = ""


def scope_of(stack: str) -> str:
    """The innermost ``round.*`` component of a JAX name stack."""
    found = _SCOPE.findall(stack)
    return found[-1] if found else ""


class ScopeMap(NamedTuple):
    """A compiled module's name and its instructions' scopes."""
    module: str
    scopes: Dict[str, str]     # instruction name ("%while.12") -> scope


def scope_map(hlo_text: str) -> ScopeMap:
    """The ``round.*`` scope of every instruction of a compiled module
    whose metadata names one, from its HLO text."""
    m = _MODULE.search(hlo_text)
    scopes = {}
    for name, stack in _INSTR.findall(hlo_text):
        scope = scope_of(stack)
        if scope:
            scopes[name] = scope
    return ScopeMap(m.group(1) if m else "", scopes)


def compiled_text(backend) -> str:
    """The HLO text, with metadata, of the ``shard_round`` a
    ``LocalBackend`` runs: the round compiled again for the shapes it ran
    at, which gives the instruction names it ran with (the compilation is
    deterministic, and the compilation cache returns it as it ran)."""
    import jax.numpy as jnp
    from repro.core import messages as M
    from repro.core.shard import shard_round
    cl = backend.cluster
    return shard_round.lower(
        cl.states[0], cl.bgs[0], 0,
        jnp.zeros((cl.in_cap, M.FIELDS), jnp.int32),
        jnp.zeros((0, M.FIELDS), jnp.int32), cl.cfg).compile().as_text()


def _newest(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _in_runs(t: float, runs: List[Tuple[float, float]]) -> bool:
    i = bisect_right(runs, (t, float("inf"))) - 1
    return i >= 0 and t < runs[i][1]


def load_dir(trace_dir: Path, smap: ScopeMap = ScopeMap("", {})
             ) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: device ops
    with their scope in ``smap``, the harness's spans and the program's
    spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(_newest(trace_dir)))
    wanted = set(HOST_SPANS) | set(PROGRAM_SPANS) | {WINDOW_SPAN}
    events: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            runs = None
            if MODULE_LINE in lines and smap.module:
                runs = sorted((e.start_ns, e.end_ns)
                              for e in lines[MODULE_LINE].events
                              if e.name.startswith(smap.module))
            for e in lines[DEVICE_LINE].events if DEVICE_LINE in lines \
                    else ():
                scope = smap.scopes.get(e.name.split(" ", 1)[0], "")
                if scope and runs is not None \
                        and not _in_runs(e.start_ns, runs):
                    scope = ""
                events.append(Event("device", plane.name,
                                    e.name[:NAME_CHARS], e.start_ns,
                                    e.end_ns, scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        events.append(Event("host", "host", e.name,
                                            e.start_ns, e.end_ns))
    return events


def dump_events(events: List[Event], path: Path) -> None:
    Path(path).write_bytes(gzip.compress(
        json.dumps([list(e) for e in events]).encode()))


def read_events(path: Path) -> List[Event]:
    return [Event(*e) for e in json.loads(gzip.decompress(
        Path(path).read_bytes()))]


def _window(events) -> Tuple[float, float]:
    wins = [e for e in events if e.kind == "host" and e.name == WINDOW_SPAN]
    if not wins:
        raise ValueError("the trace has no 'window' span")
    return wins[0].start, wins[0].end


def scope_seconds(events: List[Event]) -> Dict[str, float]:
    """Busy seconds per scope, per chip averaged over the chips: each
    instant goes to the outermost op running then (of the ops running at
    once, the one that started first, the longest if several did)."""
    w0, w1 = _window(events)
    planes = sorted({e.plane for e in events if e.kind == "device"})
    if not planes:
        raise ValueError("the trace has no device operations")
    out: Dict[str, float] = {}
    for p in planes:
        ops = sorted((max(e.start, w0), -min(e.end, w1), e.scope or OTHER)
                     for e in events if e.kind == "device" and e.plane == p)
        t = w0
        for a, neg_b, scope in ops:
            a, b = max(a, t), -neg_b
            if b > a:
                out[scope] = out.get(scope, 0.0) + (b - a) / 1e9
                t = b
    return {s: v / len(planes) for s, v in out.items()}


def _clipped(events, names, w0, w1):
    return [(e.name, max(e.start, w0), min(e.end, w1)) for e in events
            if e.kind == "host" and e.name in names
            and min(e.end, w1) > max(e.start, w0)]


def summarize(events: List[Event]) -> dict:
    """The reduction described in the module's docstring, as JSON."""
    w0, w1 = _window(events)
    window_s = (w1 - w0) / 1e9
    base = trace_reduce.summarize(events)
    scopes = scope_seconds(events)
    busy = sum(scopes.values())
    prog = _clipped(events, PROGRAM_SPANS, w0, w1)
    span_s: Dict[str, float] = {}
    for name, a, b in prog:
        span_s[name] = span_s.get(name, 0.0) + (b - a) / 1e9
    rounds = base.span_counts.get("backend.step", 0)

    prog_iv = _union([(a, b) for _, a, b in prog])
    covered: Dict[str, List[float]] = {}
    for name, a, b in _clipped(events, STEP_SPANS, w0, w1):
        inside = sum(max(0.0, min(b, y) - max(a, x)) for x, y in prog_iv)
        c = covered.setdefault(name, [0.0, 0.0])
        c[0] += inside / 1e9
        c[1] += (b - a) / 1e9

    dev = _union([(max(e.start, w0), min(e.end, w1)) for e in events
                  if e.kind == "device" and min(e.end, w1) > max(e.start, w0)])
    holes: List[Tuple[float, float]] = []
    t = w0
    for a, b in dev + [(w1, w1)]:
        if a > t:
            holes.append((t, a))
        t = max(t, b)
    holes.sort(key=lambda h: h[0] - h[1])
    spans = [e for e in events if e.kind == "host"
             and e.name in set(HOST_SPANS) | set(PROGRAM_SPANS)]
    gaps = []
    for a, b in holes[:TOP]:
        mid = (a + b) / 2
        open_ = [e for e in spans if e.start <= mid < e.end]
        name = (min(open_, key=lambda e: e.end - e.start).name
                if open_ else "no span")
        gaps.append([name, (b - a) / 1e9])

    def share(scope):
        return 100.0 * scopes.get(scope, 0.0) / busy if busy else None

    def per_round(*names):
        if not rounds:
            return None
        return 1e3 * sum(span_s.get(n, 0.0) for n in names) / rounds

    metrics = {
        "round.blocks_share": share("round.blocks"),
        "round.prepass_share": share("round.prepass"),
        "round.serial_share": share("round.serial"),
        "round.bg_share": share("round.bg"),
        "host.launch_ms_per_round": per_round("cluster.launch",
                                              "shardmap.launch"),
        "host.harvest_ms_per_round": per_round("cluster.harvest",
                                               "shardmap.harvest"),
        "balancer.inspect_share": (100.0 * span_s["balancer.inspect"]
                                   / window_s
                                   if "balancer.inspect" in span_s
                                   else None),
    }
    return {
        "window_s": window_s, "busy_s": busy, "chips": base.chips,
        "rounds": rounds, "metrics": metrics,
        "scope_share": {s: 100.0 * v / busy for s, v in
                        sorted(scopes.items(), key=lambda kv: -kv[1])},
        "span_ms_per_round": {n: 1e3 * v / rounds
                              for n, v in sorted(span_s.items())}
        if rounds else {},
        "step_coverage": {n: 100.0 * c[0] / c[1]
                          for n, c in covered.items() if c[1] > 0},
        "idle_gaps": gaps,
    }


def cut(events: List[Event], t0: float, ms: float,
        name_chars: int = 48) -> List[Event]:
    """The events that overlap ``[t0, t0 + ms)``, clipped to it, in a
    trace whose ``window`` span is that interval."""
    t1 = t0 + ms * 1e6
    out = [Event("host", "host", WINDOW_SPAN, 0.0, t1 - t0)]
    for e in events:
        if e.name == WINDOW_SPAN or e.end <= t0 or e.start >= t1:
            continue
        out.append(e._replace(name=e.name[:name_chars],
                              start=max(e.start, t0) - t0,
                              end=min(e.end, t1) - t0))
    return out


def main(argv=None) -> int:
    import argparse
    import os
    import time
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--slice", type=Path)
    ap.add_argument("--slice-ms", type=float, default=60.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import harness
    from repro.jax_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"compile cache: {enable_compile_cache()}")
    seen: Dict[str, object] = {}
    load = trace_reduce.load_dir

    def keep(backend):
        seen["backend"] = backend
        return backend

    def load_both(trace_dir):
        smap = scope_map(compiled_text(seen["backend"]))
        harness.log(f"scope map: {smap.module}, {len(smap.scopes)} "
                    f"instructions with a round scope")
        seen["events"] = ev = load_dir(trace_dir, smap)
        ops = [e for e in ev if e.kind == "device"]
        harness.log(f"scoped ops: {sum(1 for e in ops if e.scope)} of "
                    f"{len(ops)}")
        return load(trace_dir)

    trace_reduce.load_dir = load_both
    cell = harness.find_cell(args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds, True,
                              t_process=t_process, backend_hook=keep)
    print(json.dumps(result), flush=True)
    events = seen["events"]
    if args.slice:
        w0, _ = _window(events)
        t0 = min(e.start for e in events if e.name == "backend.step"
                 and e.start >= w0 + 0.5e9)
        dump_events(cut(events, t0, args.slice_ms), args.slice)
    print(json.dumps(summarize(events)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
