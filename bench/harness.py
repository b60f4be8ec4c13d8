"""One run of one benchmark cell: build, load, warm up, measure, referee.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is a file that this module finds by the name ``BENCHMARK.json``
gives it:

  * ``bench/configs/<config>.json`` — the deployment: DiLi's static
    capacities, the backend that maps it onto chips, the store's size;
  * ``bench/traffic/<mix>.json`` — read by ``traffic.py``;
  * ``bench/metrics/<metric>.py`` — a reader with ``read(rec)``, which
    returns the metric's value from the traced run, or None.

The entry the window drives is ``DiLiClient.submit``/``pump`` over the
configuration's backend with the paper's §7.1 balancer live. The window is
closed-loop; each op's latency runs from the moment its client hands it to
the ``DiLiClient`` to the end of the pump that resolved it. After the
window the ops still in flight are drained; then the reference
(``reference.SortedSet``) replays every op the run submitted, in
submission order, and every result and the final key set are compared.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import reference, traffic, trace_reduce
from .ycsb import OP_FIND, OP_INSERT, OP_REMOVE

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 2.0          # the traced part of a --trace 1 window
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class SetupError(RuntimeError):
    """The cell cannot run here (wrong device, bad files)."""


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ files
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    mix: dict
    per_layer: List[dict]


def find_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    mix = traffic.load_mix(root / "bench" / "traffic" / f"{w['traffic']}.json")
    per_layer = [m for m in bench["per_layer"]
                 if "workloads" not in m or name in m["workloads"]]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                mix, per_layer)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def dili_config(config: dict):
    from repro.core.types import DiLiConfig
    return DiLiConfig(**config["dili"])


def make_backend(config: dict, seed: int):
    from repro.api import LocalBackend, ShardMapBackend
    cfg = dili_config(config)
    kind = config["backend"]
    if kind == "local":
        return LocalBackend(cfg, seed=seed)
    if kind == "shard_map":
        return ShardMapBackend(cfg, seed=seed)
    raise SetupError(f"unknown backend {kind!r}")


# ------------------------------------------------------------------ spans
class Spans:
    """Host spans written into the profiler's trace; free when off."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.on else contextlib.nullcontext()


class SpanBackend:
    """Delegating proxy that puts a ``backend.step`` span round each round."""

    def __init__(self, backend, spans: Spans):
        self._backend = backend
        self._spans = spans

    def step(self):
        with self._spans("backend.step"):
            return self._backend.step()

    def __getattr__(self, name):
        return getattr(self._backend, name)


class SpanPolicy:
    """The balance policy with a ``balancer.step`` span round each pass."""

    def __init__(self, policy, spans: Spans):
        self.policy = policy
        self._spans = spans

    def step(self):
        with self._spans("balancer.step"):
            return self.policy.step()


class CompileCounter:
    """Counts JAX traces and compilations while it is entered: the window
    should have none."""

    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


# ---------------------------------------------------------- closed loop
class ClosedLoop:
    """``clients`` virtual clients over one ``DiLiClient``; each takes the
    next op of the stream when its previous op resolves. Every submitted
    op is logged in submission order for the referee."""

    def __init__(self, client, stream, clients: int, spans: Spans):
        self.client = client
        self.stream = stream
        self.clients = clients
        self.spans = spans
        self.kinds: List[int] = []
        self.keys: List[int] = []
        self.futs: list = []
        self.t_sub: List[float] = []
        self.t_done: Dict[int, float] = {}
        self.out: Dict[object, int] = {}
        self._call = {OP_FIND: client.find, OP_INSERT: client.insert,
                      OP_REMOVE: client.remove}

    def submit(self, kind: int, key: int, now: float) -> None:
        fut = self._call[kind](key)
        self.out[fut] = len(self.futs)
        self.kinds.append(kind)
        self.keys.append(key)
        self.futs.append(fut)
        self.t_sub.append(now)

    def refill(self) -> None:
        now = time.perf_counter()
        while len(self.out) < self.clients:
            kind, key = next(self.stream)
            self.submit(kind, key, now)

    def pump(self, refill: bool = True) -> float:
        """One round; returns its end time."""
        with self.spans("pump"):
            self.client.pump()
        t = time.perf_counter()
        with self.spans("traffic"):
            done = [f for f in self.out if f.done]
            for f in done:
                self.t_done[self.out.pop(f)] = t
            if refill:
                self.refill()
        return t


def load_store(client, loop: ClosedLoop, keys: np.ndarray) -> None:
    """Insert ``keys`` through the client in feed-sized chunks, keeping
    its queue short, then pump until they are all resolved."""
    chunk = client.cfg.batch_size * client.backend.n
    limit = 10 * (len(keys) // client.cfg.batch_size + 1) + 2000
    i = 0
    for _ in range(limit):
        if i >= len(keys) and not client.pending:
            return
        if i < len(keys) and client.pending < 2 * client.max_inflight:
            now = time.perf_counter()
            for k in keys[i:i + chunk].tolist():
                loop.submit(OP_INSERT, k, now)
            loop.out.clear()         # loads are not closed-loop clients
            i += chunk
        client.pump()
    raise RuntimeError(f"load of {len(keys)} keys not done in {limit} "
                       f"rounds")


# ------------------------------------------------------------------ run
@dataclass
class RunRecord:
    """What a per-layer metric reader reads: counters advanced over the
    traced window, and the trace's reduction."""
    cell: Cell
    cfg: object
    rounds: int
    ops_done: int
    counters: Dict[str, int]
    trace: Optional[trace_reduce.TraceSummary]
    peaks: dict


def device_info(n_used: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:n_used]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def referee(loop: ClosedLoop) -> dict:
    """Replay every submitted op on the reference in submission order and
    compare each result; returns the reference for the key-set check."""
    ref = reference.SortedSet()
    wrong = missing = 0
    for kind, key, fut in zip(loop.kinds, loop.keys, loop.futs):
        exp = ref.apply(kind, key)
        if not fut.done:
            missing += 1
        elif fut.result(wait=False) != exp:
            wrong += 1
    return {"ref": ref, "wrong": wrong, "missing": missing}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True,
             traffic_hook: Optional[Callable] = None,
             backend_hook: Optional[Callable] = None,
             balancer_kw: Optional[dict] = None,
             config_hook: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result line's object.

    ``traffic_hook(client)`` runs as the traffic starts, before the
    warm-up, and ``backend_hook(backend)`` wraps the backend from the
    start: the tests plant faults in the timed path with them.
    ``config_hook`` edits the configuration and ``balancer_kw`` sets the
    balancer's options: the control switches a weaker path on with them.
    """
    import jax
    from repro.api import DiLiClient
    from repro.core.balancer import Balancer

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < cell.chips:
        raise SetupError(f"{cell.name} needs {cell.chips} chips, JAX found "
                         f"{len(devs)}")
    peaks = peaks_for(devs[0].device_kind) if require_tpu else {}
    seed = int(seed) % (1 << 63)
    config = cell.config if config_hook is None else config_hook(
        json.loads(json.dumps(cell.config)))
    spans = Spans(trace)
    split = {"start_s": time.perf_counter() - t_process}

    t = time.perf_counter()
    backend = make_backend(config, seed)
    if backend_hook is not None:
        backend = backend_hook(backend)
    balancer = Balancer(backend, rng=backend.balancer_rng,
                        **(balancer_kw or {}))
    client = DiLiClient(SpanBackend(backend, spans),
                        balance=SpanPolicy(balancer, spans),
                        balance_every=config["balance_every"])
    client.pump()                    # first round: compiles or loads it
    split["backend_s"] = time.perf_counter() - t

    mix = cell.mix
    key_space = config["key_space"]
    keys = traffic.load_keys(config["record_count"], key_space, seed)
    stream = traffic.op_stream(mix, key_space, seed)
    loop = ClosedLoop(client, stream, mix["clients"], spans)

    failure = None
    try:
        t = time.perf_counter()
        n_seed = config["seed_load"]
        load_store(client, loop, keys[:n_seed])
        client.settle()
        client.refresh_route_cache()
        split["seed_load_s"] = time.perf_counter() - t
        t = time.perf_counter()
        load_store(client, loop, keys[n_seed:])
        split["main_load_s"] = time.perf_counter() - t
        t = time.perf_counter()
        client.settle()
        split["settle_s"] = time.perf_counter() - t
        n_load = len(loop.futs)

        t = time.perf_counter()
        if traffic_hook is not None:
            traffic_hook(client)
        loop.refill()
        for _ in range(mix["warmup_rounds"]):
            loop.pump()
        split["warmup_s"] = time.perf_counter() - t

        window = TRACE_SECONDS if trace else float(seconds)
        window = min(window, float(seconds))
        if trace:
            tr_dir = TRACE_DIR / cell.name
            shutil.rmtree(tr_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tr_dir), profiler_options=opts)
        st0 = dict(client.stats)
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        t_end = t0
        with spans(trace_reduce.WINDOW_SPAN), CompileCounter() as compiles:
            while t_end - t0 < window:
                t_end = loop.pump()
        st1 = dict(client.stats)
        if trace:
            jax.profiler.stop_trace()
        win_s = t_end - t0
        win_ops = [i for i, td in loop.t_done.items() if t0 < td <= t_end]
        lat = np.array([loop.t_done[i] - loop.t_sub[i] for i in win_ops])
        dev = device_info(cell.chips)

        t = time.perf_counter()
        try:
            client.drain()
        except RuntimeError as e:
            failure = f"drain: {e}"
        # ops that resolved during the drain
        for f in [f for f in loop.out if f.done]:
            loop.t_done[loop.out.pop(f)] = time.perf_counter()
        drain_s = time.perf_counter() - t
    except Exception as e:       # the run broke: report it as not correct
        log(f"run failed: {type(e).__name__}: {e}")
        return _broken(loop, cell, f"{type(e).__name__}: {e}", split)

    t = time.perf_counter()
    with spans("referee"):
        rr = referee(loop)
        final = client.all_keys() if failure is None else None
    exp_keys = rr["ref"].keys()
    keys_diff = (len(set(final) ^ set(exp_keys)) if final is not None
                 else len(exp_keys))
    ref_s = time.perf_counter() - t

    checks = {"wrong_results": {"value": rr["wrong"], "limit": 0},
              "missing_results": {"value": rr["missing"], "limit": 0},
              "final_keys_differ": {"value": keys_diff, "limit": 0}}
    correct = failure is None and all(c["value"] <= c["limit"]
                                      for c in checks.values())
    split.update(drain_s=drain_s, referee_s=ref_s)
    info = {"window_s": win_s, "window_ops": len(win_ops),
            "window_rounds": st1["rounds"] - st0["rounds"],
            "compiles_in_window": compiles.count,
            "load_ops": n_load, "traffic_ops": len(loop.futs) - n_load,
            "setup_split": split, "stats": {k: int(v) for k, v in
                                            client.stats.items()}}
    log("run: " + json.dumps(info))
    if failure:
        log(f"FAILED: {failure}")

    if trace:
        tr = trace_reduce.summarize(trace_reduce.load_dir(tr_dir))
        shutil.rmtree(tr_dir, ignore_errors=True)
        # every shard-round's gate (a ``cond``) shows in the trace, and the
        # kernel's calls inside the gates that opened: compare the counts
        log("trace: " + json.dumps({
            "ops": {n[:40]: [tr.op_counts[n], s]
                    for n, s in tr.op_seconds.items()
                    if n.startswith(("%hybrid_search", "%cond."))},
            "spans": tr.span_counts}))
        rec = RunRecord(cell=cell, cfg=dili_config(config),
                        rounds=st1["rounds"] - st0["rounds"],
                        ops_done=len(win_ops),
                        counters={k: int(st1[k]) - int(st0[k])
                                  for k in st1},
                        trace=tr, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev.update(busy_s=tr.busy_s, window_s=tr.window_s)
        out = {"metrics": metrics, "device": dev,
               "breakdown": tr.breakdown()}
    else:
        metrics = {"ops_per_s": {"value": len(win_ops) / win_s,
                                 "unit": "ops/s"}}
        if len(lat):                 # no op completed: no latency
            for name, q in (("op_p50_ms", 50), ("op_p99_ms", 99)):
                metrics[name] = {"value": float(np.percentile(lat, q)) * 1e3,
                                 "unit": "ms"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        out = {"metrics": metrics, "device": dev}
    failed = rr["wrong"] + rr["missing"]
    return {"correct": bool(correct), "attempted": len(loop.futs),
            "failed": failed, **out, "checks": checks}


def _broken(loop, cell, why, split) -> dict:
    log("setup split: " + json.dumps(split))
    n = len(loop.futs)
    return {"correct": False, "attempted": n, "failed": n, "metrics": {},
            "device": device_info(cell.chips), "error": why,
            "checks": {"run_failed": {"value": 1, "limit": 0}}}
