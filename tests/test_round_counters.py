"""The round's counters agree across backends, and each backend's round
writes its host spans into a profiler trace.

One seeded client workload runs on ``LocalBackend`` and on
``ShardMapBackend`` over four virtual devices, on its on-device
``all_to_all`` path and on its host-route path: the three report equal
``serial_rows`` (rows the serial loop executed) and equal fast-path
counts, all of them non-zero. The SPMD paths run in a subprocess, so the
test session keeps its single-device view.
"""
import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import json, os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    from pathlib import Path
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro.api import DiLiClient, LocalBackend, ShardMapBackend
    from repro.core.net import NemesisConfig
    from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE

    cfg = DiLiConfig(num_shards=4, pool_capacity=1024, max_sublists=16,
                     max_ctrs=16, max_scan=1024, batch_size=8,
                     mailbox_cap=64, move_batch=4, block_probe=True)

    def run(backend):
        client = DiLiClient(backend)
        rng = np.random.default_rng(0)
        client.insert_batch(rng.permutation(np.arange(1, 160))[:80].tolist())
        client.drain()
        for r in range(12):
            kinds = rng.choice([OP_FIND, OP_INSERT, OP_REMOVE], 24).tolist()
            keys = rng.integers(1, 160, 24).tolist()
            client.submit(kinds, keys)
            if r == 10:       # the last rounds under the profiler
                d = tempfile.mkdtemp()
                jax.profiler.start_trace(d)
            client.pump()
        client.drain()
        jax.profiler.stop_trace()
        pb = sorted(Path(d).rglob("*.xplane.pb"))[-1]
        names = sorted({e.name for p in ProfileData.from_file(str(pb)).planes
                        for line in p.lines for e in line.events
                        if e.name.startswith(("cluster.", "shardmap.",
                                              "client."))})
        return dict(client.stats), names

    out = {"local": run(LocalBackend(cfg, seed=0)),
           "spmd": run(ShardMapBackend(cfg, seed=0)),
           "hostroute": run(ShardMapBackend(cfg, seed=0,
                                            nemesis=NemesisConfig()))}
    print(json.dumps(out))
""")

COUNTED = ("serial_rows", "fast_hits", "mut_hits", "blk_hits")


def test_counters_agree_across_backends_and_spans_are_written():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=root,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    local, spmd, host = (out[k][0] for k in ("local", "spmd", "hostroute"))
    for name in COUNTED:
        assert local[name] > 0, (name, local)
        assert spmd[name] == local[name], (name, spmd, local)
        assert host[name] == local[name], (name, host, local)
    assert local["rounds"] == spmd["rounds"] == host["rounds"]
    client = ["client.admit", "client.resolve"]
    assert out["local"][1] == client + ["cluster.harvest", "cluster.launch",
                                        "cluster.rates", "cluster.route"]
    assert out["spmd"][1] == client + ["shardmap.harvest",
                                       "shardmap.launch"]
    assert out["hostroute"][1] == client + ["shardmap.harvest",
                                            "shardmap.launch",
                                            "shardmap.route"]
