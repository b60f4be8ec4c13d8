"""Quickstart: the DiLi distributed list as a library.

Builds a 4-server cluster behind the futures-based ``DiLiClient``, loads
keys, lets the load balancer Split/Move sublists while a mixed client
workload runs, and verifies linearizability against the sequential oracle
— the paper's core claims, in ~60 lines.

The client routes each op to its key's likely owner via a client-side
registry cache (refreshed from wrong-route replies), paces admission so
overload queues client-side, and drives the balance policy from its pump
loop. Swap ``LocalBackend`` for ``ShardMapBackend`` to run the identical
workload on an SPMD device mesh.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.api import DiLiClient, LocalBackend
from repro.core.balancer import Balancer
from repro.core.oracle import OracleList
from repro.core.types import DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE
from repro.jax_cache import enable_compile_cache

enable_compile_cache()
cfg = DiLiConfig(num_shards=4, pool_capacity=8192, max_sublists=64,
                 max_ctrs=64, max_scan=8192, batch_size=32,
                 mailbox_cap=256, split_threshold=50, move_batch=16)
backend = LocalBackend(cfg)
client = DiLiClient(backend, balance=Balancer(backend))
oracle = OracleList()
rng = np.random.default_rng(0)

# ---- load phase: 800 keys (the client picks the serving shards)
keys = rng.permutation(np.arange(1, 5000))[:800].tolist()
load = client.insert_batch(keys)
oracle.apply_batch([OP_INSERT] * len(keys), keys)
client.drain(run_balance=True)

# ---- mixed phase: ops race the balancer's Split/Move churn
checks = []
for round_i in range(20):
    kinds = rng.choice([OP_FIND, OP_INSERT, OP_REMOVE], 32).tolist()
    ks = rng.integers(1, 5000, 32).tolist()
    checks.append((client.submit(kinds, ks), oracle.apply_batch(kinds, ks)))
    client.pump()      # one round; runs the balance policy at its cadence
client.settle()        # drain futures, run balance to a fixed point

# ---- verify
wrong = sum(f.result() != exp
            for batch, exps in checks for f, exp in zip(batch, exps))
assert wrong == 0, f"{wrong} ops violated linearizability"
assert all(load.results()), "load-phase inserts must all succeed"
assert client.all_keys() == sorted(oracle.snapshot())
loads = [sum(e["size"] or 0 for e in backend.sublists(s)
             if e["owner"] == s) for s in range(4)]
print(f"ops linearized correctly : {sum(len(b) for b, _ in checks) + len(keys)}")
print(f"final key count          : {len(oracle.snapshot())}")
print(f"keys per server          : {loads}")
print(f"sublists per server      : "
      f"{[sum(1 for e in backend.sublists(s) if e['owner'] == s) for s in range(4)]}")
print(f"max delegation hops seen : {client.stats['max_hops']}")
print(f"stale-route corrections  : {client.wrong_routes}")
print("OK")
