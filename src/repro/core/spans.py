"""Host spans the program writes into the profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation`` around one phase of a
round (``Cluster.step``, ``ShardMapBackend.step``, ``DiLiClient.pump``)
or of a balancer pass (``Balancer.step``), never around one op or one
row. It lands in the same ``.xplane.pb`` as the device's ops, on one
clock, so a gap in which the chip runs nothing can be put down to the host
phase open around it. With no profiler running, entering one costs about a
microsecond.

Every span the program opens is named in ``SPANS``; the names differ from
the spans a benchmark harness puts around the program's calls from outside
(``backend.step``, ``balancer.step``, ...), so a sum over one name never
counts the other's time.
"""
from jax.profiler import TraceAnnotation as span

SPANS = (
    "cluster.launch",      # feed and inbox building, one shard_round a shard
    "cluster.harvest",     # device-to-host pulls of each RoundOut, results
    "cluster.rates",       # per-entry op-rate and per-shard replica EWMAs
    "cluster.route",       # routing, membership, durability log, round_trace
    "shardmap.launch",     # client feed and the SPMD round's call
    "shardmap.harvest",    # round stats, op rates, completions, membership
    "shardmap.route",      # host routing of the outboxes (nemesis path)
    "client.admit",        # route-cache refresh, admission, backend.submit
    "client.resolve",      # resolving the round's completions
    "balancer.inspect",    # sublists, chain sizes, replica sets, bg tables
    "balancer.plan",       # decisions and Split/Move/Merge/replicate commands
)

__all__ = ["SPANS", "span"]
