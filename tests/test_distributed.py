"""SPMD shard_map backend: semantics must match the simulator backend.

Runs in a subprocess with XLA host devices so the main test session keeps a
single-device view (the dry-run is the only consumer of many devices).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import messages as M
    from repro.core import background as B
    from repro.core.distributed import (ROUTED_LIVE, make_dili_round,
                                        stack_states)
    from repro.core.shard import unpack_harvest
    from repro.core.oracle import OracleList
    from repro.core.sim import Cluster
    from repro.core.types import (DiLiConfig, OP_FIND, OP_INSERT, OP_REMOVE,
                                  RES_PENDING)

    cfg = DiLiConfig(num_shards=4, pool_capacity=1024, max_sublists=16,
                     max_ctrs=16, max_scan=1024, batch_size=8,
                     mailbox_cap=64, move_batch=4)
    CAP_PAIR = 16
    mesh = Mesh(np.array(jax.devices()).reshape(4), ("shard",))

    # borrow the simulator for initial states (registry replicas included)
    sim = Cluster(cfg)
    states, bgs = stack_states(sim.states, sim.bgs)
    rnd = make_dili_round(mesh, cfg, cap_pair=CAP_PAIR)

    inbox = jnp.zeros((4, 4 * CAP_PAIR, M.FIELDS), jnp.int32)
    oracle = OracleList()
    rng = np.random.default_rng(0)
    results = {}
    expected = {}
    slot = 0

    def client_batch(round_i):
        global slot
        rows = np.zeros((4, cfg.batch_size, M.FIELDS), np.int32)
        if round_i % 2:          # alternate load and drain rounds
            return jnp.asarray(rows)
        for s in range(4):
            for b in range(cfg.batch_size):
                kind = int(rng.choice([OP_FIND, OP_INSERT, OP_REMOVE]))
                key = int(rng.integers(1, 60))
                rows[s, b] = 0
                rows[s, b, M.F_KIND] = M.MSG_OP
                rows[s, b, M.F_DST] = s
                rows[s, b, M.F_SRC] = s
                rows[s, b, M.F_A] = kind
                rows[s, b, M.F_KEY] = key
                rows[s, b, M.F_REF1] = np.int64(0x003FFFFF).astype(np.int32)
                rows[s, b, M.F_SID] = s
                rows[s, b, M.F_TS] = slot
                expected[slot] = oracle.apply(kind, key)
                slot += 1
        return jnp.asarray(rows)

    zeros = jnp.zeros((4, cfg.batch_size, M.FIELDS), jnp.int32)
    for r in range(38):
        batch = client_batch(r) if r < 30 else zeros  # 8 drain rounds
        states, bgs, inbox, vec = rnd(states, bgs, inbox, batch)
        h = unpack_harvest(np.asarray(vec)[:, :ROUTED_LIVE], cfg)
        cs, cv = h.comp_slot, h.comp_val
        for s in range(4):
            for a, b in zip(cs[s], cv[s]):
                if a >= 0:
                    results[int(a)] = int(b)

    missing = [k for k in expected if k not in results]
    assert not missing, f"ops never completed: {missing[:10]}"
    bad = {k: (results[k], expected[k]) for k in expected
           if bool(results[k]) != expected[k]}
    assert not bad, f"mismatches: {dict(list(bad.items())[:5])}"
    print(f"OK {len(expected)} ops linearized correctly on shard_map backend")
""")


@pytest.mark.slow
def test_shard_map_backend_matches_oracle():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


def test_import_is_free_of_deprecation_warnings():
    """The SPMD backend uses the installed JAX's own ``jax.shard_map``."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-W", "error::DeprecationWarning",
                        "-c", "import repro.core.distributed"], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr


def test_shard_map_backend_names_the_devices_it_found():
    import jax

    from repro.api import ShardMapBackend
    from repro.core.types import DiLiConfig
    n = len(jax.devices())
    cfg = DiLiConfig(num_shards=n + 1, pool_capacity=256, max_sublists=8,
                     max_ctrs=8, max_scan=256, batch_size=8)
    with pytest.raises(ValueError, match=f"found {n} "
                       f"{jax.default_backend()} device"):
        ShardMapBackend(cfg)
