"""Elastic scaling: checkpoints restore onto a different mesh topology.

A run checkpointed on one device layout must restore bit-identically onto
another (failover re-provisioning / pod-count changes). The save path is
host-gathered numpy; the restore path applies arbitrary target shardings.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_pytree, save_pytree
from repro.configs import get_smoke_config
from repro.models import transformer as T

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.checkpoint import restore_pytree
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.runtime.sharding import param_shardings

    path = sys.argv[1]
    cfg = get_smoke_config("qwen2_5_3b").replace(
        d_model=64, n_heads=4, n_kv_heads=2)
    template = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(7),
                              dtype=jnp.float32))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    shardings = param_shardings(template, mesh)
    params = restore_pytree(template, path, shardings)
    # restored onto the 2x2 mesh with the rule-derived shardings
    leaf = params["blocks"]["attn"]["wq"]
    assert len(leaf.sharding.device_set) == 4, leaf.sharding
    # bitwise identical to the single-device original
    ref = restore_pytree(template, path)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_restore_onto_different_mesh(tmp_path):
    cfg = get_smoke_config("qwen2_5_3b").replace(
        d_model=64, n_heads=4, n_kv_heads=2)
    params = T.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    path = str(tmp_path / "elastic.npz")
    save_pytree(params, path)

    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT, path], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ELASTIC_OK" in r.stdout
