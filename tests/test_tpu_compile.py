"""Compile the chip's programs for a described TPU v5e — no chip needed.

Interpret mode on the CPU cannot see what Mosaic (the TPU kernel
compiler) refuses: unaligned slices, vector gathers, VMEM overruns. These
tests compile the Pallas kernels and the DiLi round at the shapes the chip
runs, for a ``v5e:2x2`` topology that is described, not attached, and
check that the kernels are really in the compiled programs
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.core import bg as B  # noqa: E402
from repro.core import messages as M  # noqa: E402
from repro.core.distributed import make_dili_round  # noqa: E402
from repro.core.shard import shard_round  # noqa: E402
from repro.core.types import init_shard  # noqa: E402
from repro.kernels.hybrid_search import hybrid_search  # noqa: E402
from repro.kernels.paged_attention import paged_attention  # noqa: E402

V5E_HBM = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), tree)


@pytest.mark.parametrize("m,c,b", [
    (256, 160, 128),        # the fig3a / zipf cells' registry and blocks
    (16384, 160, 128),      # the registry of a 1M-key load (chip_smoke)
])
def test_hybrid_search_compiles_for_v5e(one_chip, m, c, b):
    args = (_sds((m,), jnp.int32, one_chip),
            _sds((m, c), jnp.int32, one_chip),
            _sds((b,), jnp.int32, one_chip))
    compiled = jax.jit(lambda km, blk, q: hybrid_search(
        km, blk, q, interpret=False)).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    mem = compiled.memory_analysis()
    # the block table stays in HBM, read in place: no relayout copy of it
    assert mem.temp_size_in_bytes < m * c * 4


def test_paged_attention_compiles_for_v5e_at_qwen2_5_3b_widths(one_chip):
    from repro.configs import get_config
    arch = get_config("qwen2_5_3b")
    b, page, pages, pool = 8, 16, 64, 1024
    h, kh, d = arch.n_heads, arch.n_kv_heads, arch.head_dim
    args = (_sds((b, h, d), jnp.bfloat16, one_chip),
            _sds((pool, page, kh, d), jnp.bfloat16, one_chip),
            _sds((pool, page, kh, d), jnp.bfloat16, one_chip),
            _sds((b, pages), jnp.int32, one_chip),
            _sds((b,), jnp.int32, one_chip))
    compiled = jax.jit(lambda *a: paged_attention(
        *a, page_size=page, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_shard_round_compiles_for_v5e(one_chip, monkeypatch):
    """chip_smoke's round with every path a cell uses compiled in."""
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    cfg = chip_smoke.smoke_config()._replace(replication=True)
    in_cap = cfg.mailbox_cap * cfg.num_shards
    state = _on(jax.eval_shape(lambda: init_shard(cfg, 0)), one_chip)
    bg = _on(jax.eval_shape(lambda: B.init_bg_table(cfg)), one_chip)
    compiled = shard_round.lower(
        state, bg, _sds((), jnp.int32, one_chip),
        _sds((in_cap, M.FIELDS), jnp.int32, one_chip),
        _sds((0, M.FIELDS), jnp.int32, one_chip), cfg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_spmd_round_compiles_for_v5e_2x2(topo, monkeypatch):
    """The four-chip round: one shard per chip, the Pallas probe inside
    the shard-mapped body, routing by one on-device all-to-all."""
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    cfg = chip_smoke.smoke_config()
    mesh = Mesh(np.array(topo.devices).reshape(cfg.num_shards), ("shard",))
    shard = NamedSharding(mesh, P("shard"))

    def stacked(tree):
        return jax.tree_util.tree_map(
            lambda x: _sds((cfg.num_shards,) + x.shape, x.dtype, shard),
            tree)

    state = stacked(jax.eval_shape(lambda: init_shard(cfg, 0)))
    bg = stacked(jax.eval_shape(lambda: B.init_bg_table(cfg)))
    in_cap = cfg.num_shards * cfg.mailbox_cap
    inbox = _sds((cfg.num_shards, in_cap, M.FIELDS), jnp.int32, shard)
    client = _sds((cfg.num_shards, cfg.batch_size, M.FIELDS), jnp.int32,
                  shard)
    rnd = make_dili_round(mesh, cfg, cap_pair=cfg.mailbox_cap)
    text = rnd.lower(state, bg, inbox, client).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-to-all" in text
    # everything the host reads of the round is one stacked int32 array:
    # each shard's packed harvest, then the three routed counts
    one = jax.eval_shape(
        lambda: shard_round(init_shard(cfg, 0), B.init_bg_table(cfg), 0,
                            jnp.zeros((in_cap, M.FIELDS), jnp.int32),
                            jnp.zeros((cfg.batch_size, M.FIELDS),
                                      jnp.int32), cfg))
    harvest = jax.eval_shape(rnd, state, bg, inbox, client)[-1]
    assert harvest.shape == (cfg.num_shards, one.harvest.shape[0] + 3)
    assert harvest.dtype == jnp.int32
