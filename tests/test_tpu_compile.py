"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler is installed even where no TPU is: these tests hand it
shapes (never arrays) on the devices of a described ``v5e:2x2`` topology and
check that Mosaic and XLA accept the programs the chip will run, at the
D400 deployment's real widths. Interpret-mode tests check none of the
Mosaic tiling rules, so this file is what guards them.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every xdist worker imports this file. All compiles stay in this file
(and so in one worker) and in the test's own process.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.datastore import QueryPred, StoreConfig, _query_step_jit, init_store
from repro.distributed import federation as fed
from repro.distributed.sharding import store_partition_specs
from repro.kernels.st_scan.st_scan import st_scan_kernel

# D400 (paper §4.4.2) at a 12 h ring: the chip_smoke.py deployment.
W1 = dict(n_edges=80, replication=3, records_per_shard=60, n_values=4,
          max_shards_per_query=512, tuple_capacity=1 << 17, max_drones=400)
Q = 8          # one query tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pred(sharding):
    f, i, b = jnp.float32, jnp.int32, jnp.bool_
    dt = (f,) * 6 + (i,) * 2 + (b,) * 4
    return QueryPred(*(_sds((Q,), d, sharding) for d in dt))


@pytest.mark.parametrize("n_ch", [1, 4])
@pytest.mark.parametrize("n_edges", [80, 20])
def test_st_scan_kernel_compiles_at_d400(topo, one_chip, no_persistent_cache,
                                         n_edges, n_ch):
    """One chip's D400 log (E=80) and one chip's share of four (E=20), at
    the 12 h ring (C = 2^17), a full query tile and S=512 OR-lists."""
    c, w, l = 1 << 17, 7, 512
    kernel = jax.jit(lambda *a: st_scan_kernel(
        *a, block_c=512, block_q=8, interpret=False,
        value_cols=tuple(range(3, 3 + n_ch))))
    args = (_sds((n_edges, w, c), jnp.float32, one_chip),
            _sds((n_edges, 2, c), jnp.int32, one_chip),
            _sds((n_edges,), jnp.int32, one_chip),
            _sds((Q, 8), jnp.float32, one_chip),
            _sds((Q, 8), jnp.int32, one_chip),
            _sds((Q, n_edges, l, 2), jnp.int32, one_chip),
            _sds((n_edges, Q, 1), jnp.int32, one_chip))
    compiled = kernel.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    outs = compiled.out_info
    assert outs[0].shape == (n_edges, Q, 1)
    assert outs[1].shape == (n_edges, n_ch, Q, 1)


def test_single_device_query_step_compiles_with_kernel(
        topo, one_chip, no_persistent_cache):
    """The facade's single-device query program at W1 shapes, with the
    compiled Pallas engine: XLA and Mosaic accept it and the kernel is in
    the program."""
    cfg = StoreConfig(**W1)
    state = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                         jax.eval_shape(lambda: init_store(cfg)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = _query_step_jit.lower(
        cfg, state, _pred(one_chip),
        _sds((cfg.n_edges,), jnp.bool_, one_chip),
        _sds(key.shape, key.dtype, one_chip), True, False, (0, 1, 2, 3)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_federated_query_compiles_on_2x2_mesh(topo, no_persistent_cache):
    """The federated query on the (fleet, edge) = (2, 2) mesh of the
    described devices: 20 edges per chip, the kernel inside shard_map."""
    mesh = jax.make_mesh((2, 2), ("fleet", "edge"), devices=topo.devices)
    cfg = StoreConfig(**W1, n_failure_domains=4)
    specs = store_partition_specs(("fleet", "edge"))
    state = jax.tree.map(
        lambda s, p: _sds(s.shape, s.dtype, NamedSharding(mesh, p)),
        jax.eval_shape(lambda: init_store(cfg)), specs)
    rep = NamedSharding(mesh, P())
    compiled = fed._query_fn(cfg, mesh, True, False, (0,)).lower(
        state, _pred(rep), _sds((cfg.n_edges,), jnp.bool_, rep),
        _sds((2,), jnp.uint32, rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "all-reduce" in text
    tup_f = compiled.input_shardings[0][0].tup_f
    assert tup_f.shard_shape(state.tup_f.shape) == (
        cfg.n_edges // 4, cfg.tuple_width, cfg.padded_capacity)
