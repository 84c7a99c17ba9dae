"""Differential test harness for the sharded federated runtime.

The core equivalence oracle: the same inserts and queries driven through the
single-device jit path (``insert_step``/``query_step``) and through the
shard_map path (``distributed.federation``) on a forced 4-host-device mesh —
parametrized over the 1-D ``(4,) ("edge",)`` layout AND the 2-D ``(2, 2)
("fleet", "edge")`` cross-host layout (hierarchical merge + double-buffered
query tiling) — must produce identical ``StoreState`` (bitwise — the sharded
path scatters the same values into the same slots) and identical
``QueryResult``/``QueryInfo``. The only tolerated difference is ``vsum`` (and
the derived ``vmean``), where the final (Q, E) combine crosses devices and
float accumulation order may differ; counts/min/max/telemetry are
order-independent and compared exactly. The same oracle is driven through the
unified ``repro.api`` facade (``AerialDB`` adopting each runtime) with
non-default ``AggSpec``s, pinning the whole generalized aggregation pipeline
— and the deprecated ``insert_step``/``query_step`` shims against it.

``tests/conftest.py`` forces ``--xla_force_host_platform_device_count=4``
before jax initializes, so the mesh is real multi-device even on CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AerialDB, AggSpec, Query
from repro.core.datastore import (StoreConfig, init_store, insert_step,
                                  make_pred, query_step)
from repro.core.placement import ShardMeta
from repro.data.synthetic import CityConfig, DroneFleet, make_sites
from repro.distributed.federation import (federated_insert_step,
                                          federated_query_step, ingest_rounds,
                                          shard_store, store_partition_specs)
from repro.distributed.sharding import mesh_edge_axes, mesh_edge_devices
from repro.launch.mesh import make_edge_mesh, make_fleet_mesh

N_DEV = 4
E = 8
ROUNDS = 6

pytestmark = pytest.mark.skipif(
    jax.device_count() < N_DEV,
    reason=f"needs {N_DEV} host devices (conftest forces them via XLA_FLAGS)")


def make_cfg(**overrides):
    sites = make_sites(E, CityConfig(), seed=3)
    kw = dict(n_edges=E, sites=tuple(map(tuple, sites.tolist())),
              tuple_capacity=2048, index_capacity=512, max_shards_per_query=64,
              records_per_shard=12, retention_every=2,
              # Latest-per-drone hot cache enabled everywhere: its replicated
              # state rides every bitwise state comparison below for free.
              max_drones=16)
    kw.update(overrides)
    return StoreConfig(**kw)


def fleet_rounds(n_drones=12, rounds=ROUNDS, records=12, seed=1):
    fleet = DroneFleet(n_drones, records_per_shard=records, seed=seed)
    return fleet.next_rounds(rounds)


def both_paths(cfg, mesh, payloads, metas, alive):
    """Drive identical inserts through both paths; returns (ref, fed) states."""
    ref = init_store(cfg)
    for i in range(payloads.shape[0]):
        meta = ShardMeta(*[jnp.asarray(np.asarray(f)[i]) for f in metas])
        ref, _ = insert_step(cfg, ref, jnp.asarray(payloads[i]), meta, alive)
    fed, _ = ingest_rounds(cfg, shard_store(init_store(cfg), mesh),
                           payloads, metas, alive, mesh=mesh)
    return ref, fed


def assert_states_identical(ref, fed):
    names = [jax.tree_util.keystr(p) for p, _
             in jax.tree_util.tree_flatten_with_path(ref)[0]]
    for name, a, b in zip(names, jax.tree.leaves(ref), jax.tree.leaves(fed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def assert_queries_identical(r1, i1, r2, i2):
    for f in r1._fields:
        a, b = np.asarray(getattr(r1, f)), np.asarray(getattr(r2, f))
        if f in ("vsum", "vmean"):  # cross-device accumulation order
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6, err_msg=f)
        else:
            np.testing.assert_array_equal(a, b, err_msg=f)
    for f in i1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(i1, f)),
                                      np.asarray(getattr(i2, f)), err_msg=f)


@pytest.fixture(scope="module", params=["edge4", "fleet2x2"])
def mesh(request):
    """Every mesh-driven test below runs on BOTH datastore layouts: the 1-D
    ``(4,) ("edge",)`` mesh and the 2-D ``(2, 2) ("fleet", "edge")`` mesh
    (hierarchical candidate merge + double-buffered query tiling) — the
    same 4 devices, two mesh contracts, one single-device oracle."""
    if request.param == "edge4":
        return make_edge_mesh(N_DEV)
    return make_fleet_mesh(2, N_DEV // 2)


@pytest.fixture(scope="module")
def loaded(mesh):
    """One store, fully loaded through both paths (shared across tests —
    queries below are read-only)."""
    cfg = make_cfg()
    alive = jnp.ones(E, bool)
    payloads, metas = fleet_rounds()
    ref, fed = both_paths(cfg, mesh, payloads, metas, alive)
    return cfg, ref, fed, alive


QUERY_PREDS = {
    "and_spatiotemporal": make_pred(
        q=3, lat0=[12.85, 12.90, 12.95], lat1=[13.10, 13.00, 13.05],
        lon0=[77.45, 77.50, 77.55], lon1=[77.75, 77.60, 77.65],
        t0=[0.0, 0.0, 60.0], t1=[1e9, 120.0, 180.0],
        has_spatial=True, has_temporal=True, is_and=True),
    "or": make_pred(q=2, lat0=12.9, lat1=12.95, lon0=77.5, lon1=77.6,
                    t0=[0.0, 30.0], t1=[60.0, 90.0],
                    has_spatial=True, has_temporal=True, is_and=False),
    "sid_point": make_pred(q=2, sid_hi=[3, 7], sid_lo=[1, 4], has_sid=True,
                           is_and=True),
    "catch_all_temporal": make_pred(q=1, t0=0.0, t1=1e9, has_temporal=True,
                                    is_and=True),
}


def test_insert_state_identical(loaded):
    """After N rounds (including retention sweeps: retention_every=2), every
    StoreState leaf — tuple ring, counters, and the whole index — is bitwise
    identical between the jit and shard_map paths."""
    _, ref, fed, _ = loaded
    assert int(np.asarray(ref.steps)) == ROUNDS  # sweeps actually ran
    assert_states_identical(ref, fed)


def test_insert_info_identical(mesh):
    """Per-step info (per-edge telemetry, replicas, retention watermark) is
    identical, round by round, including sweep rounds."""
    cfg = make_cfg()
    alive = jnp.ones(E, bool)
    payloads, metas = fleet_rounds(rounds=4)
    ref = init_store(cfg)
    fed = shard_store(init_store(cfg), mesh)
    for i in range(payloads.shape[0]):
        meta = ShardMeta(*[jnp.asarray(np.asarray(f)[i]) for f in metas])
        p = jnp.asarray(payloads[i])
        ref, ri = insert_step(cfg, ref, p, meta, alive)
        fed, fi = federated_insert_step(cfg, fed, p, meta, alive, mesh)
        for k in ri:
            np.testing.assert_array_equal(np.asarray(ri[k]), np.asarray(fi[k]),
                                          err_msg=f"round {i}: {k}")
    assert_states_identical(ref, fed)


@pytest.mark.parametrize("pred_name", sorted(QUERY_PREDS))
def test_query_identical(loaded, mesh, pred_name):
    cfg, ref, fed, alive = loaded
    pred = QUERY_PREDS[pred_name]
    key = jax.random.key(0)
    r1, i1 = query_step(cfg, ref, pred, alive, key)
    r2, i2 = federated_query_step(cfg, fed, pred, alive, key, mesh)
    assert_queries_identical(r1, i1, r2, i2)


@pytest.mark.parametrize("planner", ["random", "min_edges", "min_shards"])
def test_query_identical_across_planners(loaded, mesh, planner):
    """Planning runs replicated in the sharded path — same key, same
    assignment, identical QueryInfo (which exposes the assignment shape)."""
    cfg, ref, fed, alive = loaded
    cfg = dataclasses.replace(cfg, planner=planner)
    pred = QUERY_PREDS["and_spatiotemporal"]
    key = jax.random.key(7)
    r1, i1 = query_step(cfg, ref, pred, alive, key)
    r2, i2 = federated_query_step(cfg, fed, pred, alive, key, mesh)
    assert_queries_identical(r1, i1, r2, i2)


def test_query_identical_with_failures(loaded, mesh):
    """Edges die AFTER insertion (the paper's experiment shape — so the
    loaded store is reusable): lookup fallback, planner re-routing, and the
    scan must stay equivalent."""
    cfg, ref, fed, alive = loaded
    alive2 = alive.at[jnp.asarray([1, 5])].set(False)
    for name, pred in QUERY_PREDS.items():
        key = jax.random.key(11)
        r1, i1 = query_step(cfg, ref, pred, alive2, key)
        r2, i2 = federated_query_step(cfg, fed, pred, alive2, key, mesh)
        assert_queries_identical(r1, i1, r2, i2)


def test_query_identical_under_overflow(loaded, mesh):
    """max_shards_per_query smaller than the matched set (query-time config —
    the loaded state is layout-identical): the distributed top-S candidate
    merge must clip to exactly the same shard set and raise the same overflow
    flags as the single-device lookup."""
    cfg, ref, fed, alive = loaded
    cfg = dataclasses.replace(cfg, max_shards_per_query=4)
    pred = QUERY_PREDS["catch_all_temporal"]
    key = jax.random.key(3)
    r1, i1 = query_step(cfg, ref, pred, alive, key)
    r2, i2 = federated_query_step(cfg, fed, pred, alive, key, mesh)
    assert bool(np.asarray(r1.overflow).all())  # overflow actually exercised
    assert_queries_identical(r1, i1, r2, i2)


def test_broadcast_baseline_identical(mesh):
    """Feather-like config (no index, replication=1): the scan-all sentinel
    path through shard_map equals the jit path."""
    cfg = make_cfg(use_index=False, replication=1)
    alive = jnp.ones(E, bool)
    payloads, metas = fleet_rounds(seed=2, rounds=3)
    ref, fed = both_paths(cfg, mesh, payloads, metas, alive)
    assert_states_identical(ref, fed)
    pred = make_pred(q=1, lat0=12.9, lat1=13.0, lon0=77.5, lon1=77.65,
                     t0=0.0, t1=200.0, has_spatial=True, has_temporal=True)
    key = jax.random.key(4)
    r1, i1 = query_step(cfg, ref, pred, alive, key)
    r2, i2 = federated_query_step(cfg, fed, pred, alive, key, mesh)
    assert_queries_identical(r1, i1, r2, i2)


@pytest.mark.slow
def test_query_kernel_path_identical(loaded, mesh):
    """The Pallas st_scan kernel dispatches per-device inside shard_map; the
    sharded kernel path must equal the single-device kernel path."""
    cfg, ref, fed, alive = loaded
    pred = QUERY_PREDS["and_spatiotemporal"]
    key = jax.random.key(0)
    r1, i1 = query_step(cfg, ref, pred, alive, key, use_kernel=True,
                        interpret=True)
    r2, i2 = federated_query_step(cfg, fed, pred, alive, key, mesh,
                                  use_kernel=True, interpret=True)
    assert_queries_identical(r1, i1, r2, i2)


# ---------------------------------------------------------------------------
# Unified API facade: the same differential oracle, driven through AerialDB
# ---------------------------------------------------------------------------

AGG_SPECS = {
    "default": AggSpec(),
    "ch2_all": AggSpec(channel=2),
    "ch1_mean": AggSpec(channel=1, ops=("mean",)),
    "ch3_minmax": AggSpec(channel=3, ops=("min", "max")),
    # multi-channel: fused (Q, K) partials cross the device combine
    "multi_ch": AggSpec(channels=(0, 2, 3)),
}


@pytest.fixture(scope="module")
def loaded_facades(loaded, mesh):
    """AerialDB sessions adopting the PR-2-loaded states: one per runtime.
    The facade owns alive/key custody; explicit keys below keep the planner
    draws identical across paths."""
    cfg, ref, fed, alive = loaded
    return (AerialDB(cfg, ref, alive, jax.random.key(0)),
            AerialDB(cfg, fed, alive, jax.random.key(0), mesh=mesh))


@pytest.mark.parametrize("spec_name", sorted(AGG_SPECS))
@pytest.mark.parametrize("pred_name", sorted(QUERY_PREDS))
def test_facade_query_identical_per_aggspec(loaded_facades, spec_name,
                                            pred_name):
    """AerialDB.query with non-default AggSpecs: sharded and single-device
    results bit-identical (vsum/vmean up to cross-device accumulation
    order), for every predicate shape x channel/ops combination."""
    db_ref, db_fed = loaded_facades
    spec = AGG_SPECS[spec_name]
    key = jax.random.key(13)
    r1, i1 = db_ref.query(QUERY_PREDS[pred_name], agg=spec, key=key)
    r2, i2 = db_fed.query(QUERY_PREDS[pred_name], agg=spec, key=key)
    assert_queries_identical(r1, i1, r2, i2)


def test_facade_builder_query_identical(loaded_facades):
    """Builder-composed queries (AND/OR combinators, agg channels) through
    both runtimes — one compiled batch, identical answers."""
    db_ref, db_fed = loaded_facades
    q = Query.batch(
        Query().bbox(12.85, 13.10, 77.45, 77.75) & Query().time(0.0, 1e9),
        Query().bbox(12.9, 12.95, 77.5, 77.6) | Query().time(0.0, 60.0),
        Query().shard(3, 1).time(0.0, 1e9))
    pred, _ = q
    spec = AggSpec(channel=2, ops=("count", "mean"))
    key = jax.random.key(29)
    r1, i1 = db_ref.query((pred, spec), key=key)
    r2, i2 = db_fed.query((pred, spec), key=key)
    assert_queries_identical(r1, i1, r2, i2)
    assert set(r1.view(spec)) == {"count", "mean",
                                  "completeness_bound", "replicas_lost"}


def test_facade_ingest_and_failures_identical(mesh):
    """Full session lifecycle through the facade on both runtimes: fused
    ingest, edge failures, queries mid-failure, recovery — states bitwise
    identical and every answer equal."""
    cfg = make_cfg()
    db_ref = AerialDB.open(cfg)
    db_fed = AerialDB.open(cfg, mesh=mesh)
    payloads, metas = fleet_rounds(seed=31, rounds=4)
    db_ref.ingest_rounds(payloads, metas)
    db_fed.ingest_rounds(payloads, metas)
    assert_states_identical(db_ref.state, db_fed.state)

    db_ref.fail_edges(1, 5)
    db_fed.fail_edges(1, 5)
    q = Query().time(0.0, 1e9).agg("count", "mean", channel=1)
    key = jax.random.key(7)
    r1, i1 = db_ref.query(q, key=key)
    r2, i2 = db_fed.query(q, key=key)
    assert_queries_identical(r1, i1, r2, i2)

    # Insert while edges are down, then recover: still identical.
    p, m = DroneFleet(6, records_per_shard=12, seed=8).next_shards()
    db_ref.insert(p, m)
    db_fed.insert(p, m)
    db_ref.recover_edges(1, 5)
    db_fed.recover_edges(1, 5)
    assert_states_identical(db_ref.state, db_fed.state)
    r1, i1 = db_ref.query(q, key=key)
    r2, i2 = db_fed.query(q, key=key)
    assert_queries_identical(r1, i1, r2, i2)


def test_query_identical_whole_device_dead(loaded, mesh):
    """An ENTIRE device's edge block dies (edges 2·E/N..3·E/N): its local
    index matches, candidate contributions, and scan partials must all mask
    out identically in both runtimes, for every predicate shape."""
    cfg, ref, fed, alive = loaded
    block = jnp.arange(2 * (E // N_DEV), 3 * (E // N_DEV))
    alive2 = alive.at[block].set(False)
    for name, pred in QUERY_PREDS.items():
        key = jax.random.key(17)
        r1, i1 = query_step(cfg, ref, pred, alive2, key)
        r2, i2 = federated_query_step(cfg, fed, pred, alive2, key, mesh)
        assert_queries_identical(r1, i1, r2, i2)


def test_facade_device_failure_and_repair_identical(mesh):
    """The full failure-domain lifecycle through the facade on both
    runtimes: device failure, during-outage ingest, recovery with the
    anti-entropy repair pass — states bitwise identical and every answer
    equal at each stage (the repair pass is deterministic host-side work,
    re-sharded onto the mesh afterwards)."""
    cfg = make_cfg(n_failure_domains=N_DEV)
    db_ref = AerialDB.open(cfg)
    db_fed = AerialDB.open(cfg, mesh=mesh)
    fleet = DroneFleet(10, records_per_shard=12, seed=41)
    pay, met = fleet.next_rounds(2)
    db_ref.ingest_rounds(pay, met)
    db_fed.ingest_rounds(pay, met)

    db_ref.fail_device(1)
    db_fed.fail_device(1)
    assert int(db_ref.alive.sum()) == E - E // N_DEV
    np.testing.assert_array_equal(np.asarray(db_ref.alive),
                                  np.asarray(db_fed.alive))

    pay2, met2 = fleet.next_rounds(2)
    db_ref.ingest_rounds(pay2, met2)
    db_fed.ingest_rounds(pay2, met2)
    assert_states_identical(db_ref.state, db_fed.state)

    q = Query().time(0.0, 1e9).agg("count", "mean", channel=1)
    key = jax.random.key(19)
    r1, i1 = db_ref.query(q, key=key)
    r2, i2 = db_fed.query(q, key=key)
    assert_queries_identical(r1, i1, r2, i2)

    db_ref.recover_device(1)
    db_fed.recover_device(1)
    assert db_ref.last_repair == db_fed.last_repair
    assert db_ref.last_repair["shards_replaced"] > 0
    assert_states_identical(db_ref.state, db_fed.state)
    r1, i1 = db_ref.query(q, key=key)
    r2, i2 = db_fed.query(q, key=key)
    assert_queries_identical(r1, i1, r2, i2)
    # recovered + repaired: the full window is complete again
    total = int(np.prod(pay.shape[:3])) + int(np.prod(pay2.shape[:3]))
    assert int(np.asarray(r1.count)[0]) == total
    assert float(np.asarray(i1.completeness_bound)[0]) == 1.0


def test_shim_return_values_unchanged(loaded, mesh):
    """The deprecated insert_step/query_step shims still return exactly what
    the PR-2 harness pinned: default-AggSpec facade answers equal shim
    answers on the same loaded state, on both runtimes."""
    cfg, ref, fed, alive = loaded
    pred = QUERY_PREDS["and_spatiotemporal"]
    key = jax.random.key(0)
    r_shim, i_shim = query_step(cfg, ref, pred, alive, key)
    r_fed, i_fed = federated_query_step(cfg, fed, pred, alive, key, mesh)
    db_ref = AerialDB(cfg, ref, alive, jax.random.key(0))
    r_api, i_api = db_ref.query(pred, key=key)
    assert_queries_identical(r_shim, i_shim, r_api, i_api)
    assert_queries_identical(r_shim, i_shim, r_fed, i_fed)


def test_fused_ingest_matches_python_loop():
    """The lax.scan ingest driver (1-device) is bitwise equivalent to the
    sequential insert_step loop it replaces."""
    cfg = make_cfg()
    alive = jnp.ones(E, bool)
    payloads, metas = fleet_rounds(seed=13)
    ref = init_store(cfg)
    for i in range(payloads.shape[0]):
        meta = ShardMeta(*[jnp.asarray(np.asarray(f)[i]) for f in metas])
        ref, _ = insert_step(cfg, ref, jnp.asarray(payloads[i]), meta, alive)
    fused, info = ingest_rounds(cfg, init_store(cfg), payloads, metas, alive)
    assert_states_identical(ref, fused)
    # info is stacked over rounds
    assert np.asarray(info["intake_per_edge"]).shape == (ROUNDS, E)


def test_store_sharding_layout(mesh):
    """shard_store realizes the layout contract: leading-E arrays split into
    E/n_dev contiguous blocks, one per device (fleet-major on the 2-D mesh);
    the step counter replicates."""
    cfg = make_cfg()
    state = shard_store(init_store(cfg), mesh)
    assert len(state.tup_f.sharding.device_set) == N_DEV
    shard_shapes = {s.data.shape for s in state.tup_f.addressable_shards}
    assert shard_shapes == {(E // N_DEV,) + state.tup_f.shape[1:]}
    assert state.steps.sharding.is_fully_replicated
    axes = mesh_edge_axes(mesh)
    assert mesh_edge_devices(mesh) == N_DEV
    specs = store_partition_specs(axes)
    # Leading E dim over the axis product. PartitionSpec stores a one-axis
    # tuple as the bare axis name, so normalise before comparing.
    lead = specs.tup_f[0]
    assert (lead if isinstance(lead, tuple) else (lead,)) == axes


def test_partition_specs_congruent_with_state(mesh):
    """Property: the ``store_partition_specs`` pytree is structure-congruent
    with ``StoreState`` (including the nested ``IndexState``) under both the
    1-D and 2-D mesh contracts, and every per-edge leaf (leading logical-E
    dim) is partitioned over exactly the mesh's edge-bearing axes — so a
    future state field can't silently ship replicated-by-default or with a
    missing spec."""
    from jax.sharding import PartitionSpec as P
    cfg = make_cfg()
    state = init_store(cfg)
    axes = mesh_edge_axes(mesh)
    specs = store_partition_specs(axes)
    is_spec = lambda x: isinstance(x, P)
    assert (jax.tree.structure(specs, is_leaf=is_spec)
            == jax.tree.structure(state))
    spec_leaves = jax.tree_util.tree_flatten_with_path(specs,
                                                       is_leaf=is_spec)[0]
    for (path, spec), leaf in zip(spec_leaves, jax.tree.leaves(state)):
        name = jax.tree_util.keystr(path)
        leaf = np.asarray(leaf)
        if leaf.ndim == 0:
            assert spec == P(), name  # the one replicated scalar (steps)
            assert "steps" in name
        elif "latest" in name:
            # The latest-per-drone cache is the one replicated array family:
            # its leading dim is DRONES, and every device holds the whole
            # identically-updated copy.
            assert spec == P(), name
            assert leaf.shape[0] == cfg.max_drones, name
        else:
            assert spec == P(axes), name
            assert leaf.shape[0] == cfg.n_edges, name


def test_facade_latest_identical(loaded_facades):
    """AerialDB.latest() (and the Query().latest() dispatch): the replicated
    hot cache answers bitwise identically on the single-device and sharded
    runtimes, on both mesh layouts, and agrees with a brute-force max-t
    oracle over everything ever inserted (nothing aged out at this scale)."""
    db_ref, db_fed = loaded_facades
    l_ref = db_ref.latest()
    l_fed = db_fed.latest()
    for f in l_ref._fields:
        np.testing.assert_array_equal(np.asarray(getattr(l_ref, f)),
                                      np.asarray(getattr(l_fed, f)),
                                      err_msg=f)
    l_q = db_fed.query(Query().latest())
    for f in l_ref._fields:
        np.testing.assert_array_equal(np.asarray(getattr(l_fed, f)),
                                      np.asarray(getattr(l_q, f)), err_msg=f)
    # Against the host oracle (12 drones inserted, cache sized for 16).
    payloads, metas = fleet_rounds()
    p = np.asarray(payloads).reshape(-1, *payloads.shape[2:])   # (N*B, R, W)
    hi = np.asarray(metas.sid_hi).reshape(-1)
    rec = np.asarray(l_ref.record)
    seen = np.asarray(l_ref.valid)
    for d in range(db_ref.cfg.max_drones):
        rows = p[hi == d].reshape(-1, p.shape[-1])
        if rows.size == 0:
            assert not seen[d]
            continue
        assert seen[d]
        best = rows[np.argmax(rows[:, 0])]
        np.testing.assert_array_equal(rec[d], best)


def test_facade_latest_disabled_raises():
    db = AerialDB.open(make_cfg(max_drones=0))
    with pytest.raises(ValueError, match="max_drones"):
        db.latest()
    with pytest.raises(ValueError, match="max_drones"):
        db.query(Query().latest())


def test_mesh_divisibility_rejected(mesh):
    cfg = make_cfg(n_edges=6, sites=())
    with pytest.raises(ValueError, match="not divisible"):
        federated_query_step(cfg, init_store(cfg),
                             QUERY_PREDS["catch_all_temporal"],
                             jnp.ones(6, bool), jax.random.key(0), mesh)


def test_mesh_factories_validate_at_construction():
    """Satellite: the divisibility check moved into the mesh factories —
    both raise the shared actionable error at construction time instead of
    failing later inside the federated runtime."""
    with pytest.raises(ValueError, match="not divisible"):
        make_edge_mesh(N_DEV, n_edges=6)
    with pytest.raises(ValueError, match="not divisible"):
        make_fleet_mesh(2, N_DEV // 2, n_edges=6)
    with pytest.raises(ValueError, match="does not divide"):
        make_fleet_mesh(3)  # 3 fleets over 4 devices
    assert make_edge_mesh(N_DEV, n_edges=E).shape == {"edge": N_DEV}
    assert make_fleet_mesh(2, n_edges=E).shape == {"fleet": 2, "edge": 2}


def test_fleet_mesh_equals_edge_mesh():
    """The cross-mesh differential, stated directly: the SAME lifecycle
    (ingest -> device failure -> degraded ingest + query -> recover + repair
    -> query) on the (2, 2) fleet mesh and the (4,) 1-D mesh yields bitwise
    identical states and identical answers — the hierarchical merge and the
    double-buffered tiling change the schedule, never the result."""
    mesh_1d = make_edge_mesh(N_DEV)
    mesh_2d = make_fleet_mesh(2, N_DEV // 2)
    cfg = make_cfg(n_failure_domains=N_DEV)
    db1 = AerialDB.open(cfg, mesh=mesh_1d)
    db2 = AerialDB.open(cfg, mesh=mesh_2d)
    fleet = DroneFleet(10, records_per_shard=12, seed=43)
    pay, met = fleet.next_rounds(3)
    db1.ingest_rounds(pay, met)
    db2.ingest_rounds(pay, met)
    assert_states_identical(db1.state, db2.state)

    q = Query().time(0.0, 1e9).agg("count", "mean", channel=1)
    for db in (db1, db2):
        db.fail_device(1)
    pay2, met2 = fleet.next_rounds(1)
    db1.ingest_rounds(pay2, met2)
    db2.ingest_rounds(pay2, met2)
    key = jax.random.key(23)
    r1, i1 = db1.query(q, key=key)
    r2, i2 = db2.query(q, key=key)
    assert_queries_identical(r1, i1, r2, i2)

    db1.recover_device(1)
    db2.recover_device(1)
    assert db1.last_repair == db2.last_repair
    assert_states_identical(db1.state, db2.state)
    r1, i1 = db1.query(q, key=key)
    r2, i2 = db2.query(q, key=key)
    assert_queries_identical(r1, i1, r2, i2)
