"""Unified API tests: the ``Query`` builder, ``AggSpec`` pipeline, and the
``AerialDB`` session facade.

Three layers of guarantees:
  * builder-compiled ``QueryPred``s are field-identical to hand-built
    ``make_pred`` ones (hypothesis property over random clause sets), and
    invalid shapes — inverted ranges (the historical silently-empty-result
    bug), duplicate clauses, inexpressible (A AND B) OR C — raise eagerly;
  * every ``AggSpec`` (channel x ops) agrees with a numpy oracle and between
    the jnp-ref and Pallas-kernel engines (the federated path is covered in
    tests/test_federation.py on the 4-device mesh);
  * the facade's single-device dispatch returns exactly what the deprecated
    ``insert_step``/``query_step`` shims return — adopting the facade is
    observationally free.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import AGG_OPS, AerialDB, AggSpec, Query, make_pred
from repro.core.datastore import StoreConfig, init_store, insert_step, query_step
from repro.core.index import QueryPred
from repro.core.placement import ShardMeta
from repro.data.synthetic import CityConfig, DroneFleet, make_sites
from repro.kernels.st_scan.chunked import CHUNK

E = 8


def small_cfg(**overrides):
    sites = make_sites(E, CityConfig(), seed=3)
    kw = dict(n_edges=E, sites=tuple(map(tuple, sites.tolist())),
              tuple_capacity=4096, index_capacity=512,
              max_shards_per_query=64, records_per_shard=12)
    kw.update(overrides)
    return StoreConfig(**kw)


@pytest.fixture(scope="module")
def loaded_db():
    """One facade-loaded store per module; query tests are read-only."""
    db = AerialDB.open(small_cfg())
    fleet = DroneFleet(12, records_per_shard=12, seed=5)
    payloads, metas = fleet.next_rounds(4)
    db.ingest_rounds(payloads, metas)
    flat = payloads.reshape(-1, payloads.shape[-1])
    return db, flat, metas


# ---------------------------------------------------------------------------
# Query builder: compilation equivalence + validation
# ---------------------------------------------------------------------------

def assert_preds_equal(got: QueryPred, exp: QueryPred):
    for f in QueryPred._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(exp, f)), err_msg=f)


@settings(deadline=None, max_examples=50)
@given(st.data())
def test_builder_matches_make_pred(data):
    """Property: any clause set the builder accepts compiles to exactly the
    QueryPred a hand-rolled make_pred call builds."""
    has_sp = data.draw(st.integers(0, 1), label="has_spatial")
    has_t = data.draw(st.integers(0, 1), label="has_temporal")
    has_sid = data.draw(st.integers(0, 1), label="has_sid")
    if not (has_sp or has_t or has_sid):
        has_t = 1
    n_clauses = has_sp + has_t + has_sid
    use_or = n_clauses >= 2 and data.draw(st.integers(0, 1), label="or")

    parts, kw = [], {}
    if has_sp:
        lats = sorted([data.draw(st.floats(-90, 90)) for _ in range(2)])
        lons = sorted([data.draw(st.floats(-180, 180)) for _ in range(2)])
        parts.append(Query().bbox(lats[0], lats[1], lons[0], lons[1]))
        kw.update(lat0=lats[0], lat1=lats[1], lon0=lons[0], lon1=lons[1],
                  has_spatial=True)
    if has_t:
        ts = sorted([data.draw(st.floats(0, 1e6)) for _ in range(2)])
        parts.append(Query().time(ts[0], ts[1]))
        kw.update(t0=ts[0], t1=ts[1], has_temporal=True)
    if has_sid:
        hi = data.draw(st.integers(0, 1 << 20))
        lo = data.draw(st.integers(0, 1 << 20))
        parts.append(Query().shard(hi, lo))
        kw.update(sid_hi=hi, sid_lo=lo, has_sid=True)

    combined = Query.any_of(*parts) if use_or else Query.all_of(*parts)
    got, spec = combined.build()
    exp = make_pred(q=1, is_and=not use_or, **kw)
    assert_preds_equal(got, exp)
    assert spec == AggSpec()

    # Chaining compiles identically to AND-combining.
    if not use_or:
        chained = parts[0]
        for p in parts[1:]:
            for kind in ("spatial", "temporal", "sid"):
                v = getattr(p, kind)
                if v is not None:
                    chained = chained._with_clause(kind, v)
        assert_preds_equal(chained.build()[0], exp)


def test_inverted_ranges_raise():
    """Regression: inverted ranges used to be silently accepted (empty
    results); the builder AND make_pred now raise with a clear message."""
    with pytest.raises(ValueError, match="inverted latitude"):
        Query().bbox(13.0, 12.9, 77.5, 77.6)
    with pytest.raises(ValueError, match="inverted longitude"):
        Query().bbox(12.9, 13.0, 77.6, 77.5)
    with pytest.raises(ValueError, match="inverted time"):
        Query().time(100.0, 0.0)
    with pytest.raises(ValueError, match="inverted lat range"):
        make_pred(q=1, lat0=13.0, lat1=12.9, has_spatial=True)
    with pytest.raises(ValueError, match="inverted t range"):
        make_pred(q=2, t0=[0.0, 50.0], t1=[10.0, 40.0], has_temporal=True)
    # Disabled clauses are not validated (their bounds are dead fields) ...
    make_pred(q=1, lat0=13.0, lat1=12.9, has_spatial=False)
    # ... OR predicates are exempt (an inverted clause contributes nothing
    # but the other clauses still match — the result is well-defined) ...
    make_pred(q=1, lat0=5.0, lat1=0.0, t0=0.0, t1=100.0,
              has_spatial=True, has_temporal=True, is_and=False)
    # ... and equal bounds are a valid (point) range.
    Query().time(5.0, 5.0)
    Query().bbox(12.9, 12.9, 77.5, 77.5)


def test_builder_rejects_inexpressible_shapes():
    a = Query().bbox(12.9, 13.0, 77.5, 77.6)
    b = Query().time(0.0, 60.0)
    c = Query().shard(1, 2)
    with pytest.raises(ValueError, match="already has a spatial clause"):
        a.bbox(12.0, 12.5, 77.0, 77.2)
    with pytest.raises(ValueError, match="both sides of & carry"):
        a & Query().bbox(12.0, 12.5, 77.0, 77.2)
    with pytest.raises(ValueError, match="cannot \\|-combine"):
        (a & b) | c
    with pytest.raises(ValueError, match="cannot &-combine"):
        (a | b) & c
    with pytest.raises(ValueError, match="empty query"):
        Query().build()
    with pytest.raises(TypeError, match="not a scalar"):
        Query().time([0.0, 1.0], 5.0)


def test_or_and_combinators_compile():
    a = Query().bbox(12.9, 13.0, 77.5, 77.6)
    b = Query().time(0.0, 60.0)
    p_or, _ = (a | b).build()
    assert not bool(p_or.is_and[0])
    assert bool(p_or.has_spatial[0]) and bool(p_or.has_temporal[0])
    p_and, _ = (a & b).build()
    assert bool(p_and.is_and[0])
    # any_of/all_of over three single clauses
    p3, _ = Query.any_of(a, b, Query().shard(2, 1)).build()
    assert not bool(p3.is_and[0]) and bool(p3.has_sid[0])


def test_agg_accumulates_and_validates():
    q = Query().time(0, 1).agg("count", channel=2).agg("mean", channel=2)
    assert q.spec == AggSpec(channel=2, ops=("count", "mean"))
    assert Query().time(0, 1).agg(channel=1).spec.ops == AGG_OPS
    with pytest.raises(ValueError, match="channel set is fixed"):
        Query().time(0, 1).agg("count", channel=0).agg("mean", channel=1)
    with pytest.raises(ValueError, match="unknown aggregate"):
        AggSpec(ops=("median",))
    with pytest.raises(ValueError, match="empty"):
        AggSpec(ops=())
    with pytest.raises(ValueError, match="channel=-1"):
        AggSpec(channel=-1)
    with pytest.raises(ValueError, match="share one AggSpec"):
        Query.batch(Query().time(0, 1).agg("count"),
                    Query().time(0, 1).agg("mean"))


def test_agg_multi_channel_spec():
    """channels= requests one fused scan over a static channel tuple; the
    channel set is fixed once chosen and single-channel specs are equal
    whichever spelling built them."""
    q = Query().time(0, 1).agg("count", "mean", channels=(0, 2))
    assert q.spec == AggSpec(channels=(0, 2), ops=("count", "mean"))
    assert q.spec.n_channels == 2 and q.spec.channel == 0
    # later .agg calls may add ops but not change the channel set
    assert q.agg("sum").spec.ops == ("count", "mean", "sum")
    with pytest.raises(ValueError, match="channel set is fixed"):
        q.agg("sum", channels=(1,))
    with pytest.raises(ValueError, match="not both"):
        Query().time(0, 1).agg("count", channel=1, channels=(1, 2))
    with pytest.raises(ValueError, match="duplicates"):
        AggSpec(channels=(1, 1))
    with pytest.raises(ValueError, match="not both"):
        AggSpec(channel=1, channels=(1, 2))
    assert AggSpec(channel=3) == AggSpec(channels=(3,))


def test_batch_stacks_queries():
    pred, spec = Query.batch(
        Query().time(0.0, 10.0),
        Query().bbox(12.9, 13.0, 77.5, 77.6) | Query().shard(1, 2),
        Query().shard(3, 4))
    assert pred.lat0.shape == (3,)
    np.testing.assert_array_equal(np.asarray(pred.has_temporal),
                                  [True, False, False])
    np.testing.assert_array_equal(np.asarray(pred.is_and),
                                  [True, False, True])
    np.testing.assert_array_equal(np.asarray(pred.sid_hi), [-1, 1, 3])


# ---------------------------------------------------------------------------
# AggSpec pipeline: numpy oracle + engine agreement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("channel", range(4))
def test_aggregates_match_numpy_oracle(loaded_db, channel):
    """Every aggregate of every channel equals a global numpy scan (the
    deployment replicates but must not double-count)."""
    db, flat, _ = loaded_db
    t_mid = float(np.median(flat[:, 0]))
    q = Query().time(0.0, t_mid).agg(*AGG_OPS, channel=channel)
    res, _ = db.query(q)
    m = flat[:, 0] <= t_mid
    v = flat[m, 3 + channel]
    assert int(res.count[0]) == int(m.sum())
    np.testing.assert_allclose(float(res.vsum[0]), v.sum(), rtol=1e-4)
    np.testing.assert_allclose(float(res.vmin[0]), v.min(), rtol=1e-5)
    np.testing.assert_allclose(float(res.vmax[0]), v.max(), rtol=1e-5)
    np.testing.assert_allclose(float(res.vmean[0]), v.mean(), rtol=1e-4)
    view = res.view(q.spec)
    assert set(view) == set(AGG_OPS) | {"completeness_bound",
                                        "replicas_lost"}
    np.testing.assert_array_equal(np.asarray(view["count"]),
                                  np.asarray(res.count))
    # Degradation telemetry rides in every view: fully-served query here.
    np.testing.assert_array_equal(np.asarray(view["completeness_bound"]), 1.0)
    np.testing.assert_array_equal(np.asarray(view["replicas_lost"]), 0)


def test_mean_of_empty_window_is_nan(loaded_db):
    db, flat, _ = loaded_db
    t_max = float(flat[:, 0].max())
    res, _ = db.query(Query().time(t_max + 1e6, t_max + 2e6).agg("mean"))
    assert int(res.count[0]) == 0
    assert np.isnan(float(res.vmean[0]))


def test_zero_match_min_max_are_nan_not_sentinels(loaded_db):
    """Regression: zero-match queries used to leak the scan's +inf/-inf
    accumulator sentinels into vmin/vmax; they must be NaN-masked like vmean
    — including per-channel in a multi-channel spec, and per-query in a
    mixed batch."""
    db, flat, _ = loaded_db
    t_max = float(flat[:, 0].max())
    empty = Query().time(t_max + 1e6, t_max + 2e6)
    res, _ = db.query(empty.agg("min", "max"))
    assert int(res.count[0]) == 0
    assert np.isnan(float(res.vmin[0])) and np.isnan(float(res.vmax[0]))
    assert not np.isinf(np.asarray(res.vmin)).any()
    # multi-channel: every channel column masked
    res_mc, _ = db.query(empty.agg("min", "max", channels=(0, 3)))
    assert np.isnan(np.asarray(res_mc.vmin)).all()
    assert np.isnan(np.asarray(res_mc.vmax)).all()
    # mixed batch: only the empty query's lanes are masked
    pred, spec = Query.batch(empty, Query().time(0.0, t_max))
    res_b, _ = db.query((pred, spec))
    assert np.isnan(float(res_b.vmin[0])) and np.isnan(float(res_b.vmax[0]))
    assert np.isfinite(float(res_b.vmin[1])) and int(res_b.count[1]) > 0
    # kernel engine path behaves identically
    db_k = AerialDB(db.cfg, db.state, db.alive, jax.random.key(0),
                    use_kernel=True, interpret=True)
    res_k, _ = db_k.query((pred, spec))
    assert np.isnan(float(res_k.vmin[0])) and np.isnan(float(res_k.vmax[0]))


def test_multi_channel_query_equals_k_single_channel_queries(loaded_db):
    """Tentpole acceptance: a K-channel AggSpec scans the log ONCE and its
    (Q, K) aggregates are identical to K independent single-channel queries
    — on both engines."""
    db, flat, _ = loaded_db
    channels = (0, 2, 3)
    pred, _ = Query.batch(
        Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, 1e9),
        Query().time(0.0, float(np.median(flat[:, 0]))))
    key = jax.random.key(11)
    dbs = [db, AerialDB(db.cfg, db.state, db.alive, jax.random.key(0),
                        use_kernel=True, interpret=True)]
    for session in dbs:
        multi, _ = session.query(pred, agg=AggSpec(channels=channels),
                                 key=key)
        assert multi.vsum.shape == (2, len(channels))
        for k, ch in enumerate(channels):
            single, _ = session.query(pred, agg=AggSpec(channel=ch), key=key)
            np.testing.assert_array_equal(np.asarray(multi.count),
                                          np.asarray(single.count))
            for f in ("vsum", "vmin", "vmax", "vmean"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(multi, f)[:, k]),
                    np.asarray(getattr(single, f)), err_msg=f)
    # view projects per-op (Q, K) arrays
    spec = AggSpec(channels=channels, ops=("count", "mean"))
    res, _ = db.query(pred, agg=spec, key=key)
    view = res.view(spec)
    assert set(view) == {"count", "mean",
                         "completeness_bound", "replicas_lost"}
    assert view["mean"].shape == (2, len(channels))


@pytest.mark.parametrize("channel", [0, 2, 3])
def test_agg_channels_agree_ref_vs_kernel(loaded_db, channel):
    """jnp-ref and Pallas-kernel engines agree per AggSpec: counts bitwise,
    float aggregates to accumulation order (the kernel reduces in block_c
    tiles). The federated path is covered by test_federation.py."""
    db, flat, _ = loaded_db
    spec = AggSpec(channel=channel)
    pred, _ = Query.batch(
        Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, 1e9),
        Query().time(0.0, float(np.median(flat[:, 0]))))
    key = jax.random.key(3)
    r_ref, i_ref = db.query((pred, spec), key=key)
    db_k = AerialDB(db.cfg, db.state, db.alive, jax.random.key(0),
                    use_kernel=True, interpret=True)
    r_ker, i_ker = db_k.query((pred, spec), key=key)
    np.testing.assert_array_equal(np.asarray(r_ref.count),
                                  np.asarray(r_ker.count))
    for f in ("vsum", "vmin", "vmax", "vmean"):
        np.testing.assert_allclose(np.asarray(getattr(r_ref, f)),
                                   np.asarray(getattr(r_ker, f)), rtol=1e-5,
                                   err_msg=f)
    for f in i_ref._fields:
        np.testing.assert_array_equal(np.asarray(getattr(i_ref, f)),
                                      np.asarray(getattr(i_ker, f)), err_msg=f)


def test_channel_out_of_range_raises(loaded_db):
    db, _, _ = loaded_db
    with pytest.raises(ValueError, match="channel=7 out of range"):
        db.query(Query().time(0, 1).agg("count", channel=7))


def test_long_or_lists_take_several_chunks():
    """min_edges piles a query's shards onto as few edges as it can, so one
    edge's OR-list runs past 2 * CHUNK entries and the served engine's
    membership loop takes three steps or more: the answers must still equal
    a numpy scan of every record."""
    n_edges = 4
    sites = make_sites(n_edges, CityConfig(), seed=3)
    cfg = StoreConfig(n_edges=n_edges, sites=tuple(map(tuple, sites.tolist())),
                      tuple_capacity=4096, index_capacity=512,
                      max_shards_per_query=128, records_per_shard=12,
                      planner="min_edges")
    db = AerialDB.open(cfg)
    payloads, metas = DroneFleet(16, records_per_shard=12,
                                 seed=7).next_rounds(6)
    db.ingest_rounds(payloads, metas)
    flat = payloads.reshape(-1, payloads.shape[-1])
    t_mid = float(np.median(flat[:, 0]))
    windows = [(0.0, 1e9), (0.0, t_mid), (t_mid, 1e9)]
    pred, _ = Query.batch(*(Query().time(t0, t1) for t0, t1 in windows))
    channels = (0, 3)
    res, info = db.query(pred, agg=AggSpec(channels=channels))
    assert int(np.asarray(info.max_shards_per_edge)[0]) > 2 * CHUNK
    for qi, (t0, t1) in enumerate(windows):
        m = (t0 <= flat[:, 0]) & (flat[:, 0] <= t1)
        assert int(res.count[qi]) == int(m.sum())
        for k, ch in enumerate(channels):
            v = flat[m, 3 + ch]
            np.testing.assert_allclose(float(res.vsum[qi, k]), v.sum(),
                                       rtol=1e-4)
            assert float(res.vmin[qi, k]) == v.min()
            assert float(res.vmax[qi, k]) == v.max()


# ---------------------------------------------------------------------------
# AerialDB facade: dispatch + custody + shim equivalence
# ---------------------------------------------------------------------------

def test_facade_matches_deprecated_shims():
    """Adopting the facade is observationally free: per-round states and
    query results are identical to the insert_step/query_step shims (whose
    return values are themselves pinned by the PR-2 differential harness)."""
    cfg = small_cfg()
    db = AerialDB.open(cfg)
    state = init_store(cfg)
    alive = jnp.ones(E, bool)
    fleet = DroneFleet(10, records_per_shard=12, seed=9)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for _ in range(3):
            payload, meta = fleet.next_shards()
            db.insert(payload, meta)
            state, _ = insert_step(cfg, state, jnp.asarray(payload),
                                   ShardMeta(*[jnp.asarray(f) for f in meta]),
                                   alive)
        for a, b in zip(jax.tree.leaves(db.state), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        q = Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, 1e9)
        pred, spec = q.build()
        key = jax.random.key(1)
        r1, i1 = db.query(q, key=key)
        r2, i2 = query_step(cfg, state, pred, alive, key)
    for f in r1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(r1, f)),
                                      np.asarray(getattr(r2, f)), err_msg=f)
    for f in i1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(i1, f)),
                                      np.asarray(getattr(i2, f)), err_msg=f)


def test_shims_emit_deprecation_warning():
    from repro.core import datastore
    datastore._warn_deprecated.cache_clear()
    cfg = small_cfg()
    state = init_store(cfg)
    with pytest.warns(DeprecationWarning, match="AerialDB.query"):
        query_step(cfg, state, make_pred(q=1, has_temporal=True, t1=1.0),
                   jnp.ones(E, bool), jax.random.key(0))


def test_facade_owns_key_custody(loaded_db):
    """Without an explicit key, the session splits its own: the random
    planner gets fresh keys per call, but results stay identical (replica
    choice never changes result content — only which edges answer)."""
    db, flat, _ = loaded_db
    db_rand = AerialDB(dataclasses.replace(db.cfg, planner="random"),
                       db.state, db.alive, jax.random.key(42))
    q = Query().bbox(12.85, 13.10, 77.45, 77.75).time(0.0, 1e9).agg("count")
    r1, _ = db_rand.query(q)
    r2, _ = db_rand.query(q)
    assert int(r1.count[0]) == int(r2.count[0]) == len(flat)


def test_fail_and_recover_edges():
    cfg = small_cfg()
    db = AerialDB.open(cfg)
    payloads, metas = DroneFleet(10, records_per_shard=12, seed=3).next_rounds(3)
    db.ingest_rounds(payloads, metas)
    q = Query().time(0.0, 1e9).agg("count")
    full = int(db.query(q)[0].count[0])
    assert full == payloads.shape[0] * payloads.shape[1] * payloads.shape[2]

    db.fail_edges(2, 6)
    np.testing.assert_array_equal(
        np.asarray(db.alive),
        [True, True, False, True, True, True, False, True])
    degraded, info = db.query(q)
    assert int(degraded.count[0]) <= full  # replication may or may not cover

    db.recover_edges([2, 6])               # list form also accepted
    assert bool(np.asarray(db.alive).all())
    assert int(db.query(q)[0].count[0]) == full


def test_facade_open_overrides_and_bad_query_type():
    db = AerialDB.open(small_cfg(), tuple_capacity=1024)
    assert db.cfg.tuple_capacity == 1024
    with pytest.raises(TypeError, match="cannot query with"):
        db.query({"not": "a query"})
    with pytest.raises(ValueError, match="not both"):
        db.query(Query().time(0, 1).agg("count"), agg=AggSpec())
