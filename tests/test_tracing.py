"""The program's own trace names: device scopes and host spans.

Three contracts:

1. Every phase scope of ``query_local`` / ``finalize_query`` /
   ``insert_local`` (``query.*``, ``insert.*``) names ops of the lowered
   single-device programs and of the federated programs on the ``(4,)``
   mesh of virtual devices — the names a profiler trace carries as each
   op's ``op_name``. The federation's exchange scopes (``query.exchange``,
   ``insert.exchange``) name every all-gather of the federated programs and
   nothing of the single-device ones, and change no program but in its
   metadata.
2. A CPU profiler trace of one ``AerialDB.query``, one
   ``IngestPipeline.flush`` and one ``IngestPipeline.latest`` holds the
   ``aerialdb.*`` host spans, nested as documented, with the sequence tags;
   on the ``(4,)`` mesh the federated steps' ``aerialdb.fed.*`` spans too.
3. Tracing is observation only: answers and stored state are bit-identical
   with a trace running and without one.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import AerialDB, AggSpec
from repro.core.datastore import (StoreConfig, _insert_step_jit,
                                  _query_step_jit, init_store, make_pred)
from repro.core.placement import ShardMeta
from repro.data.synthetic import CityConfig, make_sites
from repro.distributed import federation as fed
from repro.ingest import IngestPipeline
from repro.launch.mesh import make_edge_mesh

E, R, D = 8, 4, 8
QUERY_SCOPES = ["query.lookup", "query.plan", "query.orlist", "query.scan",
                "query.combine"]
INSERT_SCOPES = ["insert.place", "insert.ring", "insert.retire",
                 "insert.index", "insert.latest"]


def _cfg(**kw):
    sites = make_sites(E, CityConfig(), seed=3)
    base = dict(n_edges=E, sites=tuple(map(tuple, sites.tolist())),
                tuple_capacity=1024, index_capacity=256,
                max_shards_per_query=32, records_per_shard=R, n_values=2,
                retention_every=2, max_drones=D)
    base.update(kw)
    return StoreConfig(**base)


def _pred(q=2):
    return make_pred(q=q, lat0=12.8, lat1=13.2, lon0=77.4, lon1=77.8,
                     t0=0.0, t1=1e6, has_spatial=True, has_temporal=True)


def _batch(b=4, t0=0.0):
    """B shards of R records, drones 0..B-1, one shard each."""
    drone = np.arange(b)
    t = t0 + np.arange(R)[None, :] * 5.0 + drone[:, None]
    pay = np.zeros((b, R, 5), np.float32)
    pay[..., 0] = t
    pay[..., 1] = 12.9 + 0.01 * drone[:, None]
    pay[..., 2] = 77.5 + 0.01 * drone[:, None]
    pay[..., 3] = drone[:, None]
    pay[..., 4] = np.arange(R)[None, :]
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    meta = ShardMeta(
        sid_hi=jnp.asarray(drone, jnp.int32),
        sid_lo=jnp.asarray(np.full(b, int(t0) // 100), jnp.int32),
        lat0=f32(pay[:, :, 1].min(1)), lat1=f32(pay[:, :, 1].max(1)),
        lon0=f32(pay[:, :, 2].min(1)), lon1=f32(pay[:, :, 2].max(1)),
        t0=f32(pay[:, :, 0].min(1)), t1=f32(pay[:, :, 0].max(1)))
    return jnp.asarray(pay), meta


def _op_scopes(lowered) -> set:
    """Path components of every op name (``loc("jit(f)/a/b/op")``) in a
    lowered program."""
    text = lowered.as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*/[^"]*)"', text)
    return {part for n in names for part in n.split("/")}


def _lowered(program: str):
    """One program lowered afresh at the tiny size: ``single_query``,
    ``single_insert`` (one device) or ``fed_query``, ``fed_insert``,
    ``fed_ingest_rounds`` (the ``(4,)`` mesh of virtual devices)."""
    pay, meta = _batch()
    alive = jnp.ones(E, bool)
    if program.startswith("single"):
        cfg = _cfg()
        state = init_store(cfg)
        if program == "single_query":
            return _query_step_jit.lower(cfg, state, _pred(), alive,
                                         jax.random.key(0), False, None,
                                         (0, 1))
        return _insert_step_jit.lower(cfg, state, pay, meta, alive)
    mesh = make_edge_mesh(4, n_edges=E)
    cfg = _cfg(n_failure_domains=4)
    state = fed.shard_store(init_store(cfg), mesh)
    if program == "fed_query":
        return fed._query_fn(cfg, mesh, False, None, (0, 1)).lower(
            state, _pred(), alive, jax.random.key_data(jax.random.key(0)))
    if program == "fed_insert":
        return fed._insert_fn(cfg, mesh).lower(state, pay, meta, alive)
    pays = jnp.stack([pay, pay])
    metas = ShardMeta(*(jnp.stack([f, f]) for f in meta))
    return fed._ingest_fn(cfg, mesh).lower(state, pays, metas, alive)


@pytest.fixture(scope="module")
def single_scopes():
    return {"query": _op_scopes(_lowered("single_query")),
            "insert": _op_scopes(_lowered("single_insert"))}


@pytest.fixture(scope="module")
def federated_scopes():
    return {"query": _op_scopes(_lowered("fed_query")),
            "insert": _op_scopes(_lowered("fed_insert")),
            "ingest_rounds": _op_scopes(_lowered("fed_ingest_rounds"))}


@pytest.mark.parametrize("scope", QUERY_SCOPES)
def test_single_device_query_program_names_its_phases(single_scopes, scope):
    assert scope in single_scopes["query"]


@pytest.mark.parametrize("scope", INSERT_SCOPES)
def test_single_device_insert_program_names_its_phases(single_scopes, scope):
    assert scope in single_scopes["insert"]


@pytest.mark.parametrize("scope",
                         QUERY_SCOPES + ["query.merge", "query.exchange"])
def test_federated_query_program_names_its_phases(federated_scopes, scope):
    assert scope in federated_scopes["query"]


@pytest.mark.parametrize("program", ["insert", "ingest_rounds"])
@pytest.mark.parametrize("scope", INSERT_SCOPES + ["insert.exchange"])
def test_federated_insert_programs_name_their_phases(federated_scopes,
                                                     program, scope):
    assert scope in federated_scopes[program]


def _all_gather_op_names(lowered) -> list:
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.split("\n") if " all-gather(" in line]


@pytest.mark.parametrize("program, scope, n", [
    ("fed_query", "query.exchange", 5),   # four candidate lists, overflow
    ("fed_insert", "insert.exchange", 1),  # the retention watermark
])
def test_every_all_gather_runs_in_the_exchange_scope(program, scope, n):
    names = _all_gather_op_names(_lowered(program))
    assert len(names) == n
    assert all(scope in name.split("/") for name in names), names


@pytest.fixture
def fresh_programs():
    """Every jitted program traced anew, before and after the test."""
    def clear():
        for fn in (fed._query_fn, fed._insert_fn, fed._ingest_fn):
            fn.cache_clear()
        jax.clear_caches()
    clear()
    yield
    clear()


@pytest.mark.parametrize("program", ["single_query", "single_insert",
                                     "fed_query", "fed_insert"])
def test_exchange_scopes_change_only_metadata(program, fresh_programs,
                                              monkeypatch):
    """Each program lowered with the exchange scopes and with them taken
    out is the same program: the single-device ones carry neither scope and
    name the same ops, the federated ones differ in op names alone."""
    scoped = _lowered(program)
    names = _op_scopes(scoped)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name.endswith(".exchange") else real(name))
    for fn in (fed._query_fn, fed._insert_fn):
        fn.cache_clear()
    jax.clear_caches()
    plain = _lowered(program)
    assert scoped.as_text() == plain.as_text()
    exchange = {"query.exchange", "insert.exchange"} & names
    if program.startswith("single"):
        assert not exchange
        assert names == _op_scopes(plain)
    else:
        assert exchange and not exchange & _op_scopes(plain)


@pytest.mark.parametrize("jitted, name", [
    (lambda cfg, mesh: fed._insert_fn(cfg, mesh), "fed_insert"),
    (lambda cfg, mesh: fed._ingest_fn(cfg, mesh), "fed_ingest_rounds"),
    (lambda cfg, mesh: fed._ingest_fn(cfg, None), "ingest_rounds"),
    (lambda cfg, mesh: fed._query_fn(cfg, mesh, False, None, (0,)),
     "fed_query"),
])
def test_federated_jits_have_stable_names(jitted, name):
    mesh = make_edge_mesh(4, n_edges=E)
    assert jitted(_cfg(n_failure_domains=4), mesh).__name__ == name


# -- host spans --------------------------------------------------------------

def _host_spans(trace_dir) -> list:
    """``[(start, end, name, stats)]`` of the ``aerialdb.*`` host events."""
    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                     dict(ev.stats)) for ev in line.events
                    if ev.name.startswith("aerialdb.")]
    return sorted(out)


def _inside(child, parent) -> bool:
    return parent[0] <= child[0] and child[1] <= parent[1]


def _served(db, pipe, t0):
    """One flush of two shards' records, one window query, one live read;
    returns the answers."""
    pay, _ = _batch(b=2, t0=t0)
    rows = np.asarray(pay).reshape(-1, 5)
    drone = np.repeat(np.arange(2), R)
    seq = np.tile(np.arange(R), 2) + int(t0)
    pipe.submit_arrays(drone, seq, rows[:, 0], rows[:, 1], rows[:, 2],
                       rows[:, 3:])
    assert pipe.flush()["flushed_shards"] == 2
    res, _ = db.query(_pred(), agg=AggSpec(channels=(0, 1)))
    return jax.device_get(res), pipe.latest()


@pytest.fixture(scope="module")
def traced(request, tmp_path_factory):
    """Host spans of a warm session's flush, query and live read: on one
    device, or on the ``(4,)`` mesh where the parameter is ``"fed4"``."""
    if getattr(request, "param", None) == "fed4":
        db = AerialDB.open(_cfg(n_failure_domains=4), seed=5,
                           mesh=make_edge_mesh(4, n_edges=E))
    else:
        db = AerialDB.open(_cfg(), seed=5)
    pipe = IngestPipeline(db, batch_shards=4)
    _served(db, pipe, 0.0)                  # compile outside the trace
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    _served(db, pipe, 1000.0)
    jax.profiler.stop_trace()
    return _host_spans(trace_dir)


def _one(spans, name, **stats):
    """The one span named ``name`` whose stats hold ``stats``."""
    found = [s for s in spans
             if s[2] == name and stats.items() <= s[3].items()]
    assert len(found) == 1, (name, [s[2] for s in spans])
    return found[0]


NEST = [("aerialdb.query.prepare", "aerialdb.query"),
        ("aerialdb.query.dispatch", "aerialdb.query"),
        ("aerialdb.ingest.coalesce", "aerialdb.ingest.flush"),
        ("aerialdb.ingest.dispatch", "aerialdb.ingest.flush"),
        ("aerialdb.ingest.block", "aerialdb.ingest.flush"),
        ("aerialdb.insert", "aerialdb.ingest.dispatch")]
# The federated steps' spans, each tagged with its program, inside the
# facade's span of the call.
FED_NEST = [(f"aerialdb.fed.{part}", program, parent)
            for program, parent in [("fed_query", "aerialdb.query.dispatch"),
                                    ("fed_insert", "aerialdb.insert")]
            for part in ("check", "call")]


@pytest.mark.parametrize("traced, child, parent", [
    *[pytest.param(None, c, p, id=f"{c}-{p}") for c, p in NEST],
    *[pytest.param("fed4", c, p, id=f"fed4-{c}-{p}") for c, p in NEST],
    *[pytest.param("fed4", (c, prog), p, id=f"fed4-{c}-{prog}-{p}")
      for c, prog, p in FED_NEST],
], indirect=["traced"])
def test_host_spans_nest(traced, child, parent):
    name, program = child if isinstance(child, tuple) else (child, None)
    tag = {"program": program} if program else {}
    assert _inside(_one(traced, name, **tag), _one(traced, parent))


@pytest.mark.parametrize("traced", ["fed4"], indirect=True)
def test_federated_steps_follow_each_other(traced):
    for program in ("fed_query", "fed_insert"):
        check = _one(traced, "aerialdb.fed.check", program=program)
        assert check[1] <= _one(traced, "aerialdb.fed.call",
                                program=program)[0]


def test_one_device_session_opens_no_federated_span(traced):
    assert not [s for s in traced if s[2].startswith("aerialdb.fed.")]


def test_host_spans_follow_each_other(traced):
    prep = _one(traced, "aerialdb.query.prepare")
    disp = _one(traced, "aerialdb.query.dispatch")
    assert prep[1] <= disp[0]
    flush = _one(traced, "aerialdb.ingest.flush")
    latest = _one(traced, "aerialdb.ingest.latest")
    assert flush[1] <= _one(traced, "aerialdb.query")[0] <= latest[0]


def test_top_spans_carry_sequence_numbers(traced):
    # The second query and the second flush of the session and pipeline.
    assert _one(traced, "aerialdb.query")[3] == {"q": 2}
    assert _one(traced, "aerialdb.ingest.flush")[3] == {"flush": 2}


def test_make_pred_span(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    _pred()
    jax.profiler.stop_trace()
    assert [s[2] for s in _host_spans(tmp_path)] == ["aerialdb.make_pred"]


def test_tracing_changes_no_answer_and_no_state(tmp_path):
    """The same session driven twice from the same seed, the second time
    under a running trace: every answer and every stored array match bit
    for bit."""
    def drive(trace_dir=None):
        db = AerialDB.open(_cfg(), seed=9)
        pipe = IngestPipeline(db, batch_shards=4)
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        got = [_served(db, pipe, t0) for t0 in (0.0, 1000.0)]
        if trace_dir is not None:
            jax.profiler.stop_trace()
        return got, db.state

    (plain, s0), (traced, s1) = drive(), drive(tmp_path)
    for a, b in zip(jax.tree.leaves((plain, s0)),
                    jax.tree.leaves((traced, s1))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
