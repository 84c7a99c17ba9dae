"""Per-kernel allclose tests against the pure oracles, swept over shapes and
dtypes, executed in Pallas interpret mode (CPU validation of the TPU target).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.datastore import make_pred
from repro.data.synthetic import CityConfig, make_sites
from repro.kernels.hash64 import ref as href
from repro.kernels.hash64.hash64 import xxh64
from repro.kernels.st_scan import ops as st_ops
from repro.kernels.st_scan import ref as st_ref
from repro.kernels.st_scan.chunked import CHUNK, st_scan_chunked
from repro.kernels.voronoi_assign import ref as vref
from repro.kernels.voronoi_assign.voronoi_assign import voronoi_assign


# ---------------------------------------------------------------------------
# hash64
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 1024, 3000])
def test_hash64_kernel_vs_oracle(n):
    rng = np.random.default_rng(n)
    hi = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    lo = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    got_hi, got_lo = xxh64(jnp.asarray(hi), jnp.asarray(lo), interpret=True)
    exp_hi, exp_lo = href.xxh64_batch_py(hi, lo)
    np.testing.assert_array_equal(np.asarray(got_hi), exp_hi)
    np.testing.assert_array_equal(np.asarray(got_lo), exp_lo)


# ---------------------------------------------------------------------------
# voronoi_assign
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,e,block", [(64, 8, 64), (1000, 20, 256), (4096, 80, 1024)])
def test_voronoi_kernel_vs_oracle(n, e, block):
    rng = np.random.default_rng(e)
    sites = make_sites(e, CityConfig(), seed=3)
    pts = rng.uniform([12.85, 77.45], [13.10, 77.75], (n, 2)).astype(np.float32)
    got = np.asarray(voronoi_assign(jnp.asarray(pts), jnp.asarray(sites),
                                    block_p=block, interpret=True))
    exp = vref.voronoi_assign_ref(pts, sites)
    diff = got != exp
    if diff.any():  # only near-equidistant points may disagree (fp32)
        d = ((pts[diff, None, :] - sites[None]) ** 2).sum(-1)
        best2 = np.sort(d, axis=1)[:, :2]
        assert np.all((best2[:, 1] - best2[:, 0]) < 1e-7)


# ---------------------------------------------------------------------------
# st_scan
# ---------------------------------------------------------------------------

def random_scan_problem(rng, e=4, c=1024, q=3, l=8, w=7):
    """Random column-major scan problem: (E, W, C) log, (E, 2, C) sids."""
    tup_f = rng.uniform(0, 100, (e, w, c)).astype(np.float32)
    tup_sid = rng.integers(0, 6, (e, 2, c)).astype(np.int32)
    tup_count = rng.integers(0, c + 1, (e,)).astype(np.int32)
    sublists = rng.integers(0, 6, (q, e, l, 2)).astype(np.int32)
    sublist_len = rng.integers(-1, l + 1, (q, e)).astype(np.int32)
    pred = make_pred(
        q=q,
        lat0=rng.uniform(0, 50, q).astype(np.float32),
        lat1=rng.uniform(50, 100, q).astype(np.float32),
        lon0=rng.uniform(0, 50, q).astype(np.float32),
        lon1=rng.uniform(50, 100, q).astype(np.float32),
        t0=rng.uniform(0, 50, q).astype(np.float32),
        t1=rng.uniform(50, 100, q).astype(np.float32),
        sid_hi=rng.integers(0, 6, q).astype(np.int32),
        sid_lo=rng.integers(0, 6, q).astype(np.int32),
        has_spatial=rng.random(q) < 0.7,
        has_temporal=rng.random(q) < 0.7,
        has_sid=rng.random(q) < 0.3,
        is_and=rng.random(q) < 0.7)
    return (jnp.asarray(tup_f), jnp.asarray(tup_sid), jnp.asarray(tup_count),
            pred, jnp.asarray(sublists), jnp.asarray(sublist_len))


@pytest.mark.parametrize("seed,c,block", [(0, 512, 128), (1, 1024, 256),
                                          (2, 1536, 512), (3, 640, 128)])
def test_st_scan_kernel_vs_ref(seed, c, block):
    rng = np.random.default_rng(seed)
    args = random_scan_problem(rng, c=c)
    exp = st_ref.st_scan_ref(*args)
    got = st_ops.st_scan(*args, block_c=block, interpret=True)
    for g, x, name in zip(got, exp, ["count", "vsum", "vmin", "vmax"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5,
                                   err_msg=name)


def test_st_scan_scan_all_sentinel():
    """sublist_len < 0 must scan without shard scoping (broadcast mode)."""
    rng = np.random.default_rng(7)
    tup_f, tup_sid, tup_count, pred, sublists, _ = random_scan_problem(rng)
    q, e = sublists.shape[:2]
    slen = jnp.full((q, e), -1, jnp.int32)
    exp = st_ref.st_scan_ref(tup_f, tup_sid, tup_count, pred, sublists, slen)
    got = st_ops.st_scan(tup_f, tup_sid, tup_count, pred, sublists, slen,
                         block_c=256, interpret=True)
    for g, x in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5)


def test_st_scan_ring_count_clamp():
    """Ring-buffer validity: tup_count above capacity (monotonic total-written
    counter) must behave exactly like a full log — min(count, cap) — in both
    engines."""
    rng = np.random.default_rng(11)
    tup_f, tup_sid, _, pred, sublists, slen = random_scan_problem(rng)
    c = tup_f.shape[2]            # column-major: the tuple axis is last
    over = jnp.asarray(rng.integers(c + 1, 5 * c, tup_f.shape[0]), jnp.int32)
    full = jnp.full(tup_f.shape[0], c, jnp.int32)
    exp = st_ref.st_scan_ref(tup_f, tup_sid, full, pred, sublists, slen)
    got_ref = st_ref.st_scan_ref(tup_f, tup_sid, over, pred, sublists, slen)
    got_ker = st_ops.st_scan(tup_f, tup_sid, over, pred, sublists, slen,
                             block_c=256, interpret=True)
    for g, x in zip(got_ref, exp):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))
    for g, x in zip(got_ker, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5)


def test_st_scan_empty_edges():
    rng = np.random.default_rng(9)
    tup_f, tup_sid, _, pred, sublists, slen = random_scan_problem(rng)
    zero = jnp.zeros(tup_f.shape[0], jnp.int32)
    got = st_ops.st_scan(tup_f, tup_sid, zero, pred, sublists, slen,
                         block_c=256, interpret=True)
    assert int(np.asarray(got[0]).sum()) == 0


def _assert_kernel_matches_ref(args, block_c, interpret):
    """Pallas vs ref: counts bitwise, float aggregates to accumulation order.
    ``interpret=None`` exercises the auto dispatch (compiled on TPU,
    interpreted elsewhere)."""
    exp = st_ref.st_scan_ref(*args)
    got = st_ops.st_scan(*args, block_c=block_c, interpret=interpret)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]),
                                  err_msg="count")
    for g, x, name in zip(got[1:], exp[1:], ["vsum", "vmin", "vmax"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("interpret", [True, None])
@pytest.mark.parametrize("c", [100, 129, 384])
def test_st_scan_non_lane_multiple_capacity(c, interpret):
    """Capacities that are not lane (128) or block multiples force the
    wrapper's C padding; padded lanes must never be admitted."""
    rng = np.random.default_rng(c)
    args = random_scan_problem(rng, c=c)
    _assert_kernel_matches_ref(args, block_c=128, interpret=interpret)


@pytest.mark.parametrize("interpret", [True, None])
def test_st_scan_zero_count_everywhere(interpret):
    """tup_count == 0 on every edge: both engines agree on all-zero counts
    even though the tuple arrays hold (stale) data."""
    rng = np.random.default_rng(21)
    tup_f, tup_sid, _, pred, sublists, slen = random_scan_problem(rng)
    zero = jnp.zeros(tup_f.shape[0], jnp.int32)
    _assert_kernel_matches_ref(
        (tup_f, tup_sid, zero, pred, sublists, slen), 256, interpret)
    exp = st_ref.st_scan_ref(tup_f, tup_sid, zero, pred, sublists, slen)
    assert int(np.asarray(exp[0]).sum()) == 0


@pytest.mark.parametrize("interpret", [True, None])
def test_st_scan_exactly_at_capacity(interpret):
    """tup_count == capacity: the whole ring is live, nothing more (the
    validity rule min(count, cap) sits exactly on its boundary)."""
    rng = np.random.default_rng(23)
    tup_f, tup_sid, _, pred, sublists, slen = random_scan_problem(rng, c=512)
    full = jnp.full(tup_f.shape[0], 512, jnp.int32)
    _assert_kernel_matches_ref(
        (tup_f, tup_sid, full, pred, sublists, slen), 128, interpret)


@pytest.mark.parametrize("channel", [1, 3])
@pytest.mark.parametrize("interpret", [True, None])
def test_st_scan_channel_selection(channel, interpret):
    """AggSpec channel generalization: both engines aggregate the selected
    value row (3 + channel), counts bitwise, floats to accumulation
    order; and selecting a channel must equal slicing it out by hand."""
    rng = np.random.default_rng(31 + channel)
    tup_f, tup_sid, cnt, pred, sublists, slen = random_scan_problem(rng)
    args = (tup_f, tup_sid, cnt, pred, sublists, slen)
    exp = st_ref.st_scan_ref(*args, channels=(channel,))
    got = st_ops.st_scan(*args, block_c=256, interpret=interpret,
                         channels=(channel,))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]),
                                  err_msg="count")
    for g, x, name in zip(got[1:], exp[1:], ["vsum", "vmin", "vmax"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5,
                                   err_msg=name)
    # Independent oracle: move the channel into row v0 and scan channel 0.
    swapped = tup_f.at[:, 3, :].set(tup_f[:, 3 + channel, :])
    exp0 = st_ref.st_scan_ref(swapped, tup_sid, cnt, pred, sublists, slen)
    for g, x in zip(exp, exp0):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))


def test_st_scan_multi_channel_fused_vs_oracles():
    """Fused multi-channel aggregation: a K-channel scan must equal (a) a
    plain numpy oracle over the live window and (b) K independent
    single-channel scans stacked — for both engines, in one pass."""
    rng = np.random.default_rng(41)
    channels = (0, 2, 3)
    tup_f, tup_sid, cnt, pred, sublists, slen = random_scan_problem(rng, c=640)
    args = (tup_f, tup_sid, cnt, pred, sublists, slen)
    got_ref = st_ref.st_scan_ref(*args, channels=channels)
    got_ker = st_ops.st_scan(*args, block_c=128, interpret=True,
                             channels=channels)
    assert got_ref[1].shape == (3, len(channels), 4)
    # (a) numpy oracle: recompute the mask and every aggregate per channel.
    e, w, c = tup_f.shape
    q = sublists.shape[0]
    npf, nps = np.asarray(tup_f), np.asarray(tup_sid)
    p = {f: np.asarray(getattr(pred, f)) for f in pred._fields}
    for qi in range(q):
        for ei in range(e):
            sp = ((p["lat0"][qi] <= npf[ei, 1]) & (npf[ei, 1] <= p["lat1"][qi])
                  & (p["lon0"][qi] <= npf[ei, 2]) & (npf[ei, 2] <= p["lon1"][qi]))
            tp = (p["t0"][qi] <= npf[ei, 0]) & (npf[ei, 0] <= p["t1"][qi])
            ip = ((nps[ei, 0] == p["sid_hi"][qi])
                  & (nps[ei, 1] == p["sid_lo"][qi]))
            if p["is_and"][qi]:
                m = ((sp | ~p["has_spatial"][qi]) & (tp | ~p["has_temporal"][qi])
                     & (ip | ~p["has_sid"][qi]))
            else:
                m = ((sp & p["has_spatial"][qi]) | (tp & p["has_temporal"][qi])
                     | (ip & p["has_sid"][qi]))
            sl = int(np.asarray(slen)[qi, ei])
            if sl == 0:
                m &= False
            elif sl > 0:
                entries = np.asarray(sublists)[qi, ei, :sl]
                m &= np.array([(entries == nps[ei, :, t]).all(1).any()
                               for t in range(c)])
            m &= np.arange(c) < int(np.asarray(cnt)[ei])
            assert int(got_ref[0][qi, ei]) == int(m.sum())
            for k, ch in enumerate(channels):
                v = npf[ei, 3 + ch][m]
                np.testing.assert_allclose(float(got_ref[1][qi, k, ei]),
                                           v.sum() if len(v) else 0.0,
                                           rtol=1e-4, atol=1e-4)
    # (b) K single-channel scans, both engines.
    for k, ch in enumerate(channels):
        one_ref = st_ref.st_scan_ref(*args, channels=(ch,))
        one_ker = st_ops.st_scan(*args, block_c=128, interpret=True,
                                 channels=(ch,))
        for got, one in ((got_ref, one_ref), (got_ker, one_ker)):
            np.testing.assert_array_equal(np.asarray(got[0]),
                                          np.asarray(one[0]))
            for agg_i in (1, 2, 3):
                np.testing.assert_array_equal(
                    np.asarray(got[agg_i][:, k]),
                    np.asarray(one[agg_i][:, 0]))


def test_st_scan_channel_out_of_range():
    rng = np.random.default_rng(5)
    args = random_scan_problem(rng, w=7)
    with pytest.raises(ValueError, match="channel=4"):
        st_ref.st_scan_ref(*args, channels=(4,))
    with pytest.raises(ValueError, match="channel=4"):
        st_ops.st_scan(*args, channels=(4,))
    # Negative channels must not alias the t/lat/lon metadata rows.
    with pytest.raises(ValueError, match="channel=-1"):
        st_ref.st_scan_ref(*args, channels=(-1,))
    with pytest.raises(ValueError, match="channel=-1"):
        st_ops.st_scan(*args, channels=(-1,))
    with pytest.raises(ValueError, match="duplicates"):
        st_ref.st_scan_ref(*args, channels=(1, 1))


@pytest.mark.parametrize("q,block_q", [(1, 8), (3, 4), (5, 8), (9, 4)])
def test_st_scan_non_multiple_query_tiles(q, block_q):
    """Query batches that are not block_q multiples force the wrapper's Q
    padding; padding-query lanes must be inert and sliced off — kernel ==
    ref bitwise on counts at every (q, block_q)."""
    rng = np.random.default_rng(q * 10 + block_q)
    args = random_scan_problem(rng, q=q, c=512)
    exp = st_ref.st_scan_ref(*args)
    got = st_ops.st_scan(*args, block_c=128, block_q=block_q, interpret=True)
    assert got[0].shape == (q, 4) and got[1].shape == (q, 1, 4)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]),
                                  err_msg="count")
    for g, x, name in zip(got[1:], exp[1:], ["vsum", "vmin", "vmax"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5,
                                   err_msg=name)


@pytest.mark.parametrize("interpret", [True, None])
def test_st_scan_lane_padded_capacity_post_wrap(interpret):
    """The store lane-pads the tuple axis above the logical capacity: with a
    post-wrap ring count (count >> capacity) neither engine may ever admit
    the padding slots — fill them with garbage and compare against an oracle
    scan of the unpadded log."""
    rng = np.random.default_rng(55)
    cap, pad = 500, 140                       # stored C = 640, logical = 500
    tup_f, tup_sid, _, pred, sublists, slen = random_scan_problem(rng, c=cap)
    garbage_f = rng.uniform(0, 100, (4, 7, pad)).astype(np.float32)
    garbage_s = rng.integers(0, 6, (4, 2, pad)).astype(np.int32)
    padded_f = jnp.concatenate([tup_f, jnp.asarray(garbage_f)], axis=2)
    padded_s = jnp.concatenate([tup_sid, jnp.asarray(garbage_s)], axis=2)
    over = jnp.asarray(rng.integers(cap + 1, 7 * cap, (4,)), jnp.int32)
    exp = st_ref.st_scan_ref(tup_f, tup_sid, jnp.full((4,), cap, jnp.int32),
                             pred, sublists, slen)
    got_ref = st_ref.st_scan_ref(padded_f, padded_s, over, pred, sublists,
                                 slen, valid_c=cap)
    got_ker = st_ops.st_scan(padded_f, padded_s, over, pred, sublists, slen,
                             block_c=128, interpret=interpret, valid_c=cap)
    for got in (got_ref, got_ker):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]),
                                      err_msg="count")
        for g, x, name in zip(got[1:], exp[1:], ["vsum", "vmin", "vmax"]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(x),
                                       rtol=1e-5, err_msg=name)


# OR-list width of the chunked-engine cases: not a multiple of CHUNK, so the
# last chunk is padded.
_CHUNKED_L = 2 * CHUNK + 5


@pytest.mark.parametrize("longest", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1,
                                     2 * CHUNK + 3, _CHUNKED_L, -1])
def test_st_scan_chunked_matches_ref(longest):
    """The served jnp engine tests OR-list membership only up to the longest
    list of the batch, CHUNK entries a step: it must equal the oracle bit
    for bit, with lengths mixed across (query, edge) pairs, the scan-all
    sentinel among them, and a lane-padded capacity (valid_c < C). Entries
    past each pair's length are real shard ids, so reading one shows."""
    rng = np.random.default_rng(100 + longest)
    q, e, cap, pad = 3, 4, 500, 140
    args = list(random_scan_problem(rng, e=e, c=cap + pad, q=q,
                                    l=_CHUNKED_L))
    # 64 distinct (hi, lo) shard ids, each held by ~1/64 of the slots; every
    # list draws from ids 1-63 without repeats, so each entry decides some
    # tuples and id (0, 0), which padding could alias, is in no list.
    sid = rng.integers(0, 64, (e, cap + pad))
    args[1] = jnp.asarray(np.stack([sid // 16, sid % 16], axis=1), jnp.int32)
    perm = np.stack([1 + rng.permutation(63)[:_CHUNKED_L]
                     for _ in range(q * e)]).reshape(q, e, _CHUNKED_L)
    args[4] = jnp.asarray(np.stack([perm // 16, perm % 16], -1), jnp.int32)
    top = abs(longest)
    slen = rng.choice([0, -1, top, top // 2, min(top, 1)], (q, e))
    slen[0, 0] = longest
    if longest >= 0:
        slen[slen < 0] = 0 if longest == 0 else 1
    if longest == _CHUNKED_L:      # a length past the list: L entries count
        slen[1, 1] = _CHUNKED_L + CHUNK
    args[5] = jnp.asarray(slen, jnp.int32)
    args[2] = jnp.asarray(rng.integers(cap + 1, 4 * cap, (e,)), jnp.int32)
    channels = (0, 2, 3)
    ref = jax.jit(st_ref.st_scan_ref, static_argnames=("channels", "valid_c"))
    got_fn = jax.jit(st_scan_chunked, static_argnames=("channels", "valid_c"))
    exp = ref(*args, channels=channels, valid_c=cap)
    got = got_fn(*args, channels=channels, valid_c=cap)
    assert (int(np.asarray(exp[0]).sum()) > 0) == (longest != 0)
    for g, x, name in zip(got, exp, ["count", "vsum", "vmin", "vmax"]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x),
                                      err_msg=name)


@settings(deadline=None, max_examples=25)
@given(st.data())
def test_st_scan_random_query_tiles_property(data):
    """Hypothesis property: for random problem shapes and random (block_q,
    block_c) tilings, the query-tiled kernel agrees with the reference —
    counts bitwise, float aggregates to accumulation order."""
    q = data.draw(st.integers(1, 12), label="q")
    e = data.draw(st.integers(1, 5), label="e")
    c = data.draw(st.integers(1, 5), label="c128") * 128
    block_q = 2 ** data.draw(st.integers(0, 3), label="log2_block_q")
    block_c = 128 * data.draw(st.integers(1, 2), label="block_c128")
    n_ch = data.draw(st.integers(1, 3), label="n_ch")
    seed = data.draw(st.integers(0, 2 ** 16), label="seed")
    rng = np.random.default_rng(seed)
    channels = tuple(rng.choice(4, n_ch, replace=False).tolist())
    args = random_scan_problem(rng, e=e, c=c, q=q)
    exp = st_ref.st_scan_ref(*args, channels=channels)
    got = st_ops.st_scan(*args, block_c=block_c, block_q=block_q,
                         interpret=True, channels=channels)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]),
                                  err_msg="count")
    for g, x, name in zip(got[1:], exp[1:], ["vsum", "vmin", "vmax"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-5,
                                   atol=1e-5, err_msg=name)


@pytest.fixture(scope="module")
def wrapped_ring_state():
    """A ring grown through the real insert path to well past capacity
    (every edge wrapped several times). Built once; the scan tests below are
    read-only."""
    from repro.core.datastore import StoreConfig, init_store
    from repro.data.synthetic import DroneFleet, make_sites
    from repro.distributed.federation import ingest_rounds

    e, cap = 4, 256
    sites = make_sites(e, CityConfig(), seed=3)
    cfg = StoreConfig(n_edges=e, sites=tuple(map(tuple, sites.tolist())),
                      tuple_capacity=cap, index_capacity=256,
                      max_shards_per_query=32, records_per_shard=8)
    fleet = DroneFleet(8, records_per_shard=8)
    payloads, metas = fleet.next_rounds(16)
    state, _ = ingest_rounds(cfg, init_store(cfg), payloads, metas,
                             jnp.ones(e, bool))
    assert int(np.asarray(state.tup_count).min()) > cap  # every ring wrapped
    return state


@pytest.mark.parametrize("interpret", [True, None])
def test_st_scan_post_wrap_ring(wrapped_ring_state, interpret):
    """Both engines must scan the whole wrapped ring and agree bitwise on
    counts."""
    state = wrapped_ring_state
    e = state.tup_f.shape[0]
    pred = make_pred(q=2, t0=[0.0, 200.0], t1=[1e9, 400.0], has_temporal=True,
                     is_and=True)
    slen = jnp.full((2, e), -1, jnp.int32)             # scan-all sentinel
    sublists = jnp.zeros((2, e, 1, 2), jnp.int32)
    _assert_kernel_matches_ref(
        (state.tup_f, state.tup_sid, state.tup_count, pred, sublists, slen),
        128, interpret)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

from repro.kernels.flash_attention.ops import flash_attention_pallas
from repro.models.attention import naive_attention


@pytest.mark.parametrize("b,s,h,kv,dh,bq,bk,causal", [
    (1, 256, 4, 4, 64, 128, 128, True),
    (2, 256, 8, 2, 32, 64, 128, True),     # GQA group=4
    (1, 384, 4, 1, 64, 128, 128, False),   # MQA, bidirectional
    (1, 128, 2, 2, 128, 64, 64, True),
])
def test_flash_pallas_vs_naive(b, s, h, kv, dh, bq, bk, causal):
    key = jax.random.key(s + h)
    q = jax.random.normal(key, (b, s, h, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kv, dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kv, dh), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    exp = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-5,
                               atol=2e-5)


def test_flash_pallas_bf16():
    key = jax.random.key(9)
    q = jax.random.normal(key, (1, 256, 4, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 4, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 4, 64), jnp.bfloat16)
    got = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    exp = naive_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(exp),
                               rtol=3e-2, atol=3e-2)


def test_flash_pallas_q_offset_decode_window():
    """Chunked decode: q block at offset p attends only to k[:p+block]."""
    key = jax.random.key(11)
    b, s, h, dh, p = 1, 256, 2, 32, 128
    q = jax.random.normal(key, (b, 128, h, dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, dh), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, q_offset=p,
                                 interpret=True)
    from repro.models.attention import flash_attention as flash_jnp
    exp = flash_jnp(q, k, v, causal=True, q_offset=p, chunk_kv=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-5,
                               atol=2e-5)
