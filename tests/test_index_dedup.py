"""``index.dedup_matched`` against a plain numpy reference, and the shape of
the lookup program it compiles into.

The reference walks each query's candidates in flat order, keeps the first
occurrence of every matched sid with its replicas, and lists the distinct
sids in ascending order: the canonical form the federated merge rests on.
Only the valid slots are compared; what an invalid slot holds is not part of
the contract.

The structural test lowers ``index.lookup`` at the D400 lookup width and
checks that no gather in it reads out all E x CAP candidates of a query:
the sorted columns come out of the dedup sort, and the replicas are gathered
only at the selected slots.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import QueryPred, dedup_matched, init_index, lookup

Q = 4
S = 768


def reference(m, hi, lo, rep, s):
    """(sid_hi, sid_lo, replicas, valid, overflow) per query, valid slots
    only: the ``s`` smallest distinct matched sids, ascending, each with the
    replicas of its first occurrence in flat order."""
    out = []
    for qi in range(m.shape[0]):
        first = {}
        for i in np.flatnonzero(m[qi]):
            first.setdefault((int(hi[qi, i]), int(lo[qi, i])), rep[qi, i])
        keys = sorted(first)
        kept = keys[:s]
        out.append((np.array([k[0] for k in kept], np.int32),
                    np.array([k[1] for k in kept], np.int32),
                    np.array([first[k] for k in kept], np.int32).reshape(-1, 3),
                    len(keys) > s))
    return out


def _columns(rng, n, n_sids, share):
    """n candidates drawn from n_sids distinct sids (hi in 0..2), each
    matched with probability ``share``; every row carries its own
    replicas, so duplicates of one sid disagree on them."""
    sid = rng.integers(0, n_sids, (Q, n))
    hi = (sid % 3).astype(np.int32)
    lo = (sid // 3).astype(np.int32)
    m = rng.random((Q, n)) < share
    rep = rng.integers(-1, 80, (Q, n, 3)).astype(np.int32)
    return m, hi, lo, rep


def _exactly(rng, n, k):
    """Exactly k distinct matched sids per query, each matched at least
    twice, among unmatched rows of other sids."""
    m, hi, lo, rep = _columns(rng, n, 4 * k, 0.0)
    for qi in range(Q):
        pos = rng.permutation(n)[:2 * k]
        sids = rng.permutation(4 * k)[:k]
        hi[qi, pos] = np.tile(sids % 3, 2)
        lo[qi, pos] = np.tile(sids // 3, 2)
        m[qi, pos] = True
    return m, hi, lo, rep


def _duplicates_across_edges(rng):
    # 80 edges x 40 entries holding 300 sids: each sid sits on about 10
    # edges, every copy with other replica columns.
    return _columns(rng, 80 * 40, 300, 0.5)


def _unmatched_smaller(rng):
    m, hi, lo, rep = _columns(rng, 3000, 500, 0.4)
    # Every unmatched row gets a sid below every matched one.
    hi = np.where(m, hi + 5, 0).astype(np.int32)
    lo = np.where(m, lo, -1 - np.arange(3000, dtype=np.int32)).astype(np.int32)
    return m, hi, lo, rep


CASES = {
    "duplicates_across_edges": _duplicates_across_edges,
    "unmatched_smaller_sids": _unmatched_smaller,
    "no_match": lambda rng: _columns(rng, 2000, 400, 0.0),
    "exactly_s_distinct": lambda rng: _exactly(rng, 4 * S, S),
    "s_plus_one_distinct": lambda rng: _exactly(rng, 4 * S, S + 1),
    "lookup_width": lambda rng: _columns(rng, 80 * 512, 20_000, 0.02),
    "merge_width": lambda rng: _columns(rng, 4 * S, 2 * S, 0.6),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dedup_matched_matches_reference(case):
    rng = np.random.default_rng(sorted(CASES).index(case) + 1701)
    m, hi, lo, rep = CASES[case](rng)
    got = jax.jit(dedup_matched, static_argnums=4)(m, hi, lo, rep, S)
    got = [np.asarray(x) for x in got]
    assert got[0].shape == (Q, S) and got[2].shape == (Q, S, 3)
    for qi, (r_hi, r_lo, r_rep, r_ovf) in enumerate(reference(m, hi, lo, rep, S)):
        k = len(r_hi)
        np.testing.assert_array_equal(got[3][qi], np.arange(S) < k)
        np.testing.assert_array_equal(got[0][qi, :k], r_hi)
        np.testing.assert_array_equal(got[1][qi, :k], r_lo)
        np.testing.assert_array_equal(got[2][qi, :k], r_rep)
        assert bool(got[4][qi]) == r_ovf
    if case == "no_match":
        assert not got[3].any()
    if case == "exactly_s_distinct":
        assert got[3].all() and not got[4].any()
    if case == "s_plus_one_distinct":
        assert got[3].all() and got[4].all()


def test_lookup_has_no_full_width_gather():
    """At Q=8, E=80, CAP=512, S=768 every gather in the lowered lookup reads
    out fewer than E x CAP elements per query."""
    q, e, cap = 8, 80, 512
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    state = jax.tree.map(sds, jax.eval_shape(lambda: init_index(e, cap)))
    f, i, b = jnp.float32, jnp.int32, jnp.bool_
    pred = QueryPred(*(jax.ShapeDtypeStruct((q,), d)
                       for d in (f,) * 6 + (i,) * 2 + (b,) * 4))
    text = jax.jit(lookup, static_argnums=3).lower(
        state, pred, jax.ShapeDtypeStruct((q, e), b), S).as_text()
    gathers = re.findall(r'"?stablehlo\.gather"?.*?->\s*tensor<([0-9x]+)x[a-z]',
                         text)
    assert gathers, "the lookup gathers the replicas of the selected slots"
    for dims in gathers:
        elems = int(np.prod([int(d) for d in dims.split("x")]))
        assert elems // q < e * cap, f"full-width gather tensor<{dims}>"
