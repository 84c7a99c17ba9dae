"""Served jnp engine for st_scan: the OR-list membership test runs in chunks.

Same semantics, shapes and results as ``ref.st_scan_ref`` (bit-identical:
the boolean mask is the same and the aggregation below is the oracle's, op
for op). Only the OR-list membership test differs. The oracle compares every
tuple against all ``L`` list entries, although each (query, edge) list holds
its ``|sublist_len|`` entries at positions ``[0, |sublist_len|)`` and pads
the rest. Here a device-side loop compares ``CHUNK`` entries a step and runs
``ceil(max |sublist_len| / CHUNK)`` steps: the work follows the longest
per-edge list of the batch, not the static list width.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.st_scan.ref import check_channels, tuple_pred_match

# List entries compared per loop step. A step costs CHUNK compares of every
# (query, edge, slot) plus one read and write of the (Q, E, C) membership
# mask; a list longer than the chunk costs one more step per CHUNK entries.
# With the min_shards planner the longest per-edge list of a D400 request is
# a few entries (at most 8 over a benchmark schedule), so 8 serves nearly
# every request in one step. On a TPU v5e at D400 widths the whole scan took
# 6.4 / 7.4 / 9.4 ms with a longest list of 8 at CHUNK 8 / 16 / 32, and 8
# stayed cheapest up to lists of 40 (PERF.md §6): a step costs ~1 ms, so
# planners that pile shards onto one edge (min_edges, failures) pay little
# for the extra steps.
CHUNK = 8


def or_list_member(tup_sid, sublists, sublist_len):
    """(Q, E, C) bool: tuple sid equals one of the first ``|sublist_len|``
    entries of its (query, edge) OR-list (``min(|sublist_len|, L)``, as the
    oracle's ``entry_valid``).

    ``tup_sid`` is column-major ``(E, 2, C)``; ``sublists`` (Q, E, L, 2).
    """
    q, e, l, _ = sublists.shape
    c = tup_sid.shape[-1]
    w = min(CHUNK, l)
    pad = (-l) % w
    list_hi, list_lo = sublists[..., 0], sublists[..., 1]            # (Q, E, L)
    if pad:
        list_hi, list_lo = (jnp.pad(a, ((0, 0), (0, 0), (0, pad)))
                            for a in (list_hi, list_lo))
    n = jnp.minimum(jnp.abs(sublist_len), l)                         # (Q, E)
    steps = (jnp.max(n, initial=0) + w - 1) // w
    sid_hi, sid_lo = tup_sid[None, :, 0, :], tup_sid[None, :, 1, :]  # (1, E, C)

    def step(i, member):
        base = i * w
        hi = jax.lax.dynamic_slice_in_dim(list_hi, base, w, axis=2)  # (Q, E, w)
        lo = jax.lax.dynamic_slice_in_dim(list_lo, base, w, axis=2)
        for j in range(w):
            ok = (base + j < n)[..., None]                           # (Q, E, 1)
            member = member | (ok & (sid_hi == hi[:, :, j, None])
                               & (sid_lo == lo[:, :, j, None]))
        return member

    return jax.lax.fori_loop(0, steps, step, jnp.zeros((q, e, c), jnp.bool_))


def st_scan_chunked(tup_f, tup_sid, tup_count, pred, sublists, sublist_len,
                    channels: Tuple[int, ...] = (0,),
                    valid_c: Optional[int] = None):
    """``ref.st_scan_ref`` with the chunked OR-list test; same arguments,
    same (count, vsum, vmin, vmax), bit for bit."""
    e, w, c = tup_f.shape
    value_rows = check_channels(channels, w)
    if valid_c is None:
        valid_c = c

    n_valid = jnp.minimum(tup_count, min(valid_c, c))
    alive_t = jnp.arange(c, dtype=jnp.int32)[None, :] < n_valid[:, None]     # (E, C)
    pm = tuple_pred_match(tup_f, tup_sid, pred)                              # (Q, E, C)
    in_list = or_list_member(tup_sid, sublists, sublist_len)                 # (Q, E, C)

    scan_all = (sublist_len < 0)[..., None]                                  # (Q, E, 1)
    selected = (sublist_len != 0)[..., None]
    shard_ok = jnp.where(scan_all, True, in_list) & selected

    m = pm & shard_ok & alive_t[None]                                        # (Q, E, C)
    vals = jnp.stack([tup_f[:, row, :] for row in value_rows])               # (K, E, C)
    mk = m[:, None]                                                          # (Q, 1, E, C)
    count = jnp.sum(m, axis=-1).astype(jnp.int32)                            # (Q, E)
    vsum = jnp.sum(jnp.where(mk, vals[None], 0.0), axis=-1)                  # (Q, K, E)
    vmin = jnp.min(jnp.where(mk, vals[None], jnp.inf), axis=-1)
    vmax = jnp.max(jnp.where(mk, vals[None], -jnp.inf), axis=-1)
    return count, vsum, vmin, vmax
