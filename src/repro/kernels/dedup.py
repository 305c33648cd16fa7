"""Shared sorted-unique block-dedup primitives (DESIGN.md §8).

The fused round kernel's batch-union pass (``kernels.tier0_fetch``)
and the search loop's accounting mirror
(``core.device_search._dedup_joins``) must group duplicate block
requests IDENTICALLY: the kernel decides which gather a request rides,
the mirror decides which counter (``io`` vs ``dedup_saved``) the
request lands in, and the bit-exact ``fold_round_log`` <-> ``IOStats``
tie depends on the two groupings never disagreeing. Both used to
hand-roll the same argsort/cumsum idiom; this module is the single
implementation so kernel and reference accounting cannot drift.

All helpers are plain jnp and run unchanged inside ``jit`` or
eagerly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sorted_unique_ranks(flat: jnp.ndarray):
    """Sorted-unique union of ``flat`` [R] int keys, plus the slot map.

    Returns ``(uniq [R], rank [R] i32)``:

      * ``uniq[j]`` is the j-th distinct key in ascending order;
        entries at or past the distinct count keep the 0 placeholder —
        no slot's ``rank`` ever points at them, so a gather pass may
        touch them harmlessly (or bound its loop by the distinct
        count);
      * ``rank[i]`` maps flat slot ``i`` to its key's unique rank:
        ``uniq[rank[i]] == flat[i]`` for every slot.

    The sort is stable, so among slots sharing a key the earliest
    flat-order slot defines the group — the same "first requester pays
    the DMA" order ``join_mask`` marks joiners against.
    """
    r = flat.shape[0]
    sort_idx = jnp.argsort(flat)                  # stable
    sb = flat[sort_idx]
    first = jnp.concatenate([jnp.ones((1,), bool), sb[1:] != sb[:-1]])
    rank = jnp.cumsum(first) - 1                  # sorted pos -> rank
    # duplicates write equal values, so the scatters are deterministic
    uniq = jnp.zeros((r,), flat.dtype).at[rank].set(sb)
    req_rank = jnp.zeros((r,), jnp.int32).at[sort_idx].set(
        rank.astype(jnp.int32))
    return uniq, req_rank


def join_mask(keys: jnp.ndarray) -> jnp.ndarray:
    """Mark slots whose key an earlier slot in the same row already
    carries.

    ``keys`` [T, R] int -> joined [T, R] bool: True where some earlier
    (flat-order) slot of the same row has the same key — the earliest
    requester of each duplicate group stays False (it pays the gather);
    every later one is a join. Rows are independent dedup scopes (one
    row = one kernel tile, or one row = the whole batch); slots that
    must never join (non-cold requests, padding) should carry unique
    negative sentinel keys.
    """
    t, r = keys.shape
    order = jnp.argsort(keys, axis=1)             # stable
    sk = jnp.take_along_axis(keys, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((t, 1), bool), sk[:, 1:] == sk[:, :-1]], axis=1)
    return jnp.zeros((t, r), bool).at[
        jnp.arange(t)[:, None], order].set(dup)
