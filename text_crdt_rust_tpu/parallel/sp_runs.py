"""Sequence-parallel RLE runs: one huge document sharded across chips.

The long-context story for the RUN representation (SURVEY §5
"long-context / sequence parallelism": *sharding one huge document's span
array across chips with carry-propagating scans over ICI*). A document
too large for one chip's memory keeps its run rows ``(±(order+1), len)``
sharded over the mesh's ``sp`` axis — shard s holds rows
``[s*R, (s+1)*R)`` in document order — and the two hot conversions
(`README.md:20-26`) become shard-local scans plus ONE small collective:

- ``live_prefix``: per-shard live-char totals are ``psum``-style
  all-gathered (one u32 per shard over ICI) so every shard knows the
  carry entering it — the internal-node subtree sums
  (`range_tree/mod.rs:85-93`) with the tree's top levels replaced by the
  mesh axis;
- ``position_of_live_rank``: content position -> (global row, offset
  within run). Each shard resolves the rank against its carry-adjusted
  local cumsum; exactly one shard hits, and a masked ``psum`` extracts
  the answer;
- ``order_to_position``: CRDT item -> content position (hot path #2's
  read-back, `cursor.rs:147-190`): the owning shard computes live chars
  before the item locally, adds its carry, and a masked ``psum``
  broadcasts it.

All collectives are XLA-emitted (``shard_map`` + ``psum``); nothing here
knows about NCCL/MPI. Tested on the virtual 8-device CPU mesh against a
host reference (``tests/test_sp_runs.py``); the same code compiles for a
real ICI mesh unchanged.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_runs(ordp: np.ndarray, lenp: np.ndarray, mesh: Mesh):
    """Upload run planes ``[CAP]`` sharded over the ``sp`` axis (rows
    padded to a multiple of the axis size; 0 = empty row)."""
    sp = mesh.shape["sp"]
    cap = len(ordp)
    pad = (-cap) % sp
    o = np.pad(np.asarray(ordp, np.int32), (0, pad))
    l = np.pad(np.asarray(lenp, np.int32), (0, pad))
    sharding = NamedSharding(mesh, P("sp"))
    return (jax.device_put(jnp.asarray(o), sharding),
            jax.device_put(jnp.asarray(l), sharding))


def _live_lens(ordp, lenp):
    return jnp.where(ordp > 0, lenp, 0)


@lru_cache(maxsize=16)
def make_sp_ops(mesh: Mesh):
    """Build the sharded lookup ops for ``mesh`` (jitted shard_map fns).

    Returns an object with ``live_prefix``, ``position_of_live_rank`` and
    ``order_to_position`` — each one shard-local compute + one small
    collective over the ``sp`` axis.  lru-cached per mesh: the three
    query jits are built once per geometry, not once per caller (the
    ``_build_call`` pattern, round-17 allowlist burn-down).
    """
    spec = P("sp")
    none = P()

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec),
             out_specs=(spec, none), check_vma=False)
    def live_prefix(ordp, lenp):
        """(per-row global live prefix [CAP], total live chars [])."""
        lv = _live_lens(ordp, lenp)
        local = jnp.cumsum(lv)
        total = local[-1] if local.size else jnp.int32(0)
        # Carry entering this shard: sum of totals of lower sp indices.
        idx = jax.lax.axis_index("sp")
        totals = jax.lax.all_gather(total, "sp")
        carry = jnp.sum(jnp.where(jnp.arange(totals.shape[0]) < idx,
                                  totals, 0))
        return local + carry, jnp.sum(totals)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, none),
             out_specs=(none, none), check_vma=False)
    def position_of_live_rank(ordp, lenp, rank1):
        """Live rank (1-based) -> (global row index, 1-based offset in
        that run). Exactly one shard owns the hit; psum extracts it.
        Out-of-range ranks (rank1 > total live) return the sentinel
        ``(0, 0)`` — distinguishable from a real hit because a real
        offset is 1-based (``off == 0`` <=> rank out of range)."""
        lv = _live_lens(ordp, lenp)
        local = jnp.cumsum(lv)
        total = local[-1] if local.size else jnp.int32(0)
        idx = jax.lax.axis_index("sp")
        totals = jax.lax.all_gather(total, "sp")
        carry = jnp.sum(jnp.where(jnp.arange(totals.shape[0]) < idx,
                                  totals, 0))
        cum = local + carry
        R = ordp.shape[0]
        rows = jnp.arange(R)
        # First row whose global cumulative live count reaches rank1.
        mine = (carry < rank1) & (rank1 <= cum[-1] if R else False)
        i_local = jnp.sum((cum < rank1).astype(jnp.int32))
        hit = mine & (i_local < R)
        safe = jnp.minimum(i_local, R - 1)
        row_g = jnp.where(hit, idx * R + safe, 0)
        off = jnp.where(
            hit, rank1 - (cum[safe] - lv[safe]), 0)
        del rows
        return (jax.lax.psum(row_g.astype(jnp.int32), "sp"),
                jax.lax.psum(off.astype(jnp.int32), "sp"))

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, none),
             out_specs=none, check_vma=False)
    def order_to_position(ordp, lenp, order):
        """Item order -> content position (live chars strictly before
        it); -1 if the item is a tombstone or unknown."""
        lv = _live_lens(ordp, lenp)
        starts = jnp.abs(ordp) - 1
        occ = ordp != 0
        contains = occ & (starts <= order) & (order < starts + lenp)
        local = jnp.cumsum(lv)
        total = local[-1] if local.size else jnp.int32(0)
        idx = jax.lax.axis_index("sp")
        totals = jax.lax.all_gather(total, "sp")
        carry = jnp.sum(jnp.where(jnp.arange(totals.shape[0]) < idx,
                                  totals, 0))
        i_local = jnp.argmax(contains)
        hit = jnp.any(contains)
        live_run = hit & (ordp[i_local] > 0)
        before = carry + local[i_local] - lv[i_local] \
            + (order - starts[i_local])
        pos = jnp.where(live_run, before, -1)
        found = jnp.where(hit, pos, 0).astype(jnp.int32)
        any_hit = jax.lax.psum(hit.astype(jnp.int32), "sp")
        summed = jax.lax.psum(found, "sp")
        return jnp.where(any_hit > 0, summed, -1)

    class SpOps:
        pass

    ops = SpOps()
    ops.live_prefix = jax.jit(live_prefix)
    ops.position_of_live_rank = jax.jit(position_of_live_rank)
    ops.order_to_position = jax.jit(order_to_position)
    return ops
