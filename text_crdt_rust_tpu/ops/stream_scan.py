"""Block-wise scans for documents larger than device memory.

SURVEY §5's long-context row names two scale regimes beyond the VMEM
engines: sharding one document's runs across chips
(``parallel.sp_runs`` / ``sp_apply``) and *"block-wise scans for >HBM
documents"* — this module. The run planes ``(±(order+1), len)`` stay
HOST-resident (arbitrary length, e.g. memory-mapped), and the read-side
conversions (`README.md:20-26`) stream device-sized tiles through ONE
jitted per-tile reduction each, with the scan carry (live chars before
the tile) riding on host exactly like ``sp_runs`` rides it on the mesh
axis — the B-tree descent (`root.rs:54-88`) with the top levels replaced
by a host-side tile table:

- ``live_total`` / per-tile carries: one pass at construction;
- ``position_of_live_rank``: host-searchsorted over the carry table
  picks the ONE tile that resolves the rank, then a single in-tile
  device lookup finishes (`cursor.rs:147-190`'s inverse);
- ``order_to_position``: tiles stream until the owning run is found
  (`doc.rs:26-29` + `cursor.rs:147-190`); unfound -> -1.

Mutation at this scale goes through ``ops.rle_hbm`` (windowed HBM
planes) or ``parallel.sp_apply`` (sharded); this module is the
read-back path for state bigger than both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .batch import _require


@jax.jit
def _tile_rank(o, l, rank1):
    """Resolve 1-based live rank ``rank1`` (known to land in this tile,
    so tile-local arithmetic fits i32) -> (tile-local row, 1-based
    offset within the run)."""
    lv = jnp.where(o > 0, l, 0)
    cum = jnp.cumsum(lv)
    row = jnp.sum((cum < rank1).astype(jnp.int32))
    before = cum[row] - lv[row]
    return row, rank1 - before


@jax.jit
def _tile_order(o, l, order):
    """(found?, tile-local position or -1 when tombstoned).

    A run row covers orders ``[abs(o)-1, abs(o)-1+len)`` (`span.rs:9-13`
    implicit chaining); the position counts live chars strictly before
    the item within this tile (`cursor.rs:147-190` semantics, matching
    ``parallel.sp_runs.order_to_position``)."""
    start = jnp.abs(o) - 1
    hit = (o != 0) & (order >= start) & (order < start + l)
    lv = jnp.where(o > 0, l, 0)
    cum_before = jnp.cumsum(lv) - lv
    row = jnp.argmax(hit)
    found = jnp.any(hit)
    live_run = found & (o[row] > 0)
    pos = jnp.where(live_run,
                    cum_before[row] + (order - start[row]),
                    -1)
    return found, pos


class StreamedRuns:
    """Read-side scans over host-resident run planes of any length.

    ``tile`` rows stream through the device per step; one compile per
    tile shape (all tiles are padded to ``tile``)."""

    def __init__(self, ordp, lenp, tile: int = 1 << 20):
        _require(len(ordp) == len(lenp), "plane length mismatch")
        _require(tile >= 1, "tile must be positive")
        self.tile = int(tile)
        n = len(ordp)
        self.ntiles = max(1, -(-n // self.tile))
        # Keep the caller's arrays as-is (np.asarray over a memmap is
        # zero-copy; a whole-plane np.pad would materialize the full
        # plane in host RAM — the one thing this module must not do).
        # Only the final partial tile pads, inside _tile().
        self.ordp = np.asarray(ordp)
        self.lenp = np.asarray(lenp)
        # Carry table: live chars BEFORE each tile (the host-side analog
        # of sp_runs' all-gathered shard totals) + per-tile order bounds
        # so order lookups skip tiles that cannot contain the order.
        # All computed HOST-side in int64 (no device round-trips, and the
        # device in-tile cumsums are i32, so each tile's live total is
        # required to fit i32 — shrink ``tile`` otherwise).
        totals = np.empty(self.ntiles, np.int64)
        self.omin = np.empty(self.ntiles, np.int64)
        self.omax = np.empty(self.ntiles, np.int64)
        for t in range(self.ntiles):
            s = t * self.tile
            o = np.asarray(self.ordp[s:s + self.tile], np.int64)
            l = np.asarray(self.lenp[s:s + self.tile], np.int64)
            totals[t] = int(np.where(o > 0, l, 0).sum())
            _require(totals[t] < 2 ** 31,
                     f"tile {t} live total {totals[t]} overflows the "
                     "i32 in-tile cumsum; use a smaller tile")
            occ = np.abs(o)
            mask = occ > 0
            self.omin[t] = (occ[mask] - 1).min() if mask.any() else -1
            self.omax[t] = (occ[mask] - 1 + l[mask]).max() \
                if mask.any() else -1
        self.carry = np.concatenate(([0], np.cumsum(totals)))
        self._cached_t = -1
        self._cached = None

    def _tile(self, t: int):
        # One-entry upload cache: repeated lookups overwhelmingly hit
        # the same tile, and a fresh H2D transfer per call would cost
        # tile * 8 bytes each time.
        if self._cached_t != t:
            s = t * self.tile
            o = np.asarray(self.ordp[s:s + self.tile], np.int32)
            l = np.asarray(self.lenp[s:s + self.tile], np.int32)
            if len(o) < self.tile:  # final partial tile only
                pad = self.tile - len(o)
                o = np.pad(o, (0, pad))
                l = np.pad(l, (0, pad))
            self._cached = (jnp.asarray(o), jnp.asarray(l))
            self._cached_t = t
        return self._cached

    def live_total(self) -> int:
        return int(self.carry[-1])

    def position_of_live_rank(self, rank1: int):
        """1-based live rank -> (global run row, 1-based in-run offset);
        (-1, 0) when ``rank1`` exceeds the live total (the documented
        out-of-range sentinel, unlike an ambiguous (0, 0))."""
        if rank1 < 1 or rank1 > self.live_total():
            return -1, 0
        t = int(np.searchsorted(self.carry[1:], rank1, side="left"))
        row, off = _tile_rank(*self._tile(t),
                              rank1 - int(self.carry[t]))
        return t * self.tile + int(row), int(off)

    def order_to_position(self, order: int) -> int:
        """CRDT order -> 0-based content position (live chars strictly
        before it), or -1 when the order is unknown or tombstoned —
        the same contract as ``parallel.sp_runs.order_to_position``."""
        for t in range(self.ntiles):
            # Host-side prune: a tile whose [min, max) order envelope
            # misses ``order`` never uploads (most lookups touch ONE
            # tile; without this, a miss would stream the whole plane).
            if self.omax[t] < 0 or not (self.omin[t] <= order
                                        < self.omax[t]):
                continue
            found, pos = _tile_order(*self._tile(t), order)
            if bool(found):
                p = int(pos)
                return -1 if p < 0 else int(self.carry[t]) + p
        return -1
