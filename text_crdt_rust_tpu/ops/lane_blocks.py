"""Per-lane K-row block machinery shared by the BLOCKED streaming
engines (``rle_lanes`` / ``rle_lanes_mixed``).

The un-blocked lanes engines pay a whole-``[CAP, B]`` plane pass (plus a
log2(CAP) roll cumsum) on every step.  The blocked layout is ``ops.rle``'s
structure carried into the per-lane world: runs live in K-row physical
blocks, per-lane logical block tables (`mutations.rs:623-669`'s leaf
locality) order them, and a step touches NB block sums plus ONE K-row
block — O(NB + K) rows instead of O(CAP log CAP).

The per-lane twist vs ``ops.rle``: every block index is a ``[1, B]``
LANE VECTOR, not a scalar, so blocks cannot be addressed with a dynamic
slice.  Gather/scatter instead loop over the NB blocks, selecting per
lane (one plane-read equivalent), and every in-block pass (cumsum,
splice, 3-way split) then costs K rows, which is where the win lives
(K << CAP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def vshift_up(x, amt, max_amt: int) -> jax.Array:
    """Rows shifted toward LOWER indices by per-lane ``amt`` in
    [0, max_amt] (the lane-vector twin of ``rle._shift_rows_up``):
    out[j, b] = x[j + amt[0, b], b].  Binary decomposition: one static
    roll per bit, selected per lane."""
    n = x.shape[0]
    out = x
    for bit in range(max(max_amt, 1).bit_length()):
        s = (1 << bit) % n
        if s:
            out = jnp.where((amt >> bit) & 1 != 0,
                            pltpu.roll(out, n - s, axis=0), out)
    return out


def _block(nb, K: int):
    """Rows of physical block ``nb`` (a loop index): a K-row slice at an
    aligned dynamic offset."""
    return pl.ds(pl.multiple_of(nb * K, K), K)


def gather_block(plane_ref, b, K: int, NB: int, hi=None) -> jax.Array:
    """Per-lane block gather: ``out[j, lane] = plane[b[0,lane]*K + j,
    lane]`` as an NB-way select over the K-row blocks.  A loop over the
    blocks, not a Python unroll: the kernel's code and its compile stay
    the same size at any NB (the editing traces reach 177 blocks).

    ``hi`` (a traced int32 scalar, default ``NB``) bounds the walk to
    blocks ``[0, hi)``: a caller that knows no lane selects a block at
    or above ``hi`` skips the rest.  The same holds for the other three
    helpers."""
    return lax.fori_loop(
        1, NB if hi is None else hi,
        lambda nb, ws: jnp.where(b == nb, plane_ref[_block(nb, K), :], ws),
        plane_ref[0:K, :])


def gather_head(plane_ref, b, K: int, NB: int, hi=None) -> jax.Array:
    """Row 0 of per-lane block ``b`` as a [1, B] vector."""
    return lax.fori_loop(
        1, NB if hi is None else hi,
        lambda nb, h: jnp.where(
            b == nb, plane_ref[pl.ds(pl.multiple_of(nb * K, K), 1), :], h),
        plane_ref[0:1, :])


def scatter_block(plane_ref, b, ws, act, K: int, NB: int,
                  hi=None) -> None:
    """Write ``ws`` back to per-lane block ``b`` on ``act`` lanes."""

    def body(nb, c):
        rows = _block(nb, K)
        plane_ref[rows, :] = jnp.where(act & (b == nb), ws,
                                       plane_ref[rows, :])
        return c

    lax.fori_loop(0, NB if hi is None else hi, body, 0)


def scatter_block2(plane_ref, b1, ws1, b2, ws2, act, K: int,
                   NB: int, hi=None) -> None:
    """Two-block scatter (block split: keep-half to ``b1``, moved half
    to the fresh block ``b2``; b1 != b2 per lane)."""

    def body(nb, c):
        rows = _block(nb, K)
        v = jnp.where(act & (b1 == nb), ws1, plane_ref[rows, :])
        plane_ref[rows, :] = jnp.where(act & (b2 == nb), ws2, v)
        return c

    lax.fori_loop(0, NB if hi is None else hi, body, 0)


def oracle_runs(oracle):
    """RLE-compress a host oracle body into the lanes engines' run rows:
    ``(signed_starts, lens)`` — ±(order+1) of each run head and its
    length, in document order.

    A char extends the current run only when its order is consecutive,
    its tombstone flag matches, AND its ``origin_left`` chains to its
    predecessor.  The chain condition is load-bearing, not cosmetic:
    the kernels' YATA scan skips whole runs on the premise that every
    non-head char's origin_left is its own predecessor (`doc.rs`
    span-skip; see ``rle_lanes_mixed.do_remote_insert``'s merge
    predicate) — seeding a run across an unchained boundary would let a
    later concurrent-insert scan skip a char it must evaluate and land
    the insert at a diverged cursor."""
    n = oracle.n
    if n == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64))
    order = oracle.order[:n].astype(np.int64)
    deleted = oracle.deleted[:n]
    oleft = oracle.origin_left[:n].astype(np.int64)
    brk = np.ones(n, dtype=bool)
    brk[1:] = ((order[1:] != order[:-1] + 1)
               | (deleted[1:] != deleted[:-1])
               | (oleft[1:] != order[:-1]))
    starts = np.nonzero(brk)[0]
    lens = np.diff(np.append(starts, n)).astype(np.int64)
    sign = np.where(deleted[starts], -1, 1).astype(np.int64)
    return sign * (order[starts] + 1), lens


def pack_lane_blocks(signed_starts, lens, *, K: int, NB: int, NBT: int,
                     capacity: int):
    """Seed ONE lane's blocked state columns from a run list (the
    residency restore/upload path of ``serve.lanes_backend``): pack runs
    into K-row physical blocks at most ``(K-1)//2`` rows each — the same
    half-full occupancy a leaf split leaves, so every seeded block keeps
    the out-of-blocks row bound the serve capacity probe relies on AND
    immediate insert traffic never needs a split to find headroom.

    Returns ``(cols, run_block)``: the numpy state columns
    ``(ordp[capacity], lenp[capacity], nlog[1], blkord[NBT], rws[NBT],
    liv[NBT], raw[NBT])`` with blocks in identity logical order
    (blkord[l] = l), plus the run -> physical-block assignment
    (i64[R]) so hint seeding stays bit-consistent with the packing
    (one occupancy rule, one owner)."""
    R = len(signed_starts)
    per = max(1, (K - 1) // 2)
    nblocks = -(-R // per) if R else 0
    assert nblocks <= NB, (
        f"{R} runs need {nblocks} blocks of {per} rows but only {NB} "
        f"blocks exist (the fits_doc probe should have refused)")
    ordp = np.zeros(capacity, np.int32)
    lenp = np.zeros(capacity, np.int32)
    blkord = np.zeros(NBT, np.int32)
    rws = np.zeros(NBT, np.int32)
    liv = np.zeros(NBT, np.int32)
    raw = np.zeros(NBT, np.int32)
    for b in range(nblocks):
        lo, hi = b * per, min((b + 1) * per, R)
        rows = hi - lo
        ordp[b * K: b * K + rows] = signed_starts[lo:hi]
        lenp[b * K: b * K + rows] = lens[lo:hi]
        blkord[b] = b
        rws[b] = rows
        live = signed_starts[lo:hi] > 0
        liv[b] = int(lens[lo:hi][live].sum())
        raw[b] = int(lens[lo:hi].sum())
    nlog = np.asarray([max(nblocks, 1)], np.int32)
    run_block = np.arange(R, dtype=np.int64) // per
    return (ordp, lenp, nlog, blkord, rws, liv, raw), run_block


def lane_apply_partial(a, i_p, bo, bl, cs, ce, idx):
    """Split run row ``i_p`` around its covered live sub-range
    ``[cs, ce)`` into [head?] [tombstone mid] [tail?] (<= +2 rows), per
    lane where ``a`` — the per-lane 3-way delete split shared by the
    blocked kernels (the in-block twin of the whole-plane transform in
    ``rle_lanes.do_delete`` / ``rle_lanes_mixed.apply_partial``).
    ``idx`` is the row iota of the plane being edited."""
    from .rle_lanes import _vrow, _vshift

    o = _vrow(bo, i_p)
    ln = _vrow(bl, i_p)
    cs_i = _vrow(cs, i_p)
    ce_i = _vrow(ce, i_p)
    cov_i = ce_i - cs_i
    has_head = (cs_i > 0) & a
    has_tail = (ce_i < ln) & a
    amt = has_head.astype(jnp.int32) + has_tail.astype(jnp.int32)
    so = _vshift(bo, amt)
    sl = _vshift(bl, amt)
    no = jnp.where(idx <= i_p, bo, so)
    nl = jnp.where(idx <= i_p, bl, sl)
    p0o = jnp.where(has_head, o, -(o + cs_i))
    p0l = jnp.where(has_head, cs_i, cov_i)
    p1o = jnp.where(has_head, -(o + cs_i), o + ce_i)
    p1l = jnp.where(has_head, cov_i, ln - ce_i)
    w0 = a & (idx == i_p)
    no = jnp.where(w0, p0o, no)
    nl = jnp.where(w0, p0l, nl)
    w1 = a & (idx == i_p + 1) & (amt >= 1)
    no = jnp.where(w1, p1o, no)
    nl = jnp.where(w1, p1l, nl)
    w2 = a & (idx == i_p + 2) & (amt == 2)
    no = jnp.where(w2, o + ce_i, no)
    nl = jnp.where(w2, ln - ce_i, nl)
    return no, nl, amt
