"""Per-lane divergent MIXED replay: B distinct documents, each applying
its OWN local/remote op stream — the production sync shape.

``ops.rle_mixed`` runs the full op surface (KIND_LOCAL/REMOTE_INS/
REMOTE_DEL, `doc.rs:242-348`) but in LOCKSTEP: one shared scalar stream
across identical lanes.  ``ops.rle_lanes`` runs divergent per-lane
streams but refuses remote ops.  This engine is the round-5 unification
(VERDICT r4 missing #2): thousands of *different* documents each
receiving *its own* remote-op stream, one op per lane per kernel step.

Design — rle_lanes' lane-vector layout carried over to the remote paths:

- document state is the un-blocked run column pair ``ordp/lenp``
  [CAP, B] (±(order+1), len) plus ``rows`` [1, B]; every op scalar of
  ``rle_mixed`` becomes a [1, B] lane vector; splices stay <= 3 rows so
  per-lane dynamic shifts are two static ``pltpu.roll``s blended by
  per-lane masks (the rle_lanes trick);
- **per-lane by-order tables** ``oll/orl/rkl`` [OCAP, B] (row = order,
  lane = doc) replace rle_mixed's 128-orders/row packed tables: each
  lane has its own order space, so the packing collapses to one row per
  order and reads/writes are one masked [OCAP, B] pass.  Prefilled
  host-side per lane (`batch._prefill_scatter`), sentinel −2 = unknown;
  unknown entries are never probed (every existing char's entry was
  prefilled or written by the local-insert path at insert time);
- **no order->block hint table**: the lanes layout always works on the
  whole [CAP, B] plane, so order lookup IS the one vectorized
  range-test pass that rle_mixed's ``ordblk`` miss-path falls back to —
  there is nothing to hint, go stale, or self-heal;
- **run-level YATA integrate** (`doc.rs:167-234`) with PER-LANE scan
  state: (cursor, scanning, scan_start, done) are [1, B] vectors; the
  while-loop runs until every lane breaks (conflict-free lanes break on
  the first probe, `doc.rs:192-194`, so iterations = the max conflict
  depth across lanes, not the sum).  The raw prefix sum the scan
  descends on is HOISTED out of the loop — the scan never mutates
  state, so one ``_vcumsum`` serves every probe of the step;
- **one-pass remote delete**: runs are disjoint ORDER intervals, so a
  target range ``[t, t+dlen)`` fully covers every run it touches except
  at most the two holding its endpoints — one interval-clip pass flips
  the full covers and 3-way-splits the <= 2 partial runs, exactly the
  local-delete shape keyed by orders (no fragmentation walk, no dmax
  pre-chunking); covered DEAD runs count toward the idempotency total
  without flipping (`double_delete.rs:6-9`).

State (ordp, lenp, rows, oll, orl) is a kernel input AND output — chunk
N+1 resumes from chunk N on device (the config-5 streaming warm start),
with each chunk's compile-known table entries merged in at step 0 via
the −2 sentinel.  ``rkl`` is read-only (author ranks are compile-time
facts; the host accumulates the full table across chunks).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .batch import (
    KIND_LOCAL,
    KIND_REMOTE_DEL,
    KIND_REMOTE_INS,
    OpTensors,
    _prefill_scatter,
    _require,
    fused_width,
    fused_width_checked,
)
from .rle import fused_splice_rows
from .rle_lanes import (
    LanesResult,
    _lane_tile,
    _live_prefix,
    _shared_cum_gate,
    _vcumsum,
    _vrow,
    _vshift,
)

TAB_UNKNOWN = -2  # by-order table sentinel: entry not yet known


def _fused_table_writes(oll, orl, oidx, act, st, il, lrun, left, right):
    """By-order table upkeep for a (possibly fused) local insert —
    shared by the un-blocked and blocked mixed kernels (each binds its
    own ``oll``/``orl``/``oidx`` via ``partial``): every sub-run head
    (orders st + k*L) logs the SHARED left neighbour; sub-run k's span
    logs origin_right = patch k-1's head (k=0 keeps the raw successor)
    — exactly what the unfused per-patch steps would have written, so
    later YATA scans read identical origins.  w == 1 (lrun == il)
    degenerates to the old head write + whole-span right."""
    span = act & (oidx >= st) & (oidx < st + il)
    qoff = oidx - st
    ls = jnp.maximum(lrun, 1)
    oll[:] = jnp.where(span & (qoff % ls == 0), left, oll[:])
    orl[:] = jnp.where(
        span, jnp.where(qoff < ls, right,
                        st + (qoff // ls - 1) * ls), orl[:])


def _mixed_lanes_kernel(
    kind_ref, pos_ref, dlen_ref, dtgt_ref, olop_ref, orop_ref, rk_ref,
    ilen_ref, start_ref,                        # [CHUNK, B] VMEM op columns
    w_ref,                                      # [CHUNK, B] rows_per_step
    ord0_ref, len0_ref, rows0_ref,              # warm-start state inputs
    oll0_ref, orl0_ref,                         # prior table state [OCAP, B]
    olld_ref, orld_ref,                         # this stream's prefill delta
    rkl_ref,                                    # ranks (read-only, full)
    ol_ref, or_ref,                             # [CHUNK, B] origin outputs
    ordp, lenp, rowsv,                          # state outputs (working)
    oll, orl,                                   # table outputs (working)
    err_ref,
    *, CAP: int, OCAP: int, CHUNK: int, WMAX: int = 1,
    SHARED_CUM: bool = False,
):
    B = ordp.shape[1]
    i = pl.program_id(1)
    idx = lax.broadcasted_iota(jnp.int32, (CAP, B), 0)
    oidx = lax.broadcasted_iota(jnp.int32, (OCAP, B), 0)
    root_i = jnp.int32(-1)  # ROOT_ORDER as i32
    root_u = jnp.uint32(0xFFFFFFFF)

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when(i == 0)
    def _init():
        ordp[:] = ord0_ref[:]
        lenp[:] = len0_ref[:]
        rowsv[:] = rows0_ref[:]
        # Merge this stream's compile-known entries over the carried
        # tables (chunk N+1's new orders were −2 in chunk N's state).
        oll[:] = jnp.where(olld_ref[:] != TAB_UNKNOWN, olld_ref[:],
                           oll0_ref[:])
        orl[:] = jnp.where(orld_ref[:] != TAB_UNKNOWN, orld_ref[:],
                           orl0_ref[:])
        err_ref[:] = jnp.zeros_like(err_ref)

    # ---- per-lane by-order table ops ------------------------------------

    def t_read(tab, o):
        """tab[o[lane], lane] as [1, B]; o values < 0 read row 0 (callers
        mask ROOT probes before use)."""
        oc = jnp.clip(o, 0, OCAP - 1)
        return jnp.sum(jnp.where(oidx == oc, tab[:], 0), axis=0,
                       keepdims=True)

    def t_write(tab, act, o, v):
        tab[:] = jnp.where(act & (oidx == o), v, tab[:])

    def t_write_run(tab, act, st, ln, v):
        tab[:] = jnp.where(act & (oidx >= st) & (oidx < st + ln), v,
                           tab[:])

    # ---- order -> run / position lookups --------------------------------

    def find_run_of_order(o, need):
        """Per-lane row/run containing order ``o`` ([1, B]): one
        vectorized range test over the whole plane.  Raises the
        missing-order flag for ``need`` lanes with no hit."""
        bo = ordp[:]
        so = jnp.abs(bo) - 1
        hit = (bo != 0) & (so <= o) & (o < so + lenp[:])
        found = jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True) > 0
        row = jnp.min(jnp.where(hit, idx, CAP), axis=0, keepdims=True)

        @pl.when(jnp.any(need & ~found))
        def _missing():
            err_ref[2:3, :] = jnp.where(need & ~found, 1, err_ref[2:3, :])

        return jnp.where(found, row, 0), found

    def raw_pos_of_order(o, need):
        """Per-lane RAW document position of the char with order ``o``."""
        row, _ = find_run_of_order(o, need)
        raw_before = jnp.sum(jnp.where(idx < row, lenp[:], 0), axis=0,
                             keepdims=True)
        so_hit = jnp.abs(_vrow(ordp[:], row)) - 1
        return raw_before + (o - so_hit)

    def cursor_after(o, need):
        is_root = o == root_i
        # An unknown table entry (sentinel −2) must flag, not silently
        # resolve as order 0 (review r5).
        unknown = need & (o == TAB_UNKNOWN)

        @pl.when(jnp.any(unknown))
        def _unk():
            err_ref[2:3, :] = jnp.where(unknown, 1, err_ref[2:3, :])

        p = raw_pos_of_order(jnp.maximum(o, 0), need & ~is_root)
        return jnp.where(is_root, 0, p + 1)

    # ---- local ops (rle_lanes paths + table upkeep) ---------------------

    def flag_capacity(act, need=2):
        """Flag err row 0 where the lane lacks ``need`` spare rows
        (delete splits need 2; a fused W-row insert needs w + 1)."""
        over = act & (rowsv[:] + need > CAP)

        @pl.when(jnp.any(over))
        def _cap():
            err_ref[0:1, :] = jnp.where(over, 1, err_ref[0:1, :])

    def apply_partial(a, i_p, bo, bl, cs, ce):
        """Split run row ``i_p`` around its covered sub-range
        ``[cs, ce)`` into [head?] [tombstone mid] [tail?] (<= +2 rows),
        per lane where ``a``.  The signed-start fix-up covers LIVE runs
        only (partial coverage of a dead run never reaches here)."""
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

    def do_local_delete(act, p, d, lv=None, cum=None):
        """Whole-doc single-pass tombstone (rle_lanes.do_delete)."""
        flag_capacity(act)
        bo = ordp[:]
        bl = lenp[:]
        if cum is None:
            lv, cum = _live_prefix(bo, bl)
        before = cum - lv
        rem = jnp.where(act, d, 0)
        cs = jnp.clip(p - before, 0, lv)
        ce = jnp.clip(p + rem - before, 0, lv)
        cov = ce - cs
        tot = jnp.sum(cov, axis=0, keepdims=True)

        @pl.when(jnp.any(act & (tot < rem)))
        def _bad():
            err_ref[1:2, :] = jnp.where(act & (tot < rem), 1,
                                        err_ref[1:2, :])

        full = (cov > 0) & (cov == bl)
        part = (cov > 0) & jnp.logical_not(full)
        npart = jnp.sum(part.astype(jnp.int32), axis=0, keepdims=True)
        i1 = jnp.min(jnp.where(part, idx, CAP), axis=0, keepdims=True)
        i2 = jnp.max(jnp.where(part, idx, -1), axis=0, keepdims=True)
        bo = jnp.where(act & full, -bo, bo)

        bo, bl, a2 = apply_partial(act & (npart >= 1), i2, bo, bl, cs, ce)
        bo, bl, a1 = apply_partial(act & (npart == 2), i1, bo, bl, cs, ce)
        ordp[:] = bo
        lenp[:] = bl
        rowsv[:] = rowsv[:] + jnp.where(act, a1 + a2, 0)

    fused_table_writes = partial(_fused_table_writes, oll, orl, oidx)

    def do_local_insert(act, k, p, il, st, w, lv=None, cum=None):
        """rle_lanes.do_insert + by-order table upkeep (the origins a
        local insert discovers at apply time, `doc.rs:447-453`).
        ``w`` > 1 is a FUSED backwards-burst step: W stride-L rows in
        one shift, the ``ops.rle`` ``_insert_splice`` contract.
        ``lv``/``cum`` may be the step-hoisted PRE-DELETE live prefix
        (valid: shared-cum mode excludes same-lane delete+insert
        steps); ``bo``/``bl`` stay FRESH so the whole-plane writes
        preserve the delete branch's results on other lanes."""
        rows = rowsv[:]
        flag_capacity(act, w + 1)
        bo = ordp[:]
        bl = lenp[:]
        if cum is None:
            lv, cum = _live_prefix(bo, bl)
        local = jnp.where(act, p, 0)
        i_r = jnp.sum(((cum < local) & (idx < rows)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(bo, i_r)
        l_r = _vrow(bl, i_r)
        off = local - (_vrow(cum, i_r) - _vrow(lv, i_r))

        left = jnp.where(p == 0, root_i, (o_r - 1) + (off - 1))
        no, nl, amt, mrg, is_split, lrun = fused_splice_rows(
            bo, bl, idx, p, i_r, o_r, l_r, off, il, st, w, WMAX,
            _vshift, active=act)

        nxt_in_blk = _vrow(bo, i_r + 1)
        first_o = _vrow(bo, 0)
        succ_p0 = jnp.where(rows > 0, first_o, 0)
        succ_after = jnp.where(i_r + 1 < rows, nxt_in_blk, 0)
        succ = jnp.where(p == 0, succ_p0,
                         jnp.where(is_split, o_r + off, succ_after))
        right = jnp.where(succ == 0, root_i, jnp.abs(succ) - 1)
        ordp[:] = no
        lenp[:] = nl
        rowsv[:] = rows + amt

        fused_table_writes(act, st, il, lrun, left, right)
        ol_ref[pl.ds(k, 1), :] = jnp.where(
            act, left.astype(jnp.uint32), ol_ref[pl.ds(k, 1), :])
        or_ref[pl.ds(k, 1), :] = jnp.where(
            act, right.astype(jnp.uint32), or_ref[pl.ds(k, 1), :])

    # ---- remote insert (`doc.rs:274-293` -> integrate) ------------------

    def integrate_cursor(act, my_rank, o_left, o_right):
        """Per-lane YATA conflict scan over runs (rle_mixed
        ``integrate_cursor`` with [1, B] scan state).  The raw prefix is
        hoisted: the scan mutates nothing, so one cumsum serves every
        probe of every lane this step."""
        cumraw = _vcumsum(lenp[:])
        n = jnp.sum(lenp[:], axis=0, keepdims=True)
        cursor0 = cursor_after(o_left, act)
        left_cursor = cursor0

        def run_at_raw(c):
            i_r = jnp.sum(((cumraw <= c) & (idx < rowsv[:])).astype(
                jnp.int32), axis=0, keepdims=True)
            o_r = _vrow(ordp[:], i_r)
            l_r = _vrow(lenp[:], i_r)
            off = c - (_vrow(cumraw, i_r) - l_r)
            return o_r, l_r, off

        # Loop-carried lane masks ride as i32 0/1: Mosaic materializes
        # loop-carried [1, T] i1 vectors as i8 and has no i8->i1
        # truncation, so a bool carry fails to compile on real TPU
        # (the cfg5r MosaicError in perf/compile_pin_r5.log).
        def cond(state):
            cursor, scanning_i, scan_start, done_i = state
            return jnp.any((done_i == 0) & (cursor < n))

        def body(state):
            cursor, scanning_i, scan_start, done_i = state
            scanning = scanning_i != 0
            done = done_i != 0
            o_r, l_r, off = run_at_raw(cursor)
            so = jnp.abs(o_r) - 1
            other_order = so + off
            live = ~done & (cursor < n)
            other_left = t_read(oll, other_order)
            other_right = t_read(orl, other_order)
            other_rank = t_read(rkl_ref, other_order)
            olc = cursor_after(other_left, live)
            brk = (other_order == o_right) | (olc < left_cursor)
            eq = ~brk & (olc == left_cursor)
            gt = my_rank > other_rank
            brk = brk | (eq & ~gt & (o_right == other_right))
            starts_scan = eq & ~gt & (o_right != other_right)
            new_scan_start = jnp.where(
                live & starts_scan & ~scanning, cursor, scan_start)
            # i32-VALUED selects: a vector select whose RESULTS are i1
            # makes Mosaic round-trip the mask through i8 (the trunci
            # MosaicError); selecting 0/1 i32 keeps it on the vreg path.
            new_scanning_i = jnp.where(
                live & eq,
                jnp.where(gt, 0,
                          jnp.where(o_right == other_right, scanning_i,
                                    1)),
                scanning_i)
            contains_right = (o_right > other_order) & (o_right < so + l_r)
            step = jnp.where(contains_right, o_right - other_order,
                             l_r - off)
            new_cursor = jnp.where(live & ~brk, cursor + step, cursor)
            new_done_i = jnp.maximum(
                done_i, jnp.where(brk | (cursor >= n), 1, 0))
            return (new_cursor, new_scanning_i, new_scan_start,
                    new_done_i)

        zero = jnp.zeros_like(cursor0)  # [1, B] i32 False
        init = (cursor0, zero, cursor0, (~act).astype(jnp.int32))
        cursor, scanning_i, scan_start, _ = lax.while_loop(
            cond, body, init)
        return jnp.where(scanning_i != 0, scan_start, cursor), cumraw

    def do_remote_insert(act, k, my_rank, o_left, o_right, il, st):
        flag_capacity(act)
        c, cumraw = integrate_cursor(act, my_rank, o_left, o_right)
        rows = rowsv[:]
        bo = ordp[:]
        bl = lenp[:]
        local = jnp.where(act, c, 0)
        i_r = jnp.sum(((cumraw < local) & (idx < rows)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(bo, i_r)
        l_r = _vrow(bl, i_r)
        off = local - (_vrow(cumraw, i_r) - l_r)

        # Raw-position splice (`rle_mixed._insert_splice_raw` lane-wise):
        # the split run may be a TOMBSTONE (preserve sign on the tail);
        # the merge fast path additionally requires a live predecessor
        # AND the op's origin_left chaining to the run's last char — the
        # YATA run-skip evaluates only run heads on the premise that
        # non-head chars' origin_left is their own predecessor, so an
        # unchained merge would hide a char the scan must evaluate.
        mrg = act & (c > 0) & (o_r > 0) & (off == l_r) & \
            ((st + 1) == (o_r + l_r)) & (o_left == o_r + l_r - 2)
        is_split = act & (c > 0) & (off < l_r)
        ins_at = jnp.where(c == 0, 0, i_r + 1)
        amt = jnp.where(jnp.logical_not(act) | mrg, 0,
                        jnp.where(is_split, 2, 1))
        so = _vshift(bo, amt)
        sl = _vshift(bl, amt)
        no = jnp.where(idx < ins_at, bo, so)
        nl = jnp.where(idx < ins_at, bl, sl)
        nl = jnp.where(is_split & (idx == i_r), off, nl)
        new_run = act & jnp.logical_not(mrg) & (idx == ins_at)
        no = jnp.where(new_run, st + 1, no)
        nl = jnp.where(new_run, il, nl)
        tail = is_split & (idx == ins_at + 1)
        tail_o = jnp.where(o_r > 0, o_r + off, o_r - off)
        no = jnp.where(tail, tail_o, no)
        nl = jnp.where(tail, l_r - off, nl)
        nl = jnp.where(mrg & (idx == i_r), l_r + il, nl)
        ordp[:] = no
        lenp[:] = nl
        rowsv[:] = rows + amt

        # Remote origins are compile-time facts already prefilled into
        # the tables; only the per-op outputs remain.
        ol_ref[pl.ds(k, 1), :] = jnp.where(
            act, o_left.astype(jnp.uint32), ol_ref[pl.ds(k, 1), :])
        or_ref[pl.ds(k, 1), :] = jnp.where(
            act, o_right.astype(jnp.uint32), or_ref[pl.ds(k, 1), :])

    # ---- remote delete (`doc.rs:295-340`) -------------------------------

    def do_remote_delete(act, t, dlen):
        """Order-interval tombstone in ONE pass (`doc.rs:295-340`
        without the fragmentation walk): runs are disjoint order
        intervals, so at most TWO covered runs are partial — the ones
        holding ``t`` and ``t+dlen-1`` — and every other covered run is
        fully inside ``[t, t+dlen)`` and flips wholesale.  Same shape as
        the local delete, keyed by ORDERS instead of live ranks; covered
        DEAD runs just count toward the idempotency total without
        flipping (`double_delete.rs:6-9`; excess counting is host-side
        per SURVEY).  Any ``dlen`` in one step — no dmax pre-chunking."""
        bo = ordp[:]
        bl = lenp[:]
        so = jnp.abs(bo) - 1
        occ = bo != 0
        cs = jnp.clip(t - so, 0, bl)
        ce = jnp.clip(t + dlen - so, 0, bl)
        cov = jnp.where(act & occ, ce - cs, 0)
        tot = jnp.sum(cov, axis=0, keepdims=True)
        rem = jnp.where(act, dlen, 0)

        @pl.when(jnp.any(act & (tot < rem)))
        def _bad():
            err_ref[1:2, :] = jnp.where(act & (tot < rem), 1,
                                        err_ref[1:2, :])

        live = bo > 0
        full = live & (cov > 0) & (cov == bl)
        part = live & (cov > 0) & jnp.logical_not(cov == bl)
        npart = jnp.sum(part.astype(jnp.int32), axis=0, keepdims=True)
        # Max growth is +2 per op: one run holding both endpoints 3-way
        # splits (+2), or the two endpoint runs each split one-sided
        # (+1 each).  Gate BOTH splits and the full flips so a flagged
        # delete is a clean no-op (review r5: overflow would let
        # pltpu.roll silently wrap the plane).
        tight = act & (npart > 0) & (rowsv[:] + 2 > CAP)

        @pl.when(jnp.any(tight))
        def _cap():
            err_ref[0:1, :] = jnp.where(tight, 1, err_ref[0:1, :])

        a = act & ~tight
        i1 = jnp.min(jnp.where(part, idx, CAP), axis=0, keepdims=True)
        i2 = jnp.max(jnp.where(part, idx, -1), axis=0, keepdims=True)
        bo = jnp.where(a & full, -bo, bo)

        bo, bl, a2 = apply_partial(a & (npart >= 1), i2, bo, bl, cs, ce)
        bo, bl, a1 = apply_partial(a & (npart == 2), i1, bo, bl, cs, ce)
        ordp[:] = bo
        lenp[:] = bl
        rowsv[:] = rowsv[:] + jnp.where(a, a1 + a2, 0)

    # ---- dispatch -------------------------------------------------------

    def op_body(k, _):
        kind = kind_ref[pl.ds(k, 1), :]
        p = pos_ref[pl.ds(k, 1), :]
        d = dlen_ref[pl.ds(k, 1), :]
        il = ilen_ref[pl.ds(k, 1), :]
        st = start_ref[pl.ds(k, 1), :]
        w = jnp.maximum(w_ref[pl.ds(k, 1), :], 1)  # pad rows carry 0

        act_ld = (kind == KIND_LOCAL) & (d > 0)
        act_li = (kind == KIND_LOCAL) & (il > 0)
        act_ri = (kind == KIND_REMOTE_INS) & (il > 0)
        act_rd = (kind == KIND_REMOTE_DEL) & (d > 0)

        if SHARED_CUM:
            # One live prefix serves both LOCAL branches (no lane
            # deletes AND inserts in one step, and both-branch steps
            # outnumber no-local steps — both checked statically).
            lv, cum = _live_prefix(ordp[:], lenp[:])
        else:
            lv = cum = None

        @pl.when(jnp.any(act_ld))
        def _():
            do_local_delete(act_ld, p, d, lv, cum)

        @pl.when(jnp.any(act_li))
        def _():
            do_local_insert(act_li, k, p, il, st, w, lv, cum)

        @pl.when(jnp.any(act_ri))
        def _():
            do_remote_insert(act_ri, k, rk_ref[pl.ds(k, 1), :],
                             olop_ref[pl.ds(k, 1), :],
                             orop_ref[pl.ds(k, 1), :], il, st)

        @pl.when(jnp.any(act_rd))
        def _():
            do_remote_delete(act_rd, dtgt_ref[pl.ds(k, 1), :], d)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)


@dataclasses.dataclass
class LanesMixedResult(LanesResult):
    """``LanesResult`` + per-lane by-order table state (the warm-start
    carry) and the missing-order flag (err row 2)."""

    oll: jax.Array = None   # i32[OCAP, B]
    orl: jax.Array = None   # i32[OCAP, B]

    def check(self) -> None:
        super().check()
        err = np.asarray(self.err)
        if err[2].max() != 0:
            raise RuntimeError(
                f"order lookup missed on lanes "
                f"{np.nonzero(err[2])[0][:8].tolist()}: an op referenced "
                f"an order absent from device state")

    def state(self):
        """(ordp, lenp, rows, oll, orl) — the next chunk's ``init``."""
        return self.ordp, self.lenp, self.rows, self.oll, self.orl


def lane_tables(stacked: OpTensors, ocap: int):
    """Per-lane by-order prefill: (oll, orl, rkl) as i32[OCAP, B] numpy,
    sentinel −2 for unknown entries (−1 is ROOT).  Everything the
    compiler knows: remote head origins, within-run chains, author
    ranks (`batch._prefill_scatter` per lane)."""
    kinds = np.asarray(stacked.kind)
    assert kinds.ndim == 2, "lane_tables takes stacked [S, B] streams"
    B = kinds.shape[1]
    oll = np.full((B, ocap), TAB_UNKNOWN, np.int32)
    orl = np.full((B, ocap), TAB_UNKNOWN, np.int32)
    rkl = np.zeros((B, ocap), np.int32)
    for b in range(B):
        per = jax.tree.map(lambda a: np.asarray(a)[:, b], stacked)
        sc = _prefill_scatter(per)
        if sc is None:
            continue
        oll[b, sc["ol"][0]] = sc["ol"][1].astype(np.uint32).astype(
            np.int64).astype(np.int32)  # u32 ROOT -> -1
        orl[b, sc["or"][0]] = sc["or"][1].astype(np.uint32).astype(
            np.int64).astype(np.int32)
        rkl[b, sc["rank"][0]] = sc["rank"][1]
    return (np.ascontiguousarray(oll.T), np.ascontiguousarray(orl.T),
            np.ascontiguousarray(rkl.T))


@functools.lru_cache(maxsize=32)
def _build_call(s_pad: int, B: int, capacity: int, ocap: int, chunk: int,
                interpret: bool, lane_tile: int | None = None,
                shared_cum: bool = False, wmax: int = 1):
    """Shape-keyed cache (streaming chunks share one compiled kernel)."""
    T = lane_tile or _lane_tile(B)
    _require(B % T == 0, f"lane_tile {T} must divide batch {B}")
    col = lambda: pl.BlockSpec((chunk, T), lambda lb, i: (i, lb),
                               memory_space=pltpu.VMEM)
    whole = lambda rows: pl.BlockSpec(
        (rows, T), lambda lb, i: (0, lb), memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        partial(_mixed_lanes_kernel, CAP=capacity, OCAP=ocap,
                CHUNK=chunk, WMAX=wmax, SHARED_CUM=shared_cum),
        grid=(B // T, s_pad // chunk),
        in_specs=[col() for _ in range(10)] + [
            whole(capacity), whole(capacity), whole(1),
            whole(ocap), whole(ocap),           # prior table state
            whole(ocap), whole(ocap),           # prefill delta
            whole(ocap),                        # ranks (read-only)
        ],
        out_specs=[
            col(), col(),
            whole(capacity), whole(capacity), whole(1),
            whole(ocap), whole(ocap),
            whole(8),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, B), jnp.uint32),
            jax.ShapeDtypeStruct((s_pad, B), jnp.uint32),
            jax.ShapeDtypeStruct((capacity, B), jnp.int32),
            jax.ShapeDtypeStruct((capacity, B), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.int32),
            jax.ShapeDtypeStruct((ocap, B), jnp.int32),
            jax.ShapeDtypeStruct((ocap, B), jnp.int32),
            jax.ShapeDtypeStruct((8, B), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
        ),
        interpret=interpret,
    )
    return jax.jit(lambda *a: call(*a))


def make_replayer_lanes_mixed(
    ops: OpTensors,
    capacity: int,
    order_capacity: int = 0,
    chunk: int = 128,
    init=None,
    rkl=None,
    interpret: bool = False,
    lane_tile: int | None = None,
):
    """Build a jitted per-lane MIXED replayer for stacked per-doc streams
    (``stack_ops`` output: every column [S, B]; kinds may differ per
    lane per step).

    ``capacity`` counts run rows per document; ``order_capacity`` rows
    of by-order table per document (0 = fit this stream: max per-lane
    total orders, +lmax headroom).  ``init`` is a prior result's
    ``state()`` 5-tuple — the streaming warm start; None = empty docs.
    ``rkl`` overrides the rank table (i32[OCAP, B]; pass the host-
    accumulated full table when chunk-chaining so earlier chunks' ranks
    stay visible); None = this stream's prefill.  Remote deletes of any
    length apply in one step (the one-pass interval delete needs no
    dmax pre-chunking).
    """
    kinds = np.asarray(ops.kind)
    _require(kinds.ndim == 2, "rle_lanes_mixed takes stacked per-doc "
             "streams ([S, B] columns; see batch.stack_ops)")
    S, B = kinds.shape
    _require(capacity >= 8, "capacity must hold a few runs")
    wmax = fused_width(ops)
    _require(wmax + 1 < capacity,
             f"fused rows_per_step {wmax} cannot fit capacity "
             f"{capacity}")
    s_pad = max(((S + chunk - 1) // chunk) * chunk, chunk)

    adv = np.asarray(ops.order_advance, dtype=np.int64).sum(axis=0)
    base = 0
    if init is not None and init[3] is not None:
        base = init[3].shape[0]
    ocap = order_capacity or max(
        ((int(adv.max() + ops.lmax) + base + 7) // 8) * 8, 8)
    _require(ocap % 8 == 0, "order_capacity must be a multiple of 8")

    def staged_col(get):
        a = np.asarray(get(ops), dtype=np.uint32).view(np.int32)
        return jnp.asarray(np.pad(a, ((0, s_pad - S), (0, 0))))

    staged = tuple(staged_col(g) for g in (
        lambda o: o.kind, lambda o: o.pos, lambda o: o.del_len,
        lambda o: o.del_target, lambda o: o.origin_left,
        lambda o: o.origin_right, lambda o: o.rank, lambda o: o.ins_len,
        lambda o: o.ins_order_start, lambda o: o.rows_per_step))

    olld, orld, rkl0 = lane_tables(ops, ocap)
    if rkl is None:
        rkl = rkl0
    else:
        rkl = np.asarray(rkl, np.int32)
        _require(rkl.shape == (ocap, B),
                 f"rkl shape {rkl.shape} != ({ocap}, {B})")

    if init is None:
        init = (jnp.zeros((capacity, B), jnp.int32),
                jnp.zeros((capacity, B), jnp.int32),
                jnp.zeros((1, B), jnp.int32),
                jnp.full((ocap, B), TAB_UNKNOWN, jnp.int32),
                jnp.full((ocap, B), TAB_UNKNOWN, jnp.int32))
    else:
        init = _grow_state(init, capacity, ocap, B)

    # Shared live prefix for the local branches: sound only when no
    # lane deletes AND inserts in the same step (a compiled replace
    # patch), and worth it only when steps firing BOTH local branches
    # outnumber steps firing neither — a remote-heavy stream with one
    # stray local op must not pay the hoist on every step (review r5).
    kn, dn, iln = (np.asarray(ops.kind), np.asarray(ops.del_len),
                   np.asarray(ops.ins_len))
    ld = (kn == KIND_LOCAL) & (dn > 0)
    li = (kn == KIND_LOCAL) & (iln > 0)
    shared_cum = (not bool(np.any(ld & li))
                  and _shared_cum_gate(ld.any(axis=1), li.any(axis=1),
                                       s_pad))
    jitted = _build_call(s_pad, B, capacity, ocap, chunk,
                         interpret, lane_tile, shared_cum, wmax)
    deltas = (jnp.asarray(olld), jnp.asarray(orld), jnp.asarray(rkl))

    def run(state=None) -> LanesMixedResult:
        ini = init if state is None else _grow_state(
            state, capacity, ocap, B)
        ol, orr, ordp, lenp, rows, oll, orl, err = jitted(
            *staged, *ini, *deltas)
        return LanesMixedResult(
            ordp=ordp, lenp=lenp, rows=rows, ol=ol[:S], orr=orr[:S],
            err=err, batch=B, oll=oll, orl=orl)

    return run


def _grow_state(state, capacity: int, ocap: int, B: int):
    """Pad a prior chunk's state 5-tuple up to this chunk's row/order
    capacities (rows pack at the front; tables are order-indexed) —
    streaming chunks may GROW both as documents accumulate."""
    from .rle_lanes import _grow_planes

    o0, l0, r0 = _grow_planes(state[:3], capacity, B)
    return (o0, l0, r0,
            _grow_table(state[3], ocap, B),
            _grow_table(state[4], ocap, B))


def _grow_table(t, ocap: int, B: int):
    """Pad a prior chunk's [ocap_old, B] table up to this chunk's ocap
    with the unknown sentinel (order spaces only grow)."""
    t = jnp.asarray(t, jnp.int32)
    _require(t.shape[0] <= ocap and t.shape[1] == B,
             f"table state shape {t.shape} incompatible with "
             f"({ocap}, {B})")
    if t.shape[0] == ocap:
        return t
    pad = jnp.full((ocap - t.shape[0], B), TAB_UNKNOWN, jnp.int32)
    return jnp.concatenate([t, pad], axis=0)


def replay_lanes_mixed(ops: OpTensors, capacity: int,
                       **kw) -> LanesMixedResult:
    """One-shot convenience wrapper over ``make_replayer_lanes_mixed``."""
    return make_replayer_lanes_mixed(ops, capacity, **kw)()


# ---------------------------------------------------------------------------
# BLOCKED per-lane MIXED engine (ISSUE 2 tentpole): the full op surface
# on K-row blocks with per-lane logical tables (blkord/rws/liv/raw +
# incrementally-maintained inclusive prefixes).  Replaces the un-blocked
# kernel's per-step whole-plane cumsum (log2(CAP) rolls over [CAP, B])
# with an NB-row descent + ONE gathered K-row block splice; remote
# cursors descend the raw prefix table the same way.  Bit-identical to
# the un-blocked kernel: block splits move rows, never runs, so the
# logical run sequence, every YATA cursor, and every emitted origin are
# the same at every step.
# ---------------------------------------------------------------------------


def _mixed_lanes_blocked_kernel(
    kind_ref, pos_ref, dlen_ref, dtgt_ref, olop_ref, orop_ref, rk_ref,
    ilen_ref, start_ref,                        # [CHUNK, B] VMEM op columns
    w_ref,                                      # [CHUNK, B] rows_per_step
    ord0_ref, len0_ref, nlog0_ref,              # warm-start state inputs
    blk0_ref, rws0_ref, liv0_ref, raw0_ref,
    oll0_ref, orl0_ref,                         # prior table state
    ordblk0_ref, fwd0_ref,                      # prior hints + split fwd ptrs
    olld_ref, orld_ref,                         # this stream's prefill delta
    rkl_ref,                                    # ranks (read-only)
    ol_ref, or_ref,                             # [CHUNK, B] outputs
    ordp, lenp, nlogv, blkord, rws, liv, raw,   # state outputs (working)
    oll, orl,                                   # table outputs (working)
    ordblk,                                     # [OCAP, B] order->block HINT
    fwd,                                        # [NBT, B] block -> split dest
    err_ref,
    cumliv, cumraw,                             # [NBT, B] scratch prefixes
    *, K: int, NB: int, NBT: int, CAP: int, OCAP: int, CHUNK: int,
    WMAX: int = 1,
):
    from .lane_blocks import (
        gather_block,
        gather_head,
        lane_apply_partial,
        scatter_block,
        scatter_block2,
        vshift_up,
    )

    B = ordp.shape[1]
    i = pl.program_id(1)
    kdx = lax.broadcasted_iota(jnp.int32, (K, B), 0)
    tidx = lax.broadcasted_iota(jnp.int32, (NBT, B), 0)
    idx_cap = lax.broadcasted_iota(jnp.int32, (CAP, B), 0)
    oidx = lax.broadcasted_iota(jnp.int32, (OCAP, B), 0)
    root_i = jnp.int32(-1)

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when(i == 0)
    def _init():
        ordp[:] = ord0_ref[:]
        lenp[:] = len0_ref[:]
        nlogv[:] = jnp.maximum(nlog0_ref[:], 1)
        blkord[:] = blk0_ref[:]
        rws[:] = rws0_ref[:]
        liv[:] = liv0_ref[:]
        raw[:] = raw0_ref[:]
        cumliv[:] = _vcumsum(liv0_ref[:])
        cumraw[:] = _vcumsum(raw0_ref[:])
        oll[:] = jnp.where(olld_ref[:] != TAB_UNKNOWN, olld_ref[:],
                           oll0_ref[:])
        orl[:] = jnp.where(orld_ref[:] != TAB_UNKNOWN, orld_ref[:],
                           orl0_ref[:])
        # Order -> physical-block HINT (the per-lane `markers.rs:8` /
        # rle_mixed ``ordblk`` analog): written on insert, left stale by
        # block splits, verified + RUN-healed on every probe, and
        # CARRIED across chunks (a cold table would pay one plane-scan
        # fallback per first probe of every old order each chunk).
        # -1 = unknown.  ``fwd[b]`` = the block b's top half last moved
        # to (split forward pointer; -1 = never split) — the hop that
        # rescues stale hints without a plane scan.
        ordblk[:] = ordblk0_ref[:]
        fwd[:] = fwd0_ref[:]
        err_ref[:] = jnp.zeros_like(err_ref)

    # ---- per-lane by-order table ops (unchanged from un-blocked) --------

    def t_read(tab, o):
        oc = jnp.clip(o, 0, OCAP - 1)
        return jnp.sum(jnp.where(oidx == oc, tab[:], 0), axis=0,
                       keepdims=True)

    def t_write(tab, act, o, v):
        tab[:] = jnp.where(act & (oidx == o), v, tab[:])

    def t_write_run(tab, act, st, ln, v):
        tab[:] = jnp.where(act & (oidx >= st) & (oidx < st + ln), v,
                           tab[:])

    # ---- logical block tables -------------------------------------------

    def trow(tbl, l):
        return jnp.sum(jnp.where(tidx == l, tbl[:], 0), axis=0,
                       keepdims=True)

    def slot_of(cum, rank1, strict):
        """Smallest logical slot whose cumulative count reaches
        ``rank1`` (strict: cum < rank1; else cum <= rank1)."""
        nl = nlogv[:]
        hit = ((cum[:] < rank1) if strict else (cum[:] <= rank1)) \
            & (tidx < nl)
        return jnp.minimum(
            jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True), nl - 1)

    def live_before(l):
        return trow(cumliv, l) - trow(liv, l)

    def raw_before(l):
        return trow(cumraw, l) - trow(raw, l)

    def split(act, l):
        """Per-lane leaf split with live AND raw table upkeep."""
        over = act & (nlogv[:] >= NB)

        @pl.when(jnp.any(over))
        def _cap():
            err_ref[0:1, :] = jnp.where(over, 1, err_ref[0:1, :])

        do = act & (nlogv[:] < NB)

        @pl.when(jnp.any(do))
        def _do():
            b = trow(blkord, l)
            r = trow(rws, l)
            keep = r // 2
            mv = r - keep
            nbv = nlogv[:]
            ws_o = gather_block(ordp, b, K, NB)
            ws_l = gather_block(lenp, b, K, NB)
            hi = (kdx >= keep) & (kdx < r)
            liv_hi = jnp.sum(jnp.where(hi & (ws_o > 0), ws_l, 0),
                             axis=0, keepdims=True)
            raw_hi = jnp.sum(jnp.where(hi, ws_l, 0), axis=0,
                             keepdims=True)
            up_o = vshift_up(ws_o, keep, K)
            up_l = vshift_up(ws_l, keep, K)
            scatter_block2(
                ordp, b, jnp.where(kdx < keep, ws_o, 0),
                nbv, jnp.where(kdx < mv, up_o, 0), do, K, NB)
            scatter_block2(
                lenp, b, jnp.where(kdx < keep, ws_l, 0),
                nbv, jnp.where(kdx < mv, up_l, 0), do, K, NB)
            for tbl in (blkord, rws, liv, raw, cumliv, cumraw):
                sh = pltpu.roll(tbl[:], 1, axis=0)
                tbl[:] = jnp.where(do & (tidx > l), sh, tbl[:])
            w_l = do & (tidx == l)
            w_l1 = do & (tidx == l + 1)
            rws[:] = jnp.where(w_l, keep, jnp.where(w_l1, mv, rws[:]))
            liv[:] = jnp.where(w_l, liv[:] - liv_hi,
                               jnp.where(w_l1, liv_hi, liv[:]))
            raw[:] = jnp.where(w_l, raw[:] - raw_hi,
                               jnp.where(w_l1, raw_hi, raw[:]))
            cumliv[:] = jnp.where(w_l, cumliv[:] - liv_hi, cumliv[:])
            cumraw[:] = jnp.where(w_l, cumraw[:] - raw_hi, cumraw[:])
            blkord[:] = jnp.where(w_l1, nbv, blkord[:])
            fwd[:] = jnp.where(do & (tidx == b), nbv, fwd[:])
            nlogv[:] = nlogv[:] + do.astype(jnp.int32)

    # ---- order -> run / position lookups --------------------------------

    def _verify_block(b_raw, o):
        """(found, block, in-block row) of order ``o`` within candidate
        block id ``b_raw`` (one K-row range test; out-of-range ids never
        match)."""
        ok = (b_raw >= 0) & (b_raw < NB)
        bc = jnp.where(ok, b_raw, 0)
        ws_o = gather_block(ordp, bc, K, NB)
        ws_l = gather_block(lenp, bc, K, NB)
        so = jnp.abs(ws_o) - 1
        hit = (ws_o != 0) & (so <= o) & (o < so + ws_l)
        f = ok & (jnp.sum(hit.astype(jnp.int32), axis=0,
                          keepdims=True) > 0)
        rowk = jnp.min(jnp.where(hit, kdx, K - 1), axis=0,
                       keepdims=True)
        return f, bc, rowk

    def locate_order(o, want, flag):
        """Per-lane (physical block, in-block row, found) of the run
        containing order ``o`` for ``want`` lanes: read the hint, VERIFY
        by one K-row range test; stale lanes chase the split FORWARD
        POINTERS (a moved run lives in the block its old block's top
        half LAST went to — fwd[b] keeps only the most recent split
        destination, so the two K-row hops cover the common one- and
        two-generation moves; older generations just fall back);
        only then fall back to one vectorized whole-plane scan (under
        ``lax.cond`` so hint-hit steps never pay it).  Hop/fallback
        hits heal the found run's whole hint span.  ``flag`` lanes (may
        be None) raise the order-miss flag when not found."""
        oc = jnp.clip(o, 0, OCAP - 1)
        bh = t_read(ordblk, oc)
        hfound, bhc, rowk_h = _verify_block(bh, o)

        miss1 = want & ~hfound

        def hops():
            b2 = jnp.sum(jnp.where(tidx == bhc, fwd[:], 0), axis=0,
                         keepdims=True)
            f2, b2c, r2 = _verify_block(jnp.where(hfound, -1, b2), o)
            b3 = jnp.sum(jnp.where(tidx == b2c, fwd[:], 0), axis=0,
                         keepdims=True)
            f3, b3c, r3 = _verify_block(jnp.where(f2, -1, b3), o)
            return (f2.astype(jnp.int32), b2c, r2,
                    f3.astype(jnp.int32), b3c, r3)

        z = jnp.zeros_like(bhc)
        f2i, b2, r2, f3i, b3, r3 = lax.cond(
            jnp.any(miss1), hops, lambda: (z, z, z, z, z, z))
        hop2 = miss1 & (f2i != 0)
        hop3 = miss1 & ~hop2 & (f3i != 0)
        miss2 = miss1 & ~hop2 & ~hop3

        def fallback():
            bo = ordp[:]
            sog = jnp.abs(bo) - 1
            ghit = (bo != 0) & (sog <= o) & (o < sog + lenp[:])
            gfound = jnp.sum(ghit.astype(jnp.int32), axis=0,
                             keepdims=True) > 0
            grow = jnp.min(jnp.where(ghit, idx_cap, CAP - 1), axis=0,
                           keepdims=True)
            return (gfound.astype(jnp.int32), grow)

        gfound_i, grow = lax.cond(
            jnp.any(miss2), fallback, lambda: (z, z))
        gfound = miss2 & (gfound_i != 0)
        found = hfound | hop2 | hop3 | gfound
        nb = jnp.where(hfound, bhc,
                       jnp.where(hop2, b2,
                                 jnp.where(hop3, b3, grow // K)))
        rowk = jnp.where(hfound, rowk_h,
                         jnp.where(hop2, r2,
                                   jnp.where(hop3, r3, grow % K)))

        heal = want & ~hfound & found

        @pl.when(jnp.any(heal))
        def _heal():
            # Heal the WHOLE found run's hint span (same one-pass cost
            # as a single entry): a stale run moved wholesale in a
            # block split, so later probes of its other chars would
            # miss too.
            gr = nb * K + rowk
            h_o = _vrow(ordp[:], gr)
            h_l = _vrow(lenp[:], gr)
            h_so = jnp.abs(h_o) - 1
            ordblk[:] = jnp.where(
                heal & (oidx >= h_so) & (oidx < h_so + h_l), nb,
                ordblk[:])

        if flag is not None:
            @pl.when(jnp.any(flag & ~found))
            def _missing():
                err_ref[2:3, :] = jnp.where(flag & ~found, 1,
                                            err_ref[2:3, :])

        return nb, rowk, found

    def slot_of_block(nb):
        """Logical slot holding physical block ``nb`` (NBT-row scan)."""
        lhit = (blkord[:] == nb) & (tidx < nlogv[:])
        return jnp.max(jnp.where(lhit, tidx, 0), axis=0, keepdims=True)

    def locate_order_pure(o):
        """Heal-free, flag-free locate for ``lax.cond`` branches (no
        ref writes; the order is known present)."""
        oc = jnp.clip(o, 0, OCAP - 1)
        bh = t_read(ordblk, oc)
        bh_ok = (bh >= 0) & (bh < NB)
        bhc = jnp.where(bh_ok, bh, 0)
        ws_o = gather_block(ordp, bhc, K, NB)
        ws_l = gather_block(lenp, bhc, K, NB)
        so = jnp.abs(ws_o) - 1
        hit = (ws_o != 0) & (so <= o) & (o < so + ws_l)
        hfound = bh_ok & (jnp.sum(hit.astype(jnp.int32), axis=0,
                                  keepdims=True) > 0)
        rowk_h = jnp.min(jnp.where(hit, kdx, K - 1), axis=0,
                         keepdims=True)
        bo = ordp[:]
        sog = jnp.abs(bo) - 1
        ghit = (bo != 0) & (sog <= o) & (o < sog + lenp[:])
        grow = jnp.min(jnp.where(ghit, idx_cap, CAP - 1), axis=0,
                       keepdims=True)
        return (jnp.where(hfound, bhc, grow // K),
                jnp.where(hfound, rowk_h, grow % K))

    def raw_pos_of_order(o, need):
        """RAW document position of order ``o``: hint-guided block
        locate + slot prefix (tables) + in-block prefix (K rows)."""
        nb, rowk, _ = locate_order(o, need, need)
        l = slot_of_block(nb)
        ws_o = gather_block(ordp, nb, K, NB)
        ws_l = gather_block(lenp, nb, K, NB)
        inblk = jnp.sum(jnp.where(kdx < rowk, ws_l, 0), axis=0,
                        keepdims=True)
        so_hit = jnp.abs(_vrow(ws_o, rowk)) - 1
        return raw_before(l) + inblk + (o - so_hit)

    def cursor_after(o, need):
        is_root = o == root_i
        unknown = need & (o == TAB_UNKNOWN)

        @pl.when(jnp.any(unknown))
        def _unk():
            err_ref[2:3, :] = jnp.where(unknown, 1, err_ref[2:3, :])

        p = raw_pos_of_order(jnp.maximum(o, 0), need & ~is_root)
        return jnp.where(is_root, 0, p + 1)

    def total_raw():
        return trow(cumraw, nlogv[:] - 1)

    # ---- local ops ------------------------------------------------------

    def do_local_delete(act, p, d):
        """Blocked per-lane live-rank tombstone (raw counts unchanged:
        tombstoning never moves raw positions)."""

        def body(carry):
            rem, iters = carry
            a = act & (rem > 0)
            l = slot_of(cumliv, p + 1, strict=True)
            need = a & (trow(rws, l) + 2 > K)

            @pl.when(jnp.any(need))
            def _():
                split(need, l)

            l = lax.cond(
                jnp.any(need),
                lambda: slot_of(cumliv, p + 1, strict=True), lambda: l)
            b = trow(blkord, l)
            base = live_before(l)
            ws_o = gather_block(ordp, b, K, NB)
            ws_l = gather_block(lenp, b, K, NB)
            lv = jnp.where(ws_o > 0, ws_l, 0)
            cum = _vcumsum(lv)
            before = base + cum - lv
            remm = jnp.where(a, rem, 0)
            cs = jnp.clip(p - before, 0, lv)
            ce = jnp.clip(p + remm - before, 0, lv)
            cov = ce - cs
            tot = jnp.sum(cov, axis=0, keepdims=True)
            full = (cov > 0) & (cov == ws_l)
            part = (cov > 0) & jnp.logical_not(full)
            npart = jnp.sum(part.astype(jnp.int32), axis=0,
                            keepdims=True)
            i1 = jnp.min(jnp.where(part, kdx, K), axis=0, keepdims=True)
            i2 = jnp.max(jnp.where(part, kdx, -1), axis=0, keepdims=True)
            ws_o = jnp.where(a & full, -ws_o, ws_o)
            ws_o, ws_l, a2 = lane_apply_partial(
                a & (npart >= 1), i2, ws_o, ws_l, cs, ce, kdx)
            ws_o, ws_l, a1 = lane_apply_partial(
                a & (npart == 2), i1, ws_o, ws_l, cs, ce, kdx)
            scatter_block(ordp, b, ws_o, a, K, NB)
            scatter_block(lenp, b, ws_l, a, K, NB)
            w_l = a & (tidx == l)
            rws[:] = jnp.where(w_l, rws[:] + a1 + a2, rws[:])
            liv[:] = jnp.where(w_l, liv[:] - tot, liv[:])
            # raw counts unchanged: tombstoning moves no raw positions.
            cumliv[:] = jnp.where(a & (tidx >= l), cumliv[:] - tot,
                                  cumliv[:])
            return rem - jnp.where(a, tot, 0), iters + 1

        rem, _ = lax.while_loop(
            lambda c: jnp.any(act & (c[0] > 0)) & (c[1] <= 2 * NBT),
            body, (jnp.where(act, d, 0), 0))

        @pl.when(jnp.any(act & (rem > 0)))
        def _bad():
            err_ref[1:2, :] = jnp.where(act & (rem > 0), 1,
                                        err_ref[1:2, :])

    fused_table_writes = partial(_fused_table_writes, oll, orl, oidx)

    def do_local_insert(act, k, p, il, st, w):
        """Blocked per-lane live-rank insert + by-order table upkeep.
        ``w`` > 1 is a FUSED backwards-burst step: W stride-L rows in
        one shift (the ``ops.rle`` ``_insert_splice`` contract; WMAX
        <= K//2 - 1 so the one leaf split always makes room)."""
        l = jnp.where(p == 0, 0, slot_of(cumliv, p, strict=True))
        need = act & (trow(rws, l) + w + 1 > K)

        @pl.when(jnp.any(need))
        def _():
            split(need, l)

        l = lax.cond(
            jnp.any(need),
            lambda: jnp.where(p == 0, 0,
                              slot_of(cumliv, p, strict=True)),
            lambda: l)
        r0 = trow(rws, l)
        b = trow(blkord, l)
        local = jnp.where(act, p - live_before(l), 0)
        ws_o = gather_block(ordp, b, K, NB)
        ws_l = gather_block(lenp, b, K, NB)
        lv = jnp.where(ws_o > 0, ws_l, 0)
        cum = _vcumsum(lv)
        i_r = jnp.sum(((cum < local) & (kdx < r0)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(ws_o, i_r)
        l_r = _vrow(ws_l, i_r)
        off = local - (_vrow(cum, i_r) - _vrow(lv, i_r))

        left = jnp.where(p == 0, root_i, (o_r - 1) + (off - 1))
        no, nl, amt, mrg, is_split, lrun = fused_splice_rows(
            ws_o, ws_l, kdx, p, i_r, o_r, l_r, off, il, st, w, WMAX,
            _vshift, active=act)

        nxt_in_blk = _vrow(ws_o, i_r + 1)
        b2 = trow(blkord, jnp.minimum(l + 1, NBT - 1))
        nxt_slot_o = gather_head(ordp, b2, K, NB)
        first_o = gather_head(ordp, trow(blkord, 0), K, NB)
        succ_p0 = jnp.where(trow(rws, 0) > 0, first_o, 0)
        succ_after = jnp.where(i_r + 1 < r0, nxt_in_blk,
                               jnp.where(l + 1 < nlogv[:], nxt_slot_o, 0))
        succ = jnp.where(p == 0, succ_p0,
                         jnp.where(is_split, o_r + off, succ_after))
        right = jnp.where(succ == 0, root_i, jnp.abs(succ) - 1)
        scatter_block(ordp, b, no, act, K, NB)
        scatter_block(lenp, b, nl, act, K, NB)
        w_l = act & (tidx == l)
        rws[:] = jnp.where(w_l, rws[:] + amt, rws[:])
        liv[:] = jnp.where(w_l, liv[:] + il, liv[:])
        raw[:] = jnp.where(w_l, raw[:] + il, raw[:])
        cumliv[:] = jnp.where(act & (tidx >= l), cumliv[:] + il,
                              cumliv[:])
        cumraw[:] = jnp.where(act & (tidx >= l), cumraw[:] + il,
                              cumraw[:])

        fused_table_writes(act, st, il, lrun, left, right)
        t_write_run(ordblk, act, st, il, b)
        ol_ref[pl.ds(k, 1), :] = jnp.where(
            act, left.astype(jnp.uint32), ol_ref[pl.ds(k, 1), :])
        or_ref[pl.ds(k, 1), :] = jnp.where(
            act, right.astype(jnp.uint32), or_ref[pl.ds(k, 1), :])

    # ---- remote insert (`doc.rs:274-293` -> integrate) ------------------

    def run_at_raw(c):
        """Per-lane (signed start, len, 0-based offset) of the run
        holding RAW position ``c``: slot descent + one block gather."""
        ls = slot_of(cumraw, c, strict=False)
        b = trow(blkord, ls)
        r0 = trow(rws, ls)
        local = c - raw_before(ls)
        ws_o = gather_block(ordp, b, K, NB)
        ws_l = gather_block(lenp, b, K, NB)
        cumb = _vcumsum(ws_l)
        i_r = jnp.sum(((cumb <= local) & (kdx < r0)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(ws_o, i_r)
        l_r = _vrow(ws_l, i_r)
        off = local - (_vrow(cumb, i_r) - l_r)
        return o_r, l_r, off

    def integrate_cursor(act, my_rank, o_left, o_right):
        """Per-lane YATA conflict scan — predicates identical to the
        un-blocked kernel (bit-identical cursors); only the probe's
        location machinery changed (table descent + block gather instead
        of a hoisted whole-plane cumsum)."""
        n = total_raw()
        cursor0 = cursor_after(o_left, act)
        left_cursor = cursor0

        def cond(state):
            cursor, scanning_i, scan_start, done_i = state
            return jnp.any((done_i == 0) & (cursor < n))

        def body(state):
            cursor, scanning_i, scan_start, done_i = state
            done = done_i != 0
            o_r, l_r, off = run_at_raw(cursor)
            so = jnp.abs(o_r) - 1
            other_order = so + off
            live = ~done & (cursor < n)
            other_left = t_read(oll, other_order)
            other_right = t_read(orl, other_order)
            other_rank = t_read(rkl_ref, other_order)
            olc = cursor_after(other_left, live)
            brk = (other_order == o_right) | (olc < left_cursor)
            eq = ~brk & (olc == left_cursor)
            gt = my_rank > other_rank
            brk = brk | (eq & ~gt & (o_right == other_right))
            starts_scan = eq & ~gt & (o_right != other_right)
            scanning = scanning_i != 0
            new_scan_start = jnp.where(
                live & starts_scan & ~scanning, cursor, scan_start)
            new_scanning_i = jnp.where(
                live & eq,
                jnp.where(gt, 0,
                          jnp.where(o_right == other_right, scanning_i,
                                    1)),
                scanning_i)
            contains_right = (o_right > other_order) & (o_right < so + l_r)
            step = jnp.where(contains_right, o_right - other_order,
                             l_r - off)
            new_cursor = jnp.where(live & ~brk, cursor + step, cursor)
            new_done_i = jnp.maximum(
                done_i, jnp.where(brk | (cursor >= n), 1, 0))
            return (new_cursor, new_scanning_i, new_scan_start,
                    new_done_i)

        zero = jnp.zeros_like(cursor0)
        init = (cursor0, zero, cursor0, (~act).astype(jnp.int32))
        cursor, scanning_i, scan_start, _ = lax.while_loop(
            cond, body, init)
        return jnp.where(scanning_i != 0, scan_start, cursor)

    def do_remote_insert(act, k, my_rank, o_left, o_right, il, st):
        c = integrate_cursor(act, my_rank, o_left, o_right)
        l = jnp.where(c == 0, 0, slot_of(cumraw, c, strict=True))
        need = act & (trow(rws, l) + 2 > K)

        @pl.when(jnp.any(need))
        def _():
            split(need, l)

        l = lax.cond(
            jnp.any(need),
            lambda: jnp.where(c == 0, 0,
                              slot_of(cumraw, c, strict=True)),
            lambda: l)
        r0 = trow(rws, l)
        b = trow(blkord, l)
        local = jnp.where(act, c - raw_before(l), 0)
        ws_o = gather_block(ordp, b, K, NB)
        ws_l = gather_block(lenp, b, K, NB)
        cumb = _vcumsum(ws_l)
        i_r = jnp.sum(((cumb < local) & (kdx < r0)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(ws_o, i_r)
        l_r = _vrow(ws_l, i_r)
        off = local - (_vrow(cumb, i_r) - l_r)

        # Raw splice: the split run may be a TOMBSTONE (sign-preserving
        # tail); merge additionally requires a live predecessor AND the
        # op's origin_left chaining to the run's last char (the YATA
        # run-skip premise — see the un-blocked kernel).
        mrg = act & (c > 0) & (o_r > 0) & (off == l_r) & \
            ((st + 1) == (o_r + l_r)) & (o_left == o_r + l_r - 2)
        is_split = act & (c > 0) & (off < l_r)
        ins_at = jnp.where(c == 0, 0, i_r + 1)
        amt = jnp.where(jnp.logical_not(act) | mrg, 0,
                        jnp.where(is_split, 2, 1))
        so = _vshift(ws_o, amt)
        sl = _vshift(ws_l, amt)
        no = jnp.where(kdx < ins_at, ws_o, so)
        nl = jnp.where(kdx < ins_at, ws_l, sl)
        nl = jnp.where(is_split & (kdx == i_r), off, nl)
        new_run = act & jnp.logical_not(mrg) & (kdx == ins_at)
        no = jnp.where(new_run, st + 1, no)
        nl = jnp.where(new_run, il, nl)
        tail = is_split & (kdx == ins_at + 1)
        tail_o = jnp.where(o_r > 0, o_r + off, o_r - off)
        no = jnp.where(tail, tail_o, no)
        nl = jnp.where(tail, l_r - off, nl)
        nl = jnp.where(mrg & (kdx == i_r), l_r + il, nl)
        scatter_block(ordp, b, no, act, K, NB)
        scatter_block(lenp, b, nl, act, K, NB)
        w_l = act & (tidx == l)
        rws[:] = jnp.where(w_l, rws[:] + amt, rws[:])
        liv[:] = jnp.where(w_l, liv[:] + il, liv[:])
        raw[:] = jnp.where(w_l, raw[:] + il, raw[:])
        cumliv[:] = jnp.where(act & (tidx >= l), cumliv[:] + il,
                              cumliv[:])
        cumraw[:] = jnp.where(act & (tidx >= l), cumraw[:] + il,
                              cumraw[:])

        t_write_run(ordblk, act, st, il, b)
        ol_ref[pl.ds(k, 1), :] = jnp.where(
            act, o_left.astype(jnp.uint32), ol_ref[pl.ds(k, 1), :])
        or_ref[pl.ds(k, 1), :] = jnp.where(
            act, o_right.astype(jnp.uint32), or_ref[pl.ds(k, 1), :])

    # ---- remote delete (`doc.rs:295-340`, covered-run walk) -------------

    def do_remote_delete(act, t, dlen):
        """Order-interval tombstone as a HINT-GUIDED covered-run walk:
        the covered orders ``[t, t+dlen)`` are one contiguous order
        interval, so walking ``o_cur`` run-by-run (hinted locate, flip
        full covers, 3-way-split the <= 2 partial endpoint runs, count
        covered DEAD runs toward the idempotency total without
        flipping, `double_delete.rs:6-9`) touches O(K + NBT) rows per
        covered run instead of the un-blocked engine's whole-plane
        interval clip.  Iterations = covered runs (tiny for the
        config-5r <= 4-char deletes); every iteration makes >= 1 char
        of progress, so the static bound only guards corrupt streams.
        A lane whose target orders are absent flags BAD-DELETE (the
        un-blocked covered-total semantics) and stops cleanly; a lane
        whose endpoint split cannot be housed flags capacity."""
        end = t + jnp.where(act, dlen, 0)

        def body(carry):
            o_cur, rem, iters = carry
            a = act & (rem > 0)
            nb, rowk, found = locate_order(o_cur, a, None)
            miss = a & ~found

            @pl.when(jnp.any(miss))
            def _bad():
                err_ref[1:2, :] = jnp.where(miss, 1, err_ref[1:2, :])

            a = a & found
            ws_o = gather_block(ordp, nb, K, NB)
            ws_l = gather_block(lenp, nb, K, NB)
            o_r = _vrow(ws_o, rowk)
            l_r = _vrow(ws_l, rowk)
            so = jnp.abs(o_r) - 1
            aa = o_cur - so
            ee = jnp.minimum(l_r, end - so)
            live = o_r > 0
            ispartial = live & ((aa > 0) | (ee < l_r))
            l = slot_of_block(nb)
            need = a & ispartial & (trow(rws, l) + 2 > K)

            @pl.when(jnp.any(need))
            def _():
                split(need, l)

            # Re-locate only when a split moved rows, and drop lanes
            # whose split could not be housed (flagged by split();
            # their delete stops cleanly mid-walk).
            nb, rowk = lax.cond(jnp.any(need),
                                lambda: locate_order_pure(o_cur),
                                lambda: (nb, rowk))
            l = slot_of_block(nb)
            housed = jnp.logical_not(ispartial) | (trow(rws, l) + 2 <= K)
            a = a & housed
            ws_o = gather_block(ordp, nb, K, NB)
            ws_l = gather_block(lenp, nb, K, NB)
            o_r = _vrow(ws_o, rowk)
            l_r = _vrow(ws_l, rowk)
            so = jnp.abs(o_r) - 1
            aa = o_cur - so
            ee = jnp.minimum(l_r, end - so)
            cov = ee - aa
            live = o_r > 0
            ispartial = live & ((aa > 0) | (ee < l_r))

            # Full live cover: flip the one row.
            flip = a & live & jnp.logical_not(ispartial)
            ws_o2 = jnp.where(flip & (kdx == rowk), -ws_o, ws_o)
            # Partial live cover: [head?] [tombstone mid] [tail?].
            part = a & ispartial
            has_head = part & (aa > 0)
            has_tail = part & (ee < l_r)
            amt = has_head.astype(jnp.int32) + has_tail.astype(jnp.int32)
            sh_o = _vshift(ws_o2, amt)
            sh_l = _vshift(ws_l, amt)
            no = jnp.where(kdx <= rowk, ws_o2, sh_o)
            nl = jnp.where(kdx <= rowk, ws_l, sh_l)
            p0o = jnp.where(has_head, o_r, -(so + aa + 1))
            p0l = jnp.where(has_head, aa, cov)
            p1o = jnp.where(has_head, -(so + aa + 1), so + ee + 1)
            p1l = jnp.where(has_head, cov, l_r - ee)
            w0 = part & (kdx == rowk)
            no = jnp.where(w0, p0o, no)
            nl = jnp.where(w0, p0l, nl)
            w1 = part & (kdx == rowk + 1) & (amt >= 1)
            no = jnp.where(w1, p1o, no)
            nl = jnp.where(w1, p1l, nl)
            w2 = part & (kdx == rowk + 2) & (amt == 2)
            no = jnp.where(w2, so + ee + 1, no)
            nl = jnp.where(w2, l_r - ee, nl)
            touch = flip | part
            scatter_block(ordp, nb, no, touch, K, NB)
            scatter_block(lenp, nb, nl, touch, K, NB)
            dec = jnp.where(a & live, cov, 0)
            w_l = a & (tidx == l)
            rws[:] = jnp.where(w_l & part, rws[:] + amt, rws[:])
            liv[:] = jnp.where(w_l, liv[:] - dec, liv[:])
            cumliv[:] = jnp.where(a & (tidx >= l), cumliv[:] - dec,
                                  cumliv[:])
            # The new-run hint rows of split pieces stay within block
            # ``nb`` (splits into OTHER blocks already healed above).
            new_rem = jnp.where(miss | jnp.logical_not(housed), 0,
                                rem - jnp.where(a, cov, 0))
            return so + ee, new_rem, iters + 1

        # Every iteration covers >= 1 char, so covered runs bound the
        # trip count; CAP + NBT guards corrupt streams.
        _, rem, _ = lax.while_loop(
            lambda c: jnp.any(c[1] > 0) & (c[2] <= CAP + NBT),
            body, (jnp.where(act, t, 0), jnp.where(act, dlen, 0), 0))

        @pl.when(jnp.any(rem > 0))
        def _leftover():
            err_ref[1:2, :] = jnp.where(rem > 0, 1, err_ref[1:2, :])

    # ---- dispatch -------------------------------------------------------

    def op_body(k, _):
        kind = kind_ref[pl.ds(k, 1), :]
        p = pos_ref[pl.ds(k, 1), :]
        d = dlen_ref[pl.ds(k, 1), :]
        il = ilen_ref[pl.ds(k, 1), :]
        st = start_ref[pl.ds(k, 1), :]
        w = jnp.maximum(w_ref[pl.ds(k, 1), :], 1)  # pad rows carry 0

        act_ld = (kind == KIND_LOCAL) & (d > 0)
        act_li = (kind == KIND_LOCAL) & (il > 0)
        act_ri = (kind == KIND_REMOTE_INS) & (il > 0)
        act_rd = (kind == KIND_REMOTE_DEL) & (d > 0)

        @pl.when(jnp.any(act_ld))
        def _():
            do_local_delete(act_ld, p, d)

        @pl.when(jnp.any(act_li))
        def _():
            do_local_insert(act_li, k, p, il, st, w)

        @pl.when(jnp.any(act_ri))
        def _():
            do_remote_insert(act_ri, k, rk_ref[pl.ds(k, 1), :],
                             olop_ref[pl.ds(k, 1), :],
                             orop_ref[pl.ds(k, 1), :], il, st)

        @pl.when(jnp.any(act_rd))
        def _():
            do_remote_delete(act_rd, dtgt_ref[pl.ds(k, 1), :], d)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)


@dataclasses.dataclass
class BlockedLanesMixedResult:
    """Blocked per-lane mixed outputs: block state + by-order tables."""

    ordp: jax.Array     # i32[CAP, B]
    lenp: jax.Array     # i32[CAP, B]
    nlog: jax.Array     # i32[1, B]
    blkord: jax.Array   # i32[NBT, B]
    rws: jax.Array      # i32[NBT, B]
    liv: jax.Array      # i32[NBT, B]
    raw: jax.Array      # i32[NBT, B]
    oll: jax.Array      # i32[OCAP, B]
    orl: jax.Array      # i32[OCAP, B]
    ordblk: jax.Array   # i32[OCAP, B] order->block hint (may be stale)
    fwd: jax.Array      # i32[NBT, B] split forward pointers
    ol: jax.Array       # u32[S, B]
    orr: jax.Array      # u32[S, B]
    err: jax.Array      # i32[8, B] 0: blocks; 1: bad delete; 2: order miss
    batch: int
    block_k: int

    def check(self) -> None:
        err = np.asarray(self.err)
        if err[0].max() != 0:
            raise RuntimeError(
                f"blocked rle_lanes_mixed out of blocks on lanes "
                f"{np.nonzero(err[0])[0][:8].tolist()}; raise capacity")
        if err[1].max() != 0:
            raise RuntimeError(
                f"delete ran past the end of the document on lanes "
                f"{np.nonzero(err[1])[0][:8].tolist()}")
        if err[2].max() != 0:
            raise RuntimeError(
                f"order lookup missed on lanes "
                f"{np.nonzero(err[2])[0][:8].tolist()}: an op referenced "
                f"an order absent from device state")

    def state(self):
        """The next chunk's ``init`` 11-tuple (the hint + forward
        tables ride along so warm-start chunks keep their locality)."""
        return (self.ordp, self.lenp, self.nlog, self.blkord, self.rws,
                self.liv, self.raw, self.oll, self.orl, self.ordblk,
                self.fwd)

    @property
    def rows(self):
        return jnp.sum(self.rws, axis=0, keepdims=True)


@functools.lru_cache(maxsize=32)
def _build_blocked_call(s_pad: int, B: int, capacity: int, block_k: int,
                        ocap: int, chunk: int, interpret: bool,
                        lane_tile: int | None = None, wmax: int = 1):
    """Shape-keyed cache for the blocked mixed kernel."""
    K = block_k
    NB = capacity // K
    NBT = max(8, NB)
    T = lane_tile or _lane_tile(B)
    _require(B % T == 0, f"lane_tile {T} must divide batch {B}")
    col = lambda: pl.BlockSpec((chunk, T), lambda lb, i: (i, lb),
                               memory_space=pltpu.VMEM)
    whole = lambda rows: pl.BlockSpec(
        (rows, T), lambda lb, i: (0, lb), memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        partial(_mixed_lanes_blocked_kernel, K=K, NB=NB, NBT=NBT,
                CAP=capacity, OCAP=ocap, CHUNK=chunk, WMAX=wmax),
        grid=(B // T, s_pad // chunk),
        in_specs=[col() for _ in range(10)] + [
            whole(capacity), whole(capacity), whole(1),
            whole(NBT), whole(NBT), whole(NBT), whole(NBT),
            whole(ocap), whole(ocap), whole(ocap),  # prior table state
            whole(NBT),                         # prior fwd pointers
            whole(ocap), whole(ocap),           # prefill delta
            whole(ocap),                        # ranks (read-only)
        ],
        out_specs=[
            col(), col(),
            whole(capacity), whole(capacity), whole(1),
            whole(NBT), whole(NBT), whole(NBT), whole(NBT),
            whole(ocap), whole(ocap), whole(ocap), whole(NBT),
            whole(8),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, B), jnp.uint32),
            jax.ShapeDtypeStruct((s_pad, B), jnp.uint32),
            jax.ShapeDtypeStruct((capacity, B), jnp.int32),
            jax.ShapeDtypeStruct((capacity, B), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.int32),
            jax.ShapeDtypeStruct((NBT, B), jnp.int32),
            jax.ShapeDtypeStruct((NBT, B), jnp.int32),
            jax.ShapeDtypeStruct((NBT, B), jnp.int32),
            jax.ShapeDtypeStruct((NBT, B), jnp.int32),
            jax.ShapeDtypeStruct((ocap, B), jnp.int32),
            jax.ShapeDtypeStruct((ocap, B), jnp.int32),
            jax.ShapeDtypeStruct((ocap, B), jnp.int32),
            jax.ShapeDtypeStruct((NBT, B), jnp.int32),
            jax.ShapeDtypeStruct((8, B), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((NBT, T), jnp.int32),    # cumliv
            pltpu.VMEM((NBT, T), jnp.int32),    # cumraw
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=128 * 1024 * 1024,
        ),
        interpret=interpret,
    )
    return jax.jit(lambda *a: call(*a))


def make_replayer_lanes_mixed_blocked(
    ops: OpTensors,
    capacity: int,
    block_k: int = 64,
    order_capacity: int = 0,
    chunk: int = 128,
    init=None,
    rkl=None,
    interpret: bool = False,
    lane_tile: int | None = None,
):
    """Build a jitted BLOCKED per-lane MIXED replayer — bit-identical
    final state, YATA cursors, and per-op origins to
    ``make_replayer_lanes_mixed`` at O(NB + K) touched rows per step.

    Same contract as the un-blocked builder; ``capacity`` must be a
    ``block_k`` multiple, ``init`` a prior blocked ``state()`` 11-tuple.
    """
    kinds = np.asarray(ops.kind)
    _require(kinds.ndim == 2, "rle_lanes_mixed takes stacked per-doc "
             "streams ([S, B] columns; see batch.stack_ops)")
    S, B = kinds.shape
    _require(block_k >= 8, "block_k must hold a few runs")
    _require(capacity % block_k == 0,
             f"capacity ({capacity}) must be a multiple of block_k "
             f"({block_k})")
    wmax = fused_width_checked([ops], block_k)
    s_pad = max(((S + chunk - 1) // chunk) * chunk, chunk)

    adv = np.asarray(ops.order_advance, dtype=np.int64).sum(axis=0)
    base = 0
    if init is not None and init[7] is not None:
        base = init[7].shape[0]
    ocap = order_capacity or max(
        ((int(adv.max() + ops.lmax) + base + 7) // 8) * 8, 8)
    _require(ocap % 8 == 0, "order_capacity must be a multiple of 8")

    def staged_col(get):
        a = np.asarray(get(ops), dtype=np.uint32).view(np.int32)
        return jnp.asarray(np.pad(a, ((0, s_pad - S), (0, 0))))

    staged = tuple(staged_col(g) for g in (
        lambda o: o.kind, lambda o: o.pos, lambda o: o.del_len,
        lambda o: o.del_target, lambda o: o.origin_left,
        lambda o: o.origin_right, lambda o: o.rank, lambda o: o.ins_len,
        lambda o: o.ins_order_start, lambda o: o.rows_per_step))

    olld, orld, rkl0 = lane_tables(ops, ocap)
    if rkl is None:
        rkl = rkl0
    else:
        rkl = np.asarray(rkl, np.int32)
        _require(rkl.shape == (ocap, B),
                 f"rkl shape {rkl.shape} != ({ocap}, {B})")

    NBT = max(8, capacity // block_k)
    if init is None:
        init = _empty_mixed_blocked_state(capacity, NBT, ocap, B)
    else:
        init = _grow_mixed_blocked_state(init, capacity, block_k, ocap, B)
    jitted = _build_blocked_call(s_pad, B, capacity, block_k, ocap,
                                 chunk, interpret, lane_tile, wmax)
    deltas = (jnp.asarray(olld), jnp.asarray(orld), jnp.asarray(rkl))

    def run(state=None) -> BlockedLanesMixedResult:
        ini = init if state is None else _grow_mixed_blocked_state(
            state, capacity, block_k, ocap, B)
        (ol, orr, ordp, lenp, nlog, blk, rws, liv, raw, oll, orl,
         ordblk, fwd, err) = jitted(*staged, *ini, *deltas)
        return BlockedLanesMixedResult(
            ordp=ordp, lenp=lenp, nlog=nlog, blkord=blk, rws=rws,
            liv=liv, raw=raw, oll=oll, orl=orl, ordblk=ordblk, fwd=fwd,
            ol=ol[:S], orr=orr[:S], err=err, batch=B, block_k=block_k)

    return run


def _empty_mixed_blocked_state(capacity: int, NBT: int, ocap: int,
                               B: int):
    z = lambda r: jnp.zeros((r, B), jnp.int32)
    unk = lambda r: jnp.full((r, B), -1, jnp.int32)
    tab = lambda r: jnp.full((r, B), TAB_UNKNOWN, jnp.int32)
    return (z(capacity), z(capacity), z(1), z(NBT), z(NBT), z(NBT),
            z(NBT), tab(ocap), tab(ocap), unk(ocap),
            jnp.full((NBT, B), -1, jnp.int32))


def _grow_mixed_blocked_state(state, capacity: int, block_k: int,
                              ocap: int, B: int):
    """Pad a prior chunk's blocked mixed 11-tuple up to this chunk's
    row/order capacities (fixed K; NB and OCAP only grow)."""
    from .rle_lanes import _grow_blocked_state

    o0, l0, nlog, blk, rws, liv = _grow_blocked_state(
        state[:6], capacity, block_k, B)
    NBT = max(8, capacity // block_k)
    rawt = jnp.asarray(state[6], jnp.int32)
    if rawt.shape[0] < NBT:
        rawt = jnp.concatenate(
            [rawt, jnp.zeros((NBT - rawt.shape[0], B), jnp.int32)],
            axis=0)
    hint = jnp.asarray(state[9], jnp.int32)
    if hint.shape[0] < ocap:
        hint = jnp.concatenate(
            [hint, jnp.full((ocap - hint.shape[0], B), -1, jnp.int32)],
            axis=0)
    fwdt = jnp.asarray(state[10], jnp.int32)
    if fwdt.shape[0] < NBT:
        fwdt = jnp.concatenate(
            [fwdt, jnp.full((NBT - fwdt.shape[0], B), -1, jnp.int32)],
            axis=0)
    return (o0, l0, nlog, blk, rws, liv, rawt,
            _grow_table(state[7], ocap, B),
            _grow_table(state[8], ocap, B),
            hint, fwdt)


def replay_lanes_mixed_blocked(ops: OpTensors, capacity: int,
                               **kw) -> BlockedLanesMixedResult:
    """One-shot wrapper over ``make_replayer_lanes_mixed_blocked``."""
    return make_replayer_lanes_mixed_blocked(ops, capacity, **kw)()
