"""Mixed-stream RLE run engine: remote ops (hot path #2) on RUN rows.

``ops.rle`` is the north-star local-replay engine: device state is the
RLE run (`src/list/span.rs:6-119` semantics, ~40x fewer rows than chars)
but it refuses remote ops.  The round-3 engine that first applied
remote ops (YATA integrate, `doc.rs:167-234`) held ONE ROW PER
CHARACTER.  This engine is the round-4 unification: the full op
surface — KIND_LOCAL, KIND_REMOTE_INS, KIND_REMOTE_DEL — applied
directly to the run representation, so the `doc.rs:242-348` hot path
(the reference's raison d'etre) runs on state that is runs, not chars.

What the remote paths add on top of ``ops.rle``'s block grid:

- **a RAW per-slot count** (``raw``) next to the live count: remote
  cursors are RAW positions (tombstones not skipped, `doc.rs:452`), so
  the block descent needs the `FullIndex` pair (`index.rs:100-158`) —
  live sums for local edits, raw sums for integrate cursors.
- **order -> physical-block index** (``ordblk``, the `markers.rs:8` /
  `split_list/mod.rs:440` SpaceIndex analog) packed 128 orders/row,
  kept EXACT: written per insert, and a block split (the only move of
  run rows across blocks) rewrites every order of the runs it moves.
  Lookups verify containment in the indexed block (runs make that a
  range test, not an equality test); a walk over the blocks in use
  stays only as a guard, counted in ``meta`` (``lookup_counts``).
- **by-order origin/rank tables** (``oll/orl/rkl``) prefilled host-side
  (`batch.prefill_logs`), updated in-kernel by local inserts — the YATA
  scan reads per-ORDER origins, which the prefilled implicit chain
  (`span.rs:24-28`) provides for mid-run chars.
- **run-level YATA integrate**: the reference's conflict scan walks
  items one at a time (`doc.rs:183-222`); on runs, every non-head char
  of a run has ``origin_left == its own predecessor`` so the scan can
  only break mid-run at the op's ``origin_right`` — each loop iteration
  therefore consumes a WHOLE run (or jumps straight to origin_right
  inside it), shrinking the scan by the run factor.
- **remote delete by order**: runs are disjoint ORDER intervals, so a
  target range ``[t, t+dlen)`` fully covers every run it touches except
  at most the two holding its endpoints.  A short range is taken run
  by run through the exact index (flip a full cover in place, 3-way
  split a partial one); a long one by one walk over the blocks in use
  that flips the full covers, plus <= 2 by-order endpoint fix-ups.
  Already-dead covered runs count toward the idempotency total without
  flipping (idempotent concurrent deletes, `double_delete.rs:6-9`;
  excess counting stays host-side per SURVEY).  Any ``dlen`` in one
  step.

Same lane batching as ``ops.rle`` (all docs replay one shared stream),
same ``RleResult`` / ``rle_to_flat`` result surface.
"""
from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.trace import span
from .batch import (
    KIND_LOCAL,
    KIND_REMOTE_DEL,
    KIND_REMOTE_INS,
    OpTensors,
    _require,
    prefill_logs,
    require_unfused,
)
from .rle import (
    RleResult,
    _cumsum_rows,
    _delete_block_math,
    _insert_splice,
    _lane_scalar,
    _locate_run,
    _row_scalar,
    _shift_rows,
    _shift_rows_up,
    _split_piece_aux,
)
from .span_arrays import make_flat_doc

LANES = 128  # orders per by-order table row

# A remote delete of ``dlen`` orders goes run by run (at most ``dlen``
# iterations) when ``dlen * DELETE_RUN_COST`` is at most the blocks in
# use, else by one walk over them.  The constant is one run-loop
# iteration's device time over one plane-walk block's, measured on a
# v5e over the crdt-benchmarks B2.4 merge stream (N = 6,000, 128 lanes,
# block_k 128; PERF.md §6): walking the blocks twice instead of once
# added 158.9 ms over 1,588,946 blocks (0.100 us a block); a second run
# loop over each range, its runs already tombstoned, added 5.76 ms over
# 8,937 iterations (0.645 us, a ratio of 6.4); an iteration that
# tombstones also stores its row and updates the slot counts, which
# rounds the ratio up to 8.
DELETE_RUN_COST = 8


def _locate_run_raw(bo, bl, idx_k, r0, local):
    """Raw-position twin of ``rle._locate_run``: find the run containing
    RAW char #``local`` (1-based, tombstones counted).  Returns
    ``(i_r, o_r, l_r, off)`` with ``off`` the 1-based char offset."""
    cum = _cumsum_rows(bl)
    i_r = jnp.max(jnp.sum(
        ((cum < local) & (idx_k < r0)).astype(jnp.int32), axis=0))
    o_r = _row_scalar(bo, i_r, idx_k)
    l_r = _row_scalar(bl, i_r, idx_k)
    off = local - (_row_scalar(cum, i_r, idx_k) - l_r)
    return i_r, o_r, l_r, off


def _insert_splice_raw(bo, bl, idx_k, c, i_r, o_r, l_r, off, il, st,
                       o_left):
    """Raw-position twin of ``rle._insert_splice``: splice a new LIVE run
    (orders ``st..st+il``) at raw position ``c`` of a block.  Differences
    from the live-rank path: the split run may be a TOMBSTONE (sign must
    be preserved on the tail: a dead run's tail starts at
    ``-(|start|+off)``), and the merge fast path additionally requires
    the preceding run to be live (same-sign append) AND the op's
    ``origin_left`` to chain to the run's last char (`span.rs:47-53`).
    The chain gate is load-bearing for the YATA run-skip: the scan
    evaluates only run HEADS and skips the rest on the premise that
    every non-head char's origin_left is its own predecessor — merging
    an unchained run (e.g. two concurrent root inserts that happen to be
    order-contiguous) would hide a char the scan must evaluate and
    diverge from the oracle (caught round 5: ``amy/zed/mid`` -> ``azm``
    instead of ``amz``)."""
    mrg = ((c > 0) & (o_r > 0) & (off == l_r)
           & ((st + 1) == (o_r + l_r))
           & (o_left == o_r + l_r - 2))
    is_split = (c > 0) & (off < l_r)
    ins_at = jnp.where(c == 0, 0, i_r + 1)
    amt = jnp.where(mrg, 0, jnp.where(is_split, 2, 1))
    so = _shift_rows(bo, amt, 2)
    sl = _shift_rows(bl, amt, 2)
    no = jnp.where(idx_k < ins_at, bo, so)
    nl = jnp.where(idx_k < ins_at, bl, sl)
    nl = jnp.where(is_split & (idx_k == i_r), off, nl)
    new_run = (idx_k == ins_at) & jnp.logical_not(mrg)
    no = jnp.where(new_run, st + 1, no)
    nl = jnp.where(new_run, il, nl)
    tail = is_split & (idx_k == ins_at + 1)
    tail_o = jnp.where(o_r > 0, o_r + off, o_r - off)
    no = jnp.where(tail, tail_o, no)
    nl = jnp.where(tail, l_r - off, nl)
    nl = jnp.where(mrg & (idx_k == i_r), l_r + il, nl)
    return no, nl, amt, mrg, is_split


class RleMixedResult(RleResult):
    """``RleResult`` + the order-index error flag (err row 2), the
    integrate counters (``meta`` rows 1 and 2, see ``integrate_counts``)
    and the order-lookup counters (rows 3 and 4, ``lookup_counts``)."""

    def integrate_counts(self, lane: int = 0):
        """(remote inserts, those whose fast scan flagged and ran the exact
        serial walk) of one lane's pass: every remote insert runs the exact
        walk when the replayer was built with ``fast_integrate=False``."""
        meta = np.asarray(self.meta)
        return int(meta[1, lane]), int(meta[2, lane])

    def lookup_counts(self, lane: int = 0):
        """(order -> run lookups, those whose indexed block missed and ran
        the walk over every block in use) of one lane's pass.  The index
        is exact, so a walk means an order absent from the state."""
        meta = np.asarray(self.meta)
        return int(meta[3, lane]), int(meta[4, lane])

    def check(self) -> None:
        super().check()
        err = np.asarray(self.err)
        if err[2].max() != 0:
            raise RuntimeError(
                "order index lookup missed: an op referenced an order "
                "absent from device state (corrupt stream or engine bug)")


def _mixed_rle_kernel(
    kind_ref, pos_ref, dlen_ref, dtgt_ref, olop_ref, orop_ref, rk_ref,
    ilen_ref, start_ref,                        # [CHUNK] SMEM op columns
    oll_in, orl_in, rkl_in,                     # [OT, 128] by-order tables
    ol_ref, or_ref,                             # [CHUNK, B] outputs
    ordp, lenp,                                 # [CAP, B] run planes (OUT
                                                #   blocks as working state)
    blk_out, rows_out, meta_out, err_ref,       # tables + flags
    rws, liv, raw, cumliv, cumraw,              # VMEM scratch (cum* =
    ordblk, oll, orl,                           #   incremental inclusive
    olp, orp, rkp,                              #   prefixes; per-run YATA
    meta, phys, logi,                           #   aux planes; SMEM scratch
    *, K: int, NB: int, NBL: int, CHUNK: int, OT: int,
    FAST: bool = True,
):
    B = ordp.shape[1]
    CAP = K * NB
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    idx_k = lax.broadcasted_iota(jnp.int32, (K, B), 0)
    idx_l = lax.broadcasted_iota(jnp.int32, (NBL, B), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    lane2 = lax.broadcasted_iota(jnp.int32, (2, LANES), 1)
    row2 = lax.broadcasted_iota(jnp.int32, (2, LANES), 0)
    root_i = jnp.int32(-1)  # ROOT_ORDER as i32
    root_u = jnp.uint32(0xFFFFFFFF)

    # The [CAP, B] planes are only ever touched one K-row block at a
    # time: a whole-plane expression would put CAP x B temporaries in
    # VMEM beside the planes themselves, and that, not the planes,
    # is what bounds the capacity a pass can hold.
    def blk(plane, b):
        return plane[pl.ds(b * K, K), :]

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when(i == 0)
    def _init():
        zero = jnp.zeros((K, B), jnp.int32)

        # Per-run YATA aux planes (the vectorized conflict scan's
        # gather-free cache): origin-left / origin-right / author rank
        # of each run's HEAD char.  Maintained through every splice;
        # split pieces inherit or/rank and chain ol to their
        # predecessor (`span.rs:24-28` implicit chain).
        @pl.loop(0, NB)
        def _(b):
            for plane in (ordp, lenp, olp, orp, rkp):
                plane[pl.ds(b * K, K), :] = zero

        # Block maps in SMEM, logical slot <-> physical block, read as
        # scalars wherever a descent or a block walk needs one.
        @pl.loop(0, NBL)
        def _(l):
            phys[l] = 0
            logi[l] = 0

        rws[:] = jnp.zeros_like(rws)
        liv[:] = jnp.zeros_like(liv)
        raw[:] = jnp.zeros_like(raw)
        cumliv[:] = jnp.zeros_like(cumliv)
        cumraw[:] = jnp.zeros_like(cumraw)
        ordblk[:] = jnp.zeros_like(ordblk)
        err_ref[:] = jnp.zeros_like(err_ref)
        oll[:] = oll_in[:]
        orl[:] = orl_in[:]
        meta[0] = 1  # logical blocks in use
        meta[2] = 0  # remote inserts
        meta[3] = 0  # ... of which ran the exact serial walk
        meta[5] = 0  # order lookups
        meta[6] = 0  # ... of which walked every block in use

    # ---- by-order tables (order o lives at [o // 128, o % 128]) ---------

    def tab_read(tab, o):
        r = tab[pl.ds(o // LANES, 1), :]
        return jnp.sum(jnp.where(lane == o % LANES, r, 0))

    def tab_write(tab, o, v):
        r = tab[pl.ds(o // LANES, 1), :]
        tab[pl.ds(o // LANES, 1), :] = jnp.where(lane == o % LANES, v, r)

    def tab_write_run(tab, start, run_len, v):
        """tab[start : start+run_len] = v; run_len <= 128, so a 2-row
        window always covers it (tables keep a spare tail row)."""
        r0 = start // LANES
        w = tab[pl.ds(r0, 2), :]
        g = row2 * LANES + lane2 + r0 * LANES
        hit = (g >= start) & (g < start + run_len)
        tab[pl.ds(r0, 2), :] = jnp.where(hit, v, w)

    def tab_fill(tab, start, run_len, v):
        """tab[start : start+run_len] = v for a run of any length: the
        local merge path grows runs past 128 orders, and those take one
        masked write per table row they span."""

        @pl.when(run_len <= LANES)
        def _():
            tab_write_run(tab, start, run_len, v)

        @pl.when(run_len > LANES)
        def _():
            @pl.loop(start // LANES, (start + run_len - 1) // LANES + 1)
            def _(r):
                g = r * LANES + lane
                w = tab[pl.ds(r, 1), :]
                tab[pl.ds(r, 1), :] = jnp.where(
                    (g >= start) & (g < start + run_len), v, w)

    # ---- slot plumbing (logical block tables) ---------------------------

    def row_at(ref, r):
        """Row ``r`` of a lane-replicated VMEM plane or table, as one
        scalar: a one-row load, not a masked reduction."""
        return _lane_scalar(ref[pl.ds(r, 1), :])

    def slot_scalar(tbl, l):
        return _lane_scalar(jnp.where(idx_l == l, tbl[:], 0))

    # Descents take a (table, inclusive-prefix) pair; the prefixes are
    # maintained INCREMENTALLY (one masked add per update; splits shift
    # them with the other tables) instead of an 8-roll cumsum per
    # lookup — this kernel descends up to 3x per YATA while-iteration,
    # so the recompute dominated the storm's step cost.
    LIV = (liv, cumliv)
    RAW = (raw, cumraw)

    def sum_before_slot(tblcum, l):
        # One masked reduction; the incremental prefix is only needed
        # by slot_of_cum/total_of (review: cum[l] - tbl[l] would be two
        # lane reductions for the same answer).
        tbl, _ = tblcum
        return _lane_scalar(jnp.where(idx_l < l, tbl[:], 0))

    def total_of(tblcum):
        _, cum = tblcum
        return slot_scalar(cum, meta[0] - 1)

    def slot_of_cum(tblcum, rank1):
        """Smallest logical slot whose cumulative count reaches
        ``rank1`` (the `root.rs:54-88` descent over block sums; LIV for
        content cursors, RAW for raw cursors — `index.rs:100`). Slots
        >= nlog may hold stale prefixes; the mask excludes them."""
        _, cum = tblcum
        nlog = meta[0]
        hit = (cum[:] < rank1) & (idx_l < nlog)
        return jnp.minimum(
            jnp.max(jnp.sum(hit.astype(jnp.int32), axis=0)), nlog - 1)

    def split(l):
        """Leaf split (`mutations.rs:623-669`): move the top half of slot
        ``l``'s rows to a fresh physical block spliced into the logical
        order at ``l+1``.  At table capacity the split is a NO-OP with the
        error flag raised (advisor r3: proceeding overwrote a live block).
        The only move of run rows across blocks, so it rewrites the
        ``ordblk`` entries of every order of the runs it moves."""
        nlog = meta[0]

        @pl.when(nlog >= NB)
        def _cap():
            err_ref[0:1, :] = jnp.ones((1, B), jnp.int32)

        @pl.when(nlog < NB)
        def _do():
            b = phys[l]
            r = slot_scalar(rws, l)
            keep = r // 2
            mv = r - keep
            nb = nlog  # fresh physical block id
            bo = ordp[pl.ds(b * K, K), :]
            bl = lenp[pl.ds(b * K, K), :]
            hi_mask = (idx_k >= keep) & (idx_k < r)
            liv_hi = _lane_scalar(jnp.where(hi_mask & (bo > 0), bl, 0))
            raw_hi = _lane_scalar(jnp.where(hi_mask, bl, 0))
            liv_lo = slot_scalar(liv, l) - liv_hi
            raw_lo = slot_scalar(raw, l) - raw_hi

            up_o = _shift_rows_up(bo, keep, K)
            up_l = _shift_rows_up(bl, keep, K)
            new_mask = idx_k < mv
            ordp[pl.ds(nb * K, K), :] = jnp.where(new_mask, up_o, 0)
            lenp[pl.ds(nb * K, K), :] = jnp.where(new_mask, up_l, 0)
            keep_mask = idx_k < keep
            ordp[pl.ds(b * K, K), :] = jnp.where(keep_mask, bo, 0)
            lenp[pl.ds(b * K, K), :] = jnp.where(keep_mask, bl, 0)
            # Aux planes move with their rows (values unchanged: a
            # block split never changes any run's head char).
            for ap in (olp, orp, rkp):
                ax = ap[pl.ds(b * K, K), :]
                ap[pl.ds(nb * K, K), :] = jnp.where(
                    new_mask, _shift_rows_up(ax, keep, K), 0)
                ap[pl.ds(b * K, K), :] = jnp.where(keep_mask, ax, 0)

            # The moved runs' orders, tombstones too, now live in nb.
            @pl.loop(0, mv)
            def _(j):
                tab_fill(ordblk, jnp.abs(row_at(ordp, nb * K + j)) - 1,
                         row_at(lenp, nb * K + j), nb)

            # Logical positions: blocks after slot l shift one slot
            # down; the moved-out top half (new physical block nb)
            # lands at slot l + 1.
            @pl.loop(0, nlog)
            def _(p):
                lp = logi[p]
                logi[p] = jnp.where(lp > l, lp + 1, lp)

            logi[nb] = l + 1

            @pl.loop(0, NBL - l - 2)
            def _(j):
                s_ = NBL - 1 - j
                phys[s_] = phys[s_ - 1]

            phys[l + 1] = nb

            # cum prefixes shift with the tables; slot l+1 inherits the
            # old inclusive prefix of l (correct), slot l loses the
            # moved-out top half (see ops.rle split).
            for tbl in (rws, liv, raw, cumliv, cumraw):
                shifted = _shift_rows(tbl[:], 1, 1)
                tbl[:] = jnp.where(idx_l <= l, tbl[:], shifted)
            rws[pl.ds(l, 1), :] = jnp.broadcast_to(keep, (1, B))
            liv[pl.ds(l, 1), :] = jnp.broadcast_to(liv_lo, (1, B))
            raw[pl.ds(l, 1), :] = jnp.broadcast_to(raw_lo, (1, B))
            cumliv[pl.ds(l, 1), :] = cumliv[pl.ds(l, 1), :] - liv_hi
            cumraw[pl.ds(l, 1), :] = cumraw[pl.ds(l, 1), :] - raw_hi
            rws[pl.ds(l + 1, 1), :] = jnp.broadcast_to(mv, (1, B))
            liv[pl.ds(l + 1, 1), :] = jnp.broadcast_to(liv_hi, (1, B))
            raw[pl.ds(l + 1, 1), :] = jnp.broadcast_to(raw_hi, (1, B))
            meta[0] = nlog + 1

    # ---- order -> run lookup (the SpaceIndex, `split_list/mod.rs:440`) --

    def find_in_block(b, o):
        """(found, row) of the run CONTAINING order ``o`` in block ``b``
        (a range test: runs make the index 1-per-run, not 1-per-char)."""
        bo = ordp[pl.ds(b * K, K), :]
        bl = lenp[pl.ds(b * K, K), :]
        so = jnp.abs(bo) - 1
        hit = (bo != 0) & (so <= o) & (o < so + bl)
        found = _lane_scalar(hit.astype(jnp.int32)) > 0
        row = jnp.max(jnp.min(jnp.where(hit, idx_k, K), axis=0))
        return found, row

    def locate_order(o):
        """(physical block, row) of the run containing order ``o``.
        ``ordblk`` is exact: verify the indexed block; the walk over the
        blocks in use is a counted guard that only an order absent from
        the state reaches.

        Callers may pass the ROOT sentinel (-1) from a discarded
        ``jnp.where`` branch (both branches evaluate): the lookup then
        returns in-range garbage without counting, walking, raising the
        miss flag or touching the index."""
        oc = jnp.maximum(o, 0)
        bh = jnp.clip(tab_read(ordblk, oc), 0, NB - 1)
        f, row = find_in_block(bh, oc)
        # As ``integrate_entry``: the walk is predicated and its answer
        # passed through SMEM, not returned from a lax.cond branch.
        meta[4] = bh * K + jnp.minimum(row, K - 1)
        meta[5] = meta[5] + jnp.where(o >= 0, 1, 0)

        @pl.when(~f & (o >= 0))
        def _fallback():
            meta[6] = meta[6] + 1

            def walk(b, g):
                bo = blk(ordp, b)
                so = jnp.abs(bo) - 1
                hit = (bo != 0) & (so <= oc) & (oc < so + blk(lenp, b))
                return jnp.minimum(g, jnp.min(
                    jnp.where(hit, b * K + idx_k, CAP), axis=0,
                    keepdims=True))

            g = jnp.max(lax.fori_loop(0, meta[0], walk,
                                      jnp.full((1, B), CAP, jnp.int32)))
            ok = g < CAP

            @pl.when(~ok)
            def _missing():
                err_ref[2:3, :] = jnp.ones((1, B), jnp.int32)

            meta[4] = jnp.where(ok, g, CAP - 1)

        return meta[4] // K, meta[4] % K

    def pos_of_order(o):
        """RAW document position of the char with order ``o``."""
        b, row = locate_order(o)
        l = logi[b]
        bo = ordp[pl.ds(b * K, K), :]
        bl = lenp[pl.ds(b * K, K), :]
        raw_before = _lane_scalar(jnp.where(idx_k < row, bl, 0))
        so_row = jnp.abs(_row_scalar(bo, row, idx_k)) - 1
        return sum_before_slot(RAW, l) + raw_before + (o - so_row)

    def cursor_after(o):
        return jnp.where(o == root_i, 0, pos_of_order(o) + 1)

    def run_at_raw(c):
        """Signed start order, length, and 0-based char offset of the run
        holding RAW position ``c`` (one shared location routine —
        ``run_at2`` — so the serial walk and the fast scan's window
        bounds can never desynchronize)."""
        _, _, _, o_r, l_r, off = run_at2(c)
        return o_r, l_r, off

    # ---- local ops (the ops.rle paths + raw/index/table upkeep) ---------

    def find_insert_slot(p):
        l = jnp.where(p == 0, 0, slot_of_cum(LIV, p))
        return l, slot_scalar(rws, l)

    def record_insert(k, b, st, il, left, right):
        """Index + origin-table upkeep and per-op origin outputs shared by
        the local and remote insert paths."""
        tab_write_run(ordblk, st, il, b)
        tab_write(oll, st, left)
        tab_write_run(orl, st, il, right)
        ol_ref[pl.ds(k, 1), :] = jnp.broadcast_to(
            left.astype(jnp.uint32), (1, B))
        or_ref[pl.ds(k, 1), :] = jnp.broadcast_to(
            right.astype(jnp.uint32), (1, B))

    def aux_splice(b, i_r, ins_at, amt, mrg, is_split, tail_ol,
                   new_ol, new_or, new_rk):
        """Mirror an insert splice's row motion onto the per-run YATA
        aux planes of block ``b``: rows >= ``ins_at`` shift down by
        ``amt``, the new run takes the op's (origin-left, origin-right,
        rank), and a split tail chains to its own predecessor char
        while inheriting the split run's origin-right/rank."""
        ao = olp[pl.ds(b * K, K), :]
        ar = orp[pl.ds(b * K, K), :]
        ak = rkp[pl.ds(b * K, K), :]
        t_rk = _row_scalar(ak, i_r, idx_k)
        new_run = (idx_k == ins_at) & jnp.logical_not(mrg)
        tail = is_split & (idx_k == ins_at + 1)
        # A split tail's origin-right is NOT the head's (merge-appended
        # chars keep their own) — but the ``orl`` TABLE entry of the
        # tail's head char (order ``tail_ol + 1``) is exact and
        # immutable once written (every existing char's entry was
        # prefilled or recorded at insert time), so read the TRUE value
        # at split time (ADVICE r5 item 3).  The tail then re-qualifies
        # for the ``integrate_fast`` sibling classification instead of
        # poisoning the window with -2 and forcing the serial walk on
        # every later op that scans past it.
        t_or = tab_read(orl, jnp.clip(tail_ol + 1, 0, OT * LANES - 1))
        for ap, a, nv, tv in ((olp, ao, new_ol, tail_ol),
                              (orp, ar, new_or, t_or),
                              (rkp, ak, new_rk, t_rk)):
            na = jnp.where(idx_k < ins_at, a, _shift_rows(a, amt, 2))
            na = jnp.where(new_run, nv, na)
            na = jnp.where(tail, tv, na)
            ap[pl.ds(b * K, K), :] = na

    def do_local_insert(k, p, il, st):
        """Insert an ``il``-char run after LIVE rank ``p``
        (`mutations.rs:17-179`): <= 3 touched rows regardless of ``il``."""
        l, r0 = find_insert_slot(p)

        @pl.when(r0 + 2 > K)
        def _():
            split(l)

        l, r0 = find_insert_slot(p)
        b = phys[l]
        base = sum_before_slot(LIV, l)
        local = p - base
        bo = ordp[pl.ds(b * K, K), :]
        bl = lenp[pl.ds(b * K, K), :]
        i_r, o_r, l_r, off = _locate_run(bo, bl, idx_k, r0, local)
        no, nl, amt, _mrg, is_split = _insert_splice(
            bo, bl, idx_k, p, i_r, o_r, l_r, off, il, st)

        left = jnp.where(p == 0, root_i,
                         ((o_r - 1) + (off - 1)).astype(jnp.int32))
        # Raw successor (`doc.rs:452`: tombstones not skipped); read from
        # the PRE-splice block.
        nxt_in_blk = _row_scalar(bo, i_r + 1, idx_k)  # 0 past the last row
        nlog = meta[0]
        b2 = phys[jnp.minimum(l + 1, NBL - 1)]
        nxt_slot_o = jnp.max(jnp.sum(jnp.where(
            idx_k == 0, ordp[pl.ds(b2 * K, K), :], 0), axis=0))
        succ_signed = jnp.where(
            i_r + 1 < r0, nxt_in_blk,
            jnp.where(l + 1 < nlog, nxt_slot_o, 0))
        first_o = _row_scalar(bo, 0, idx_k)  # p == 0: the raw doc head
        succ_p0 = jnp.where(r0 > 0, first_o, 0)
        succ = jnp.where(p == 0, succ_p0,
                         jnp.where(is_split, o_r + off, succ_signed))
        right = jnp.where(succ == 0, root_i,
                          (jnp.abs(succ) - 1).astype(jnp.int32))

        # Split-head order uses jnp.abs like the remote path (`do_remote
        # _insert`): o_r is signed (tombstone runs are negative), and the
        # split head's order must be the magnitude regardless of liveness.
        aux_splice(b, i_r, jnp.where(p == 0, 0, i_r + 1), amt, _mrg,
                   is_split, (jnp.abs(o_r) - 1) + off - 1, left, right,
                   tab_read(rkl_in, st))
        ordp[pl.ds(b * K, K), :] = no
        lenp[pl.ds(b * K, K), :] = nl
        rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + amt
        liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] + il
        raw[pl.ds(l, 1), :] = raw[pl.ds(l, 1), :] + il
        cumliv[:] = jnp.where(idx_l >= l, cumliv[:] + il, cumliv[:])
        cumraw[:] = jnp.where(idx_l >= l, cumraw[:] + il, cumraw[:])
        record_insert(k, b, st, il, left, right)

    def do_local_delete(p, d):
        """Tombstone ``d`` live chars after live rank ``p`` (the
        `mutations.rs:520-570` walk; raw counts are unchanged)."""

        def body(carry):
            rem, iters = carry
            l = slot_of_cum(LIV, p + 1)

            @pl.when(slot_scalar(rws, l) + 2 > K)
            def _():
                split(l)

            l = slot_of_cum(LIV, p + 1)
            b = phys[l]
            base = sum_before_slot(LIV, l)
            bo = ordp[pl.ds(b * K, K), :]
            bl = lenp[pl.ds(b * K, K), :]
            aux_in = (olp[pl.ds(b * K, K), :],
                      orp[pl.ds(b * K, K), :],
                      rkp[pl.ds(b * K, K), :])
            no, nl, added, tot, aux_out = _delete_block_math(
                bo, bl, idx_k, K, base, p, rem, aux=aux_in)
            for ap, na in zip((olp, orp, rkp), aux_out):
                ap[pl.ds(b * K, K), :] = na
            ordp[pl.ds(b * K, K), :] = no
            lenp[pl.ds(b * K, K), :] = nl
            rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + added
            liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] - tot
            cumliv[:] = jnp.where(idx_l >= l, cumliv[:] - tot, cumliv[:])
            return rem - tot, iters + 1

        rem, _ = lax.while_loop(
            lambda c: (c[0] > 0) & (c[1] <= 2 * NBL), body, (d, 0))

        @pl.when(rem > 0)
        def _bad_delete():
            err_ref[1:2, :] = jnp.ones((1, B), jnp.int32)

    # ---- remote insert (`doc.rs:274-293` -> integrate) ------------------

    def run_at2(c):
        """``run_at_raw`` + the run's (logical slot, physical block,
        row): everything the fast scan's window bounds need."""
        l = slot_of_cum(RAW, c + 1)
        b = phys[l]
        r0 = slot_scalar(rws, l)
        local = c - sum_before_slot(RAW, l)
        bo = ordp[pl.ds(b * K, K), :]
        bl = lenp[pl.ds(b * K, K), :]
        cum = _cumsum_rows(bl)
        i_r = jnp.max(jnp.sum(
            ((cum <= local) & (idx_k < r0)).astype(jnp.int32), axis=0))
        o_r = _row_scalar(bo, i_r, idx_k)
        l_r = _row_scalar(bl, i_r, idx_k)
        off = local - (_row_scalar(cum, i_r, idx_k) - l_r)
        return l, b, i_r, o_r, l_r, off

    BIGK = NBL * K + K  # past any valid doc-order key

    def integrate_fast(cursor0, my_rank, o_left, o_right):
        """Vectorized YATA conflict scan: ONE classification walk over
        the run rows of the window's blocks, in document order, replaces
        the serial run-walk (whose per-op cost grows with the document
        and dominated the config-4 storm).

        Sound when every run in the scan window is either a direct
        SIBLING (head ``origin_left`` == the op's — order equality, so
        ``olc == left_cursor`` exactly) or a PHYSICALLY-CHAINED piece
        (head chains to its own predecessor char, which sits in the
        previous row of the same block — then ``olc == head position >=
        left_cursor``, the serial walk's plain advance).  When the op's
        ``origin_left`` is ROOT any other run is a plain advance too:
        ``left_cursor`` is 0, and a non-sibling's origin_left is a char
        (only siblings have ROOT there), so its ``olc`` is at least 1
        and the serial walk neither breaks nor ties on it; a run holding
        a mid-run ``o_right`` lands the cursor on ``tpos``, as the
        serial walk's ``contains_right`` step does.  Anything else
        — including a split piece whose by-order predecessor was
        spliced away from it — raises ``flag`` and the caller falls
        back to the exact serial loop, so exotic windows lose speed,
        never correctness.  The scanning/scan_start state machine
        (`doc.rs:183-222`, pinned-scan_start rule) reduces to:

          kfb = first sibling that breaks (rank > mine, same o_right)
          kll = last lower-ranked sibling before kfb
          kss = first higher-ranked different-o_right sibling after kll
          cursor = kss if it exists else kfb (else the o_right bound)

        The walk visits the window's logical slots in order, so keys
        rise from block to block and the three reductions fold: a slot
        with a lower-ranked sibling restarts ``kss`` after it, and once
        ``kfb`` is found later slots only add to ``flag``.  Lanes fold
        apart ([1, B] carries) and meet once, after the walk.
        """
        n = total_of(RAW)
        tpos = jnp.where(o_right == root_i, n, pos_of_order(o_right))
        # Window bounds as doc-order keys (logical slot * K + row).
        l0, b0, i0, o_r0, l_r0, off0 = run_at2(cursor0)
        key_lo = l0 * K + i0 - jnp.where(off0 == 0, 1, 0)
        lT, bT, iT, o_rT, l_rT, offT = run_at2(tpos)
        key_hi = jnp.where(tpos >= n, BIGK,
                           lT * K + iT + jnp.where(offT == 0, 0, 1))
        l_end = jnp.where(tpos >= n, meta[0] - 1, lT)
        root_left = o_left == root_i

        def classify(l, carry):
            kfb, kll, kss, bad_any = carry
            b = phys[l]
            bo = blk(ordp, b)
            ao, ar, ak = blk(olp, b), blk(orp, b), blk(rkp, b)
            key = l * K + idx_k
            W = (bo != 0) & (key > key_lo) & (key < key_hi)
            h = jnp.abs(bo) - 1
            S = W & (ao == o_left)
            # Chained piece whose predecessor char (order h-1) is
            # literally the previous row's last char: olc = own head
            # position, a plain advance.  Row 0 of a block cannot verify
            # adjacency (its predecessor row lives in another block) ->
            # not safe.
            e_prev = pltpu.roll(h + blk(lenp, b) - 1, 1, axis=0)
            chain = (W & ~S & (h > 0) & (ao == h - 1)
                     & (idx_k > 0) & (e_prev == h - 1))
            bad = ((W & ~S & ~chain & ~root_left)
                   | (S & ((ak == my_rank) | (ar == -2))))
            gt_r = ak > my_rank
            sgo = S & gt_r & (ar == o_right)
            sgn = S & gt_r & (ar != o_right)
            slt = S & ~gt_r

            def first(m):
                return jnp.min(jnp.where(m, key, BIGK), axis=0,
                               keepdims=True)

            kfb_b = first(sgo)
            cap = jnp.where(kfb < BIGK, -1, kfb_b)
            kll_b = jnp.max(jnp.where(slt & (key < cap), key, -1), axis=0,
                            keepdims=True)
            kss_b = first(sgn & (key > kll_b) & (key < cap))
            return (jnp.minimum(kfb, kfb_b),
                    jnp.where(kll_b >= 0, kll_b, kll),
                    jnp.where(kll_b >= 0, kss_b, jnp.minimum(kss, kss_b)),
                    jnp.maximum(bad_any, jnp.max(
                        bad.astype(jnp.int32), axis=0, keepdims=True)))

        big = jnp.full((1, B), BIGK, jnp.int32)
        kfb, kll, kss, bad_any = lax.fori_loop(
            l0, l_end + 1, classify,
            (big, jnp.full((1, B), -1, jnp.int32), big,
             jnp.zeros((1, B), jnp.int32)))
        kfb, kll, kss = jnp.min(kfb), jnp.max(kll), jnp.min(kss)
        flag = jnp.max(bad_any) > 0

        # Mid-run window start: the char AT cursor0 chains to the char
        # at cursor0 - 1 == the op's origin_left char, so it is always
        # a direct sibling (the serial walk probes it at off > 0); its
        # key precedes every window key.
        pseudo = (off0 > 0) & (cursor0 < tpos)
        # The pseudo candidate is a MID-RUN char: its origin-right and
        # rank come from the exact by-order tables (the serial walk's
        # source), not the head aux — merge-appended chars keep their
        # own origin-right.
        order0 = jnp.clip(jnp.abs(o_r0) - 1 + off0, 0, OT * LANES - 1)
        p_or = tab_read(orl, order0)
        p_rk = tab_read(rkl_in, order0)
        kP = key_lo  # strictly below every window key
        p_gt = p_rk > my_rank
        flag = flag | (pseudo & (p_rk == my_rank))
        kfb = jnp.where(pseudo & p_gt & (p_or == o_right), kP, kfb)
        kll = jnp.where(pseudo & ~p_gt & (kP < kfb) & (kll < 0), kP, kll)
        kss = jnp.where(pseudo & p_gt & (p_or != o_right)
                        & (kll < kP) & (kP < kfb), kP, kss)

        # kss was reduced against the PRE-pseudo kfb; if the pseudo
        # candidate lowered kfb (it precedes every window key), a stale
        # window kss must lose to it — compare against kfb, not BIGK.
        kwin = jnp.where(kss < kfb, kss, kfb)
        # Winner position: tpos when nothing broke earlier, the window
        # start for the pseudo candidate, else the winning run head's
        # raw position (one block read).
        l_w = jnp.clip(kwin // K, 0, NBL - 1)
        i_w = kwin % K
        b_w = phys[l_w]
        bl_w = lenp[pl.ds(b_w * K, K), :]
        hp_w = sum_before_slot(RAW, l_w) + _lane_scalar(
            jnp.where(idx_k < i_w, bl_w, 0))
        c = jnp.where(kwin >= BIGK, tpos,
                      jnp.where(kwin == kP, cursor0, hp_w))
        return c, flag

    def integrate_entry(my_rank, o_left, o_right):
        cursor0 = cursor_after(o_left)
        meta[2] = meta[2] + 1
        if not FAST:
            meta[3] = meta[3] + 1
            return integrate_cursor(cursor0, my_rank, o_left, o_right)
        c_fast, flag = integrate_fast(cursor0, my_rank, o_left, o_right)
        # Branch via pl.when + an SMEM cell, not lax.cond: a cond whose
        # branch nests the serial while-loop (with its ref writes) sends
        # Mosaic compilation into the weeds (>7 min for the storm
        # kernel vs ~20s with predication).
        meta[1] = c_fast

        @pl.when(flag)
        def _exact():
            meta[3] = meta[3] + 1
            meta[1] = integrate_cursor(cursor0, my_rank, o_left, o_right)

        return meta[1]

    def integrate_cursor(cursor0, my_rank, o_left, o_right):
        """The YATA conflict scan (`doc.rs:183-222`) over RUNS: a run's
        non-head chars have ``origin_left == own predecessor`` (olc ==
        own position > left_cursor), so after evaluating a head char the
        scan can only stop inside that run AT ``o_right`` — each
        iteration consumes a whole run or jumps straight there.
        Pinned-scan_start rule (tests/test_integrate_divergence.py).
        The serial exact path: ``integrate_fast`` replaces it whenever
        the window shape allows, falling back here via ``flag``."""
        left_cursor = cursor0
        n = total_of(RAW)

        def cond(state):
            cursor, scanning, scan_start, done = state
            return ~done & (cursor < n)

        def body(state):
            cursor, scanning, scan_start, done = state
            o_r, l_r, off = run_at_raw(cursor)
            so = jnp.abs(o_r) - 1
            other_order = so + off
            other_left = tab_read(oll, other_order)
            other_right = tab_read(orl, other_order)
            other_rank = tab_read(rkl_in, other_order)
            olc = cursor_after(other_left)
            brk = (other_order == o_right) | (olc < left_cursor)
            eq = ~brk & (olc == left_cursor)
            gt = my_rank > other_rank
            brk = brk | (eq & ~gt & (o_right == other_right))
            starts_scan = eq & ~gt & (o_right != other_right)
            new_scan_start = jnp.where(starts_scan & ~scanning, cursor,
                                       scan_start)
            new_scanning = jnp.where(
                eq, jnp.where(gt, False, jnp.where(
                    o_right == other_right, scanning, True)),
                scanning,
            )
            # Run-skip: chars (off+1 .. l_r-1) all have olc == own
            # position > left_cursor (no brk, no eq) — jump past them,
            # stopping only at o_right if this run contains it.
            contains_right = (o_right > other_order) & (o_right < so + l_r)
            step = jnp.where(contains_right, o_right - other_order,
                             l_r - off)
            return (jnp.where(brk, cursor, cursor + step), new_scanning,
                    new_scan_start, brk)

        init = (cursor0, jnp.asarray(False), cursor0, jnp.asarray(False))
        cursor, scanning, scan_start, _ = lax.while_loop(cond, body, init)
        return jnp.where(scanning, scan_start, cursor)

    def do_remote_insert(k, my_rank, o_left, o_right, il, st):
        c = integrate_entry(my_rank, o_left, o_right)
        l = jnp.where(c == 0, 0, slot_of_cum(RAW, c))

        @pl.when(slot_scalar(rws, l) + 2 > K)
        def _():
            split(l)

        l = jnp.where(c == 0, 0, slot_of_cum(RAW, c))
        b = phys[l]
        r0 = slot_scalar(rws, l)
        local = c - sum_before_slot(RAW, l)
        bo = ordp[pl.ds(b * K, K), :]
        bl = lenp[pl.ds(b * K, K), :]
        i_r, o_r, l_r, off = _locate_run_raw(bo, bl, idx_k, r0, local)
        no, nl, amt, _mrg, _is_split = _insert_splice_raw(
            bo, bl, idx_k, c, i_r, o_r, l_r, off, il, st, o_left)
        aux_splice(b, i_r, jnp.where(c == 0, 0, i_r + 1), amt, _mrg,
                   _is_split, (jnp.abs(o_r) - 1) + off - 1,
                   o_left, o_right, my_rank)
        ordp[pl.ds(b * K, K), :] = no
        lenp[pl.ds(b * K, K), :] = nl
        rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + amt
        liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] + il
        raw[pl.ds(l, 1), :] = raw[pl.ds(l, 1), :] + il
        cumliv[:] = jnp.where(idx_l >= l, cumliv[:] + il, cumliv[:])
        cumraw[:] = jnp.where(idx_l >= l, cumraw[:] + il, cumraw[:])
        record_insert(k, b, st, il, o_left, o_right)

    # ---- remote delete (`doc.rs:295-340`) -------------------------------

    def run_cover(b, row, t, dlen):
        """Signed start order, length and 0-based start of the run at
        (block ``b``, ``row``), and the part ``[a, e)`` of it that
        ``[t, t+dlen)`` covers (run-relative; empty when ``e <= a``)."""
        o_r = row_at(ordp, b * K + row)
        l_r = row_at(lenp, b * K + row)
        so = jnp.abs(o_r) - 1
        return (o_r, l_r, so, jnp.maximum(t - so, 0),
                jnp.minimum(l_r, t + dlen - so))

    def retire_partial(t, dlen, o, l):
        """Split the covered sub-range out of the LIVE, PARTLY covered
        run that holds order ``o`` in slot ``l``: [head?] [tombstone
        mid] [tail?], splitting the slot first when it is full."""

        @pl.when(row_at(rws, l) + 2 > K)
        def _():
            split(l)

        b2, row2 = locate_order(o)  # split may have moved the run
        l2 = logi[b2]
        bo = ordp[pl.ds(b2 * K, K), :]
        bl = lenp[pl.ds(b2 * K, K), :]
        o_r, l_r, so, a, e = run_cover(b2, row2, t, dlen)
        cov = e - a
        has_head = a > 0
        has_tail = e < l_r
        amt = has_head.astype(jnp.int32) + has_tail.astype(jnp.int32)
        sh_o = _shift_rows(bo, amt, 2)
        sh_l = _shift_rows(bl, amt, 2)
        no = jnp.where(idx_k <= row2, bo, sh_o)
        nl = jnp.where(idx_k <= row2, bl, sh_l)
        p0o = jnp.where(has_head, o_r, -(so + a + 1))
        p0l = jnp.where(has_head, a, cov)
        p1o = jnp.where(has_head, -(so + a + 1), so + e + 1)
        p1l = jnp.where(has_head, cov, l_r - e)
        w0 = idx_k == row2
        no = jnp.where(w0, p0o, no)
        nl = jnp.where(w0, p0l, nl)
        w1 = (idx_k == row2 + 1) & (amt >= 1)
        no = jnp.where(w1, p1o, no)
        nl = jnp.where(w1, p1l, nl)
        w2 = (idx_k == row2 + 2) & (amt == 2)
        no = jnp.where(w2, so + e + 1, no)
        nl = jnp.where(w2, l_r - e, nl)
        ordp[pl.ds(b2 * K, K), :] = no
        lenp[pl.ds(b2 * K, K), :] = nl
        # Aux pieces: piece 0 keeps the original head; later pieces
        # chain to their predecessor char (shared 3-way-split
        # transform, see rle._split_piece_aux).
        aux_out = _split_piece_aux(
            (olp[pl.ds(b2 * K, K), :], orp[pl.ds(b2 * K, K), :],
             rkp[pl.ds(b2 * K, K), :]),
            idx_k, row2, amt, w1, w2, so, a, e, has_head)
        for ap, na in zip((olp, orp, rkp), aux_out):
            ap[pl.ds(b2 * K, K), :] = na
        rws[pl.ds(l2, 1), :] = rws[pl.ds(l2, 1), :] + amt
        liv[pl.ds(l2, 1), :] = liv[pl.ds(l2, 1), :] - cov
        cumliv[:] = jnp.where(idx_l >= l2, cumliv[:] - cov, cumliv[:])

    def retire_endpoint(t, dlen, o):
        """``retire_partial`` on the run containing order ``o`` if it is
        LIVE and PARTIALLY covered — full covers were flipped by the
        plane walk, dead runs are idempotent retires."""
        b, row = locate_order(o)
        o_r, l_r, _, a, e = run_cover(b, row, t, dlen)

        @pl.when((o_r > 0) & ((a > 0) | (e < l_r)) & (e > a))
        def _():
            retire_partial(t, dlen, o, logi[b])

    def delete_by_runs(t, dlen):
        """Tombstone ``[t, t+dlen)`` run by run through the exact index:
        one iteration per run the range touches, each locating the run
        that holds the next order, counting its covered chars (dead runs
        too), flipping it in place if it is live and wholly covered, or
        3-way splitting it if live and partly covered.  Returns the
        covered total."""

        def body(carry):
            o, tot = carry
            b, row = locate_order(o)
            l = logi[b]
            o_r, l_r, so, a, e = run_cover(b, row, t, dlen)
            full = (o_r > 0) & (a == 0) & (e == l_r)

            @pl.when(full)
            def _flip():
                g = b * K + row
                ordp[pl.ds(g, 1), :] = -ordp[pl.ds(g, 1), :]
                liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] - l_r
                cumliv[:] = jnp.where(idx_l >= l, cumliv[:] - l_r,
                                      cumliv[:])

            @pl.when((o_r > 0) & ~full & (e > a))
            def _part():
                retire_partial(t, dlen, o, l)

            # At least one order on: a missed lookup (err row 2) cannot
            # stall the loop.
            return jnp.maximum(so + l_r, o + 1), tot + (e - a)

        _, tot = lax.while_loop(lambda c: c[0] < t + dlen, body,
                                (t, jnp.int32(0)))
        return tot

    def delete_by_walk(t, dlen):
        """Tombstone ``[t, t+dlen)`` in one walk over the blocks in use,
        flipping the full covers, then the <= 2 live partial runs (each
        holds an endpoint) by order.  Returns the covered total."""
        liv0 = liv[:]

        def flip(b, tot):
            # Flip the block's full covers; its slot's live count drops
            # by the flipped chars (raw counts are unchanged by
            # tombstoning).
            bo = blk(ordp, b)
            bl = blk(lenp, b)
            so = jnp.abs(bo) - 1
            cs = jnp.clip(t - so, 0, bl)
            ce = jnp.clip(t + dlen - so, 0, bl)
            cov = jnp.where(bo != 0, ce - cs, 0)
            full = (bo > 0) & (cov > 0) & (cov == bl)
            ordp[pl.ds(b * K, K), :] = jnp.where(full, -bo, bo)
            l = logi[b]
            liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] - jnp.sum(
                jnp.where(full, bl, 0), axis=0, keepdims=True)
            return tot + jnp.sum(cov, axis=0, keepdims=True)

        tot = jnp.max(lax.fori_loop(0, meta[0], flip,
                                    jnp.zeros((1, B), jnp.int32)))
        cumliv[:] = cumliv[:] - _cumsum_rows(liv0 - liv[:])

        # The last endpoint, then the first: one traced fix-up body.
        @pl.loop(0, 2)
        def _(j):
            retire_endpoint(t, dlen, jnp.where(j == 0, t + dlen - 1, t))

        return tot

    def do_remote_delete(t, dlen):
        """ORDER-interval tombstone (`doc.rs:295-340` without the
        fragmentation walk; see ops.rle_lanes_mixed): runs are disjoint
        order intervals, so [t, t+dlen) fully covers every run it
        touches except at most the two holding its endpoints.  Covered
        DEAD runs count toward the idempotency total
        (`double_delete.rs:6-9`).  Any ``dlen`` in one step — no dmax
        pre-chunking.  A range touches at most ``dlen`` runs, so it goes
        run by run unless that could cost more than a walk over the
        blocks in use (``DELETE_RUN_COST``)."""
        by_runs = dlen * DELETE_RUN_COST <= meta[0]

        def covered(tot):
            @pl.when(tot < dlen)
            def _bad():
                err_ref[1:2, :] = jnp.ones((1, B), jnp.int32)

        @pl.when(by_runs)
        def _():
            covered(delete_by_runs(t, dlen))

        @pl.when(~by_runs)
        def _():
            covered(delete_by_walk(t, dlen))

    # ---- dispatch -------------------------------------------------------

    def op_body(k, _):
        kind = kind_ref[k]
        p = pos_ref[k]
        d = dlen_ref[k]
        il = ilen_ref[k]
        st = start_ref[k]

        @pl.when((kind == KIND_LOCAL) & (d > 0))
        def _():
            do_local_delete(p, d)

        @pl.when((kind == KIND_LOCAL) & (il > 0))
        def _():
            do_local_insert(k, p, il, st)

        @pl.when((kind == KIND_REMOTE_INS) & (il > 0))
        def _():
            do_remote_insert(k, rk_ref[k], olop_ref[k], orop_ref[k], il, st)

        @pl.when(kind == KIND_REMOTE_DEL)
        def _():
            do_remote_delete(dtgt_ref[k], d)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)

    @pl.when(i == last)
    def _flush():
        @pl.loop(0, NBL)
        def _(l):
            blk_out[0, pl.ds(l, 1), :] = jnp.broadcast_to(phys[l], (1, B))

        rows_out[:] = rws[:][jnp.newaxis]
        # Row 0: blocks in use; rows 1-2: the integrate counters; rows
        # 3-4: the order-lookup counters.
        row = lax.broadcasted_iota(jnp.int32, (1, 8, B), 1)
        out = jnp.zeros((1, 8, B), jnp.int32)
        for r, m in enumerate((0, 2, 3, 5, 6)):
            out = jnp.where(row == r, meta[m], out)
        meta_out[:] = out


VMEM_LIMIT_BYTES = 124 * 1024 * 1024  # of the v5e's 128 MiB


def _table_rows(ops: OpTensors) -> int:
    """Rows of each by-order table: everything the compiler knows (remote
    origins, within-run chains, ranks), packed 128 orders/row, i32 (ROOT
    -> -1 by u32 wraparound).  One spare tail row for the 2-row run
    writes, rounded up to a sublane tile."""
    total_orders = int(np.asarray(ops.order_advance, dtype=np.int64).sum())
    ocap = max(total_orders + ops.lmax, LANES)
    rows = (ocap + LANES - 1) // LANES + 1
    return ((rows + 7) // 8) * 8


def vmem_bytes(ops: OpTensors, capacity: int, batch: int = 128,
               block_k: int = 256, chunk: int = 1024) -> int:
    """VMEM a pass of the kernel holds for ``ops`` at this geometry,
    int32 throughout: the five ``[capacity, batch]`` planes, the six
    by-order tables, the slot tables and block outputs, the
    double-buffered ``[chunk, batch]`` origin outputs.  Mosaic's own
    scratch comes on top (about 1 MiB at the merge cell's geometry, whose
    five planes are 112.8 MiB)."""
    nblp = max(8, capacity // block_k)
    words = (5 * capacity * batch + 6 * _table_rows(ops) * LANES
             + 7 * nblp * batch + 4 * chunk * batch)
    return 4 * words


def make_replayer_rle_mixed(
    ops: OpTensors,
    capacity: int,
    batch: int = 128,
    block_k: int = 256,
    chunk: int = 1024,
    interpret: bool = False,
    fast_integrate: bool = True,
):
    """Stage a mixed local/remote op stream on the RUN representation and
    build a jitted replayer.

    ``capacity`` counts RUN rows (`ops.rle` contract).  Remote deletes
    of any length apply in one step (the order-interval delete needs
    no dmax pre-chunking); insert chunks must be <= 128 chars (the
    order-table write window).

    VMEM bounds ``capacity * batch``: five ``[capacity, batch]`` int32
    planes stay resident for the whole pass, single-buffered (the
    ``ordp``/``lenp`` output blocks and the ``olp``/``orp``/``rkp``
    scratch), beside six ``[OT, 128]`` int32 by-order tables (three
    inputs, ``ordblk`` and two working copies) and the double-buffered
    ``[chunk, batch]`` origin outputs.  Every pass over the planes
    (``integrate_fast``, a long remote delete, the lookup guard) walks
    them a ``block_k``-row block at a time, so no temporary is
    plane-sized.  Compiled for a described v5e at batch 128, block_k
    128, chunk 1024: capacity 46,208 (361 blocks, crdt-benchmarks B2.4
    at N = 6,000) with OT 1,088 needs 120.21 MiB of scoped VMEM, of
    which the five planes are 112.8 MiB: over a 120 MiB limit, within
    ``VMEM_LIMIT_BYTES`` (124 MiB; the chip has 128).  ``vmem_bytes``
    counts a geometry's share, and one over the limit is refused here.
    Fewer lanes do not help below 128 (the lane axis fills a vreg).
    """
    kinds = np.asarray(ops.kind)
    _require(kinds.ndim == 1, "rle-mixed engine takes one shared stream")
    require_unfused(ops, "the rle-mixed engine")
    _require(capacity % block_k == 0,
             f"capacity ({capacity}) must be a multiple of block_k "
             f"({block_k})")
    _require(interpret or chunk % 1024 == 0 or (
        jax.default_backend() != "tpu"),
        "chunk must be a multiple of 1024 on TPU")
    NB = capacity // block_k
    _require(NB >= 1, "need at least one block")
    _require(block_k >= 8, "block_k must hold a few runs")
    _require(ops.lmax <= LANES, (
        f"insert chunks must be <= {LANES} chars for the order-table "
        f"window (compile with lmax<={LANES})"))

    OT = _table_rows(ops)
    need = vmem_bytes(ops, capacity, batch, block_k, chunk)
    _require(need <= VMEM_LIMIT_BYTES, (
        f"capacity {capacity} x {batch} lanes needs {need} B of VMEM, "
        f"over the kernel's {VMEM_LIMIT_BYTES}"))

    def table(x):
        return jnp.asarray(
            np.asarray(x, dtype=np.uint32).view(np.int32).reshape(OT, LANES))

    with span("compiler.prefill"):
        doc0 = prefill_logs(make_flat_doc(8, OT * LANES), ops)
        tables = (table(doc0.ol_log), table(doc0.or_log),
                  table(doc0.rank_log))

    s = ops.num_steps
    s_pad = max(((s + chunk - 1) // chunk) * chunk, chunk)
    pad = ((0, s_pad - s),)

    def padded(a):
        return jnp.asarray(np.pad(
            np.asarray(a, dtype=np.uint32).view(np.int32), pad))

    with span("rle.stage"):
        staged = tuple(padded(c) for c in (
            ops.kind, ops.pos, ops.del_len, ops.del_target, ops.origin_left,
            ops.origin_right, ops.rank, ops.ins_len, ops.ins_order_start))

    jitted = _build_mixed_call(s_pad, batch, capacity, block_k, chunk,
                               OT, interpret, fast_integrate)
    built = False

    def run() -> RleMixedResult:
        # As ``ops.rle``'s replayer: the first call builds the program,
        # every later one enqueues it (wall and thread CPU time).
        nonlocal built
        with span("rle.dispatch" if built else "rle.build", cpu=True):
            ol, orr, ordp, lenp, blk, rows, meta, err = jitted(*staged,
                                                               *tables)
            res = RleMixedResult(
                ordp=ordp, lenp=lenp, blkord=blk[0], rows=rows[0],
                meta=meta[0], ol=ol[:s], orr=orr[:s], err=err,
                block_k=block_k, num_blocks=NB, batch=batch)
        built = True
        return res

    return run


@functools.lru_cache(maxsize=32)
def _build_mixed_call(s_pad: int, batch: int, capacity: int,
                      block_k: int, chunk: int, OT: int,
                      interpret: bool, fast_integrate: bool):
    """Shape-keyed cache: streams sharing one geometry share one traced
    kernel (a per-call pallas_call re-traced on every replayer build —
    the cost that capped the differential-fuzz drivers; the lanes
    engines already cached theirs)."""
    NB = capacity // block_k
    NBLp = max(8, NB)
    smem = lambda: pl.BlockSpec(
        (chunk,), lambda i: (i,), memory_space=pltpu.SMEM)

    def whole(shape):
        # One buffer: the block never moves, so a second would only
        # double the resident planes.
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape),
                            pipeline_mode=pl.Buffered(1),
                            memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        partial(_mixed_rle_kernel, K=block_k, NB=NB, NBL=NBLp, CHUNK=chunk,
                OT=OT, FAST=fast_integrate),
        grid=(s_pad // chunk,),
        in_specs=[smem() for _ in range(9)] + [
            whole((OT, LANES)), whole((OT, LANES)), whole((OT, LANES))],
        out_specs=[
            pl.BlockSpec((chunk, batch), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk, batch), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            whole((capacity, batch)),
            whole((capacity, batch)),
            whole((1, NBLp, batch)),
            whole((1, NBLp, batch)),
            whole((1, 8, batch)),
            whole((8, batch)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, batch), jnp.uint32),
            jax.ShapeDtypeStruct((s_pad, batch), jnp.uint32),
            jax.ShapeDtypeStruct((capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((1, NBLp, batch), jnp.int32),
            jax.ShapeDtypeStruct((1, NBLp, batch), jnp.int32),
            jax.ShapeDtypeStruct((1, 8, batch), jnp.int32),
            jax.ShapeDtypeStruct((8, batch), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((NBLp, batch), jnp.int32),       # rws
            pltpu.VMEM((NBLp, batch), jnp.int32),       # liv
            pltpu.VMEM((NBLp, batch), jnp.int32),       # raw
            pltpu.VMEM((NBLp, batch), jnp.int32),       # cumliv
            pltpu.VMEM((NBLp, batch), jnp.int32),       # cumraw
            pltpu.VMEM((OT, LANES), jnp.int32),         # ordblk
            pltpu.VMEM((OT, LANES), jnp.int32),         # ol table
            pltpu.VMEM((OT, LANES), jnp.int32),         # or table
            pltpu.VMEM((capacity, batch), jnp.int32),   # olp (run aux)
            pltpu.VMEM((capacity, batch), jnp.int32),   # orp
            pltpu.VMEM((capacity, batch), jnp.int32),   # rkp
            pltpu.SMEM((8,), jnp.int32),                # meta
            pltpu.SMEM((NBLp,), jnp.int32),             # phys
            pltpu.SMEM((NBLp,), jnp.int32),             # logi
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="rle_mixed_replay",
    )

    def rle_mixed_replay(*args):
        return call(*args)

    return jax.jit(rle_mixed_replay)


def replay_mixed_rle(ops: OpTensors, capacity: int, **kw) -> RleMixedResult:
    """One-shot convenience wrapper over ``make_replayer_rle_mixed``."""
    return make_replayer_rle_mixed(ops, capacity, **kw)()
