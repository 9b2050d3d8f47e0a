"""Run-length blocked replay engine: RLE spans on device, VMEM-resident.

The round-2 engines stored ONE ROW PER CHARACTER (524,288 rows for the
automerge-paper trace) and applied one per-keystroke op per sequential
step.  This engine is the blueprint's missing core (SURVEY §7 "flat RLE
span arrays"): device state is the run — the same compression the
reference's `YjsSpan` B-tree entries carry (`src/list/span.rs:6-119`,
16 B/span) — and the op stream is RLE-merged (`ops.batch.merge_patches`),
so the whole automerge-paper trace is 10,712 device steps over ~13k rows
instead of 259,778 steps over 524k rows:

- state is two VMEM planes, ``ordp`` = ±(start_order+1) (sign = live /
  tombstone, 0 = empty slot) and ``lenp`` = run char length; a run row
  encodes `span.rs:9-13`'s implicit order chain — char k of a run has
  order ``start+k`` — so splits are index arithmetic (`span.rs:33-45`);
- rows pack into blocks of ``K`` runs; per-block LIVE-CHAR counts play
  the B-tree's subtree sums (`range_tree/mod.rs:85-93`): position→block
  is a masked scan over ≤``NB`` block sums, position→run one in-block
  cumsum — O(NB + K) per op on runs, not characters;
- an insert touches ≤3 rows (split + new run + tail) NO MATTER HOW LONG
  the inserted text is — the per-op cost is independent of ``ins_len``,
  which is what makes the merged stream pay off;
- a FUSED step (``rows_per_step`` W > 1, compiled by
  ``batch.compile_local_patches(fuse_w=W)`` from backwards-contiguous
  insert bursts — the kevin prepend shape the forward coalescer can't
  touch) splices W descending-order runs in ONE shift: W ops' worth of
  work per sequential device step (PERF.md §11);
- a delete flips sign on covered runs and splits at most the two
  boundary runs (`mutations.rs:520-570` semantics, tombstones =
  sign-flip per `span.rs:110-119`);
- blocks never rebalance globally: a full block SPLITS — the top half
  moves to a fresh physical block spliced into a LOGICAL block-order
  table — the device analog of the reference's leaf split
  (`mutations.rs:623-669`), O(K) per split and amortized O(1) per op.
  This removes the O(capacity)-per-overflow pathology that kept the
  round-2 engines off the pure-prepend worst case (`benches/yjs.rs:51-62`);
- documents batch in the lane dimension (identical-stream lanes), and
  divergent doc GROUPS ride a leading grid dimension exactly like
  ``ops.rle_hbm`` (config-3 ragged corpus shape);
- control scalars and single rows are read as one-row loads
  (``ref[pl.ds(row, 1), :]``): only the descent and the delete's cover
  counts reduce over a whole plane.

Origins a local insert discovers (`doc.rs:447-453`) are emitted per op:
``origin_left`` of the run head, with the rest of the run chained
implicitly host-side (`span.rs:24-28`); ``origin_right`` is the raw
successor (tombstones NOT skipped, the `doc.rs:452` behavior the other
engines match).  ``rle_to_flat`` expands the run rows to the standard
per-char ``FlatDoc`` so every downstream consumer (sync, checkpoint,
oracle diff) is unchanged.
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

from ..common import ROOT_ORDER
from ..obs.trace import span
from .batch import (
    KIND_LOCAL,
    OpTensors,
    _require,
    fused_width_checked,
    merge_fused_origins,
    prefill_logs,
)
from .span_arrays import FlatDoc, I32, U32, make_flat_doc


def _lane_scalar(x2d) -> jax.Array:
    """Row-sum then lane-max: collapse a lane-replicated [rows, B] value to
    one scalar. Valid because every doc (lane) replays the same stream, so
    all lanes hold identical control state."""
    return jnp.max(jnp.sum(x2d, axis=0))


def _cumsum_rows(x) -> jax.Array:
    """Inclusive cumsum along the (sublane) row axis via log2 roll-adds."""
    n = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    out = x
    shift = 1
    while shift < n:
        out = out + jnp.where(row >= shift, pltpu.roll(out, shift, axis=0), 0)
        shift *= 2
    return out


def _shift_rows(x, amount, max_amount: int) -> jax.Array:
    """Rows shifted toward higher indices by dynamic ``amount``
    (0..max_amount) — one static roll per bit (``flat._shift_right``)."""
    out = x
    for b in range(max(max_amount, 1).bit_length()):
        out = jnp.where((amount >> b) & 1 != 0,
                        pltpu.roll(out, 1 << b, axis=0), out)
    return out


def _shift_rows_up(x, amount, max_amount: int) -> jax.Array:
    """Rows shifted toward LOWER indices by dynamic ``amount`` (the
    mirror of ``_shift_rows``): out[j] = x[j + amount]."""
    out = x
    n = x.shape[0]
    for b in range(max(max_amount, 1).bit_length()):
        s = (1 << b) % n
        if s:
            out = jnp.where((amount >> b) & 1 != 0,
                            pltpu.roll(out, n - s, axis=0), out)
    return out



def _row_scalar(arr2d, r, idx_k) -> jax.Array:
    """Row ``r`` of a lane-replicated [K, B] value, as one scalar."""
    return jnp.max(jnp.sum(jnp.where(idx_k == r, arr2d, 0), axis=0))


def _masked_run_rows(bo, bl, idx_k):
    """The default row-read primitive of the shared block math
    (``_locate_run``, ``_delete_block_math``; ``fused_splice_rows``
    takes its ``shift`` the same way).  ``rows(cum, lv)`` returns
    ``read(r) -> (o, l, before)``: row ``r``'s signed order and length
    in the block as loaded, and the live chars before it in the block
    (``cum - lv`` at ``r``).  Each read is a masked reduction over the
    whole [K, B] value, which any block value allows; ``_rle_kernel``
    passes one-row loads of its refs instead (``block_rows``)."""

    def rows(cum, lv):
        def read(r):
            return (_row_scalar(bo, r, idx_k), _row_scalar(bl, r, idx_k),
                    _row_scalar(cum, r, idx_k) - _row_scalar(lv, r, idx_k))
        return read
    return rows


def _locate_run(bo, bl, idx_k, r0, local, rows=None):
    """Find the run containing live char #``local`` (1-based) in a block:
    returns ``(i_r, o_r, l_r, off)`` — row index, ±(order+1), length, and
    the 1-based char offset within the run. The hit is a live run by
    construction (tombstone rows don't advance the live cumsum).
    ``rows`` is the caller's row-read primitive (``_masked_run_rows``
    when None)."""
    lv = jnp.where(bo > 0, bl, 0)
    cum = _cumsum_rows(lv)
    i_r = jnp.max(jnp.sum(
        ((cum < local) & (idx_k < r0)).astype(jnp.int32), axis=0))
    rows = rows or _masked_run_rows(bo, bl, idx_k)
    o_r, l_r, before = rows(cum, lv)(i_r)
    return i_r, o_r, l_r, local - before


def fused_splice_rows(bo, bl, idx, p, i_r, o_r, l_r, off, il, st, w,
                      wmax: int, shift, active=None):
    """THE W-row fused-splice arithmetic, shared by every fused kernel
    (``rle``/``rle_hbm`` via ``_insert_splice``; both ``rle_lanes`` and
    both ``rle_lanes_mixed`` kernels call it directly with their lane
    mask and shift primitive) — the PR-6 review debt: five drifting
    copies of this block, now one.

    ``w`` run rows of stride ``L = il // w`` land in ONE shift — row j
    of the spliced window holds orders ``st + il - (j+1)*L`` (patch
    order DESCENDS in document order: a same-position burst prepends
    each patch before the previous one).  ``w == 1`` reduces to the
    plain splice exactly (one row, order ``st``, length ``il``).  The
    in-kernel append-merge stays w==1-only: a fused burst's first patch
    merging would be un-done by its second patch's split at the same
    boundary, so skipping it keeps the expanded state bit-identical to
    the unfused stream (see the compile-side proof note).

    ``idx`` is the caller's row-index plane, ``shift`` its row-shift
    primitive (``_shift_rows`` for [K, 1] grids, the lanes kernels'
    ``_vshift`` for [K, B] planes), ``wmax`` the static shift bound,
    and ``active`` an optional lane mask (None = every lane active —
    the single-doc kernels).  Returns ``(no, nl, amt, mrg, is_split,
    lrun)``: new order/length planes, rows added, path flags, and the
    fused stride (the mixed kernels' by-order table writes need it).
    """
    lrun = il // jnp.maximum(w, 1)
    mrg = (w == 1) & (p > 0) & (off == l_r) & ((st + 1) == (o_r + l_r))
    is_split = (p > 0) & (off < l_r)
    if active is None:
        dead = mrg
    else:
        mrg = active & mrg
        is_split = active & is_split
        dead = jnp.logical_not(active) | mrg
    ins_at = jnp.where(p == 0, 0, i_r + 1)
    amt = jnp.where(dead, 0, w + is_split.astype(jnp.int32))
    so = shift(bo, amt, wmax + 1)
    sl = shift(bl, amt, wmax + 1)
    no = jnp.where(idx < ins_at, bo, so)
    nl = jnp.where(idx < ins_at, bl, sl)
    nl = jnp.where(is_split & (idx == i_r), off, nl)
    new_run = (idx >= ins_at) & (idx < ins_at + w) & \
        jnp.logical_not(mrg)
    if active is not None:
        new_run = active & new_run
    no = jnp.where(new_run, st + il - (idx - ins_at + 1) * lrun + 1, no)
    nl = jnp.where(new_run, lrun, nl)
    tail = is_split & (idx == ins_at + w)
    no = jnp.where(tail, o_r + off, no)
    nl = jnp.where(tail, l_r - off, nl)
    nl = jnp.where(mrg & (idx == i_r), l_r + il, nl)
    return no, nl, amt, mrg, is_split, lrun


def _insert_splice(bo, bl, idx_k, p, i_r, o_r, l_r, off, il, st,
                   w=None, wmax: int = 1):
    """In-register insert splice (`mutations.rs:17-179`): ≤3 touched rows
    regardless of ``il``. Returns ``(no, nl, amt, mrg, is_split)`` —
    the new block planes, rows added, and which path was taken.

    ``w``/``wmax`` extend the splice to FUSED multi-row steps
    (``batch.compile_local_patches`` ``fuse_w``); the arithmetic lives
    in ``fused_splice_rows`` (shared with the lanes kernels).

    The in-place merge path is device-state compaction only (an
    order-contiguous live extension of run ``i_r``); YjsSpan merge
    predicates live host-side — this run is raw doc order.
    """
    if w is None:
        w = jnp.int32(1)
    no, nl, amt, mrg, is_split, _lrun = fused_splice_rows(
        bo, bl, idx_k, p, i_r, o_r, l_r, off, il, st, w, wmax,
        _shift_rows)
    return no, nl, amt, mrg, is_split


def _split_piece_aux(aux, idx_k, i_p, amt, w1, w2, so0, s_off, e_off,
                     has_head):
    """Aux-plane transform shared by every 3-way run split ([head?]
    [mid] [tail?] — delete boundaries and remote-delete endpoint
    retires): pieces after the first chain to their own predecessor
    char (`span.rs:24-28` implicit chain survives splits), their
    origin-right is poisoned with -2 (unknowable from the head:
    merge-appended chars keep their own; ops/rle_mixed.py falls back
    to the serial walk if such a piece ever classifies as a sibling),
    and rank is inherited (runs are single-agent).  ``so0`` is the
    run's 0-based start order; pieces begin at ``so0 + s_off`` /
    ``so0 + e_off``.  Returns the three transformed planes."""
    olp_b, orp_b, rkp_b = aux
    t_rk = _row_scalar(rkp_b, i_p, idx_k)
    sent = jnp.int32(-2)
    p1_ol = jnp.where(has_head, so0 + s_off - 1, so0 + e_off - 1)
    p2_ol = so0 + e_off - 1
    out = []
    for a, v1, v2 in ((olp_b, p1_ol, p2_ol), (orp_b, sent, sent),
                      (rkp_b, t_rk, t_rk)):
        na = jnp.where(idx_k <= i_p, a, _shift_rows(a, amt, 2))
        na = jnp.where(w1, v1, na)
        na = jnp.where(w2, v2, na)
        out.append(na)
    return tuple(out)


def _delete_block_math(bo, bl, idx_k, K, base, p, rem, aux=None,
                       rows=None):
    """One delete iteration over one block (`mutations.rs:520-570`): flip
    fully-covered runs, split at most the two boundary runs. Returns
    ``(no, nl, added_rows, covered)``; caller walks blocks while
    ``covered`` hasn't reached ``rem``.

    ``aux`` (optional) is a tuple of per-run head-metadata planes
    (origin-left, origin-right, rank — the ``rle_mixed`` YATA fast-path
    cache); split pieces inherit their run's origin-right/rank, and a
    non-first piece's head chains to its own predecessor char (the
    `span.rs:24-28` implicit chain survives splits). Returns the
    transformed aux as a 5th element when given.

    ``rows`` is the caller's row-read primitive (``_masked_run_rows``
    when None).  Both boundary rows are read as loaded: a partial row
    is never flipped, and ``i1 < i2`` keeps its place through ``i2``'s
    split."""

    def apply_partial(active, i_p, bo, bl, aux):
        o, ln, live_before = read(i_p)
        lv_i = jnp.where(o > 0, ln, 0)
        before_i = base + live_before
        cs_i = jnp.clip(p - before_i, 0, lv_i)
        ce_i = jnp.clip(p + rem - before_i, 0, lv_i)
        cov_i = ce_i - cs_i
        has_head = (cs_i > 0) & active
        has_tail = (ce_i < ln) & active
        amt = has_head.astype(jnp.int32) + has_tail.astype(jnp.int32)
        so = _shift_rows(bo, amt, 2)
        sl = _shift_rows(bl, amt, 2)
        no = jnp.where(idx_k <= i_p, bo, so)
        nl = jnp.where(idx_k <= i_p, bl, sl)
        # Part layout: [head?] [tombstone mid] [tail?]; the tombstone
        # start encodes as -(o + cs) per the ±(order+1) convention.
        p0o = jnp.where(has_head, o, -(o + cs_i))
        p0l = jnp.where(has_head, cs_i, cov_i)
        p1o = jnp.where(has_head, -(o + cs_i), o + ce_i)
        p1l = jnp.where(has_head, cov_i, ln - ce_i)
        w0 = active & (idx_k == i_p)
        no = jnp.where(w0, p0o, no)
        nl = jnp.where(w0, p0l, nl)
        w1 = active & (idx_k == i_p + 1) & (amt >= 1)
        no = jnp.where(w1, p1o, no)
        nl = jnp.where(w1, p1l, nl)
        w2 = active & (idx_k == i_p + 2) & (amt == 2)
        no = jnp.where(w2, o + ce_i, no)
        nl = jnp.where(w2, ln - ce_i, nl)
        if aux is None:
            return no, nl, amt, None
        # Partial covers only reach LIVE runs: o > 0, start order o-1.
        # Piece 0 keeps the original head (its aux row is untouched).
        return no, nl, amt, _split_piece_aux(
            aux, idx_k, i_p, amt, w1, w2, o - 1, cs_i, ce_i, has_head)

    lv = jnp.where(bo > 0, bl, 0)
    cum = _cumsum_rows(lv)
    before = base + cum - lv
    cs = jnp.clip(p - before, 0, lv)
    ce = jnp.clip(p + rem - before, 0, lv)
    cov = ce - cs
    tot = jnp.max(jnp.sum(cov, axis=0))
    full = (cov > 0) & (cov == bl)
    part = (cov > 0) & jnp.logical_not(full)
    npart = jnp.max(jnp.sum(part.astype(jnp.int32), axis=0))
    i1 = jnp.max(jnp.min(jnp.where(part, idx_k, K), axis=0))
    i2 = jnp.max(jnp.max(jnp.where(part, idx_k, -1), axis=0))
    read = (rows or _masked_run_rows(bo, bl, idx_k))(cum, lv)

    bo = jnp.where(full, -bo, bo)
    # Higher-index boundary first so i1's row index stays valid.
    bo, bl, a2, aux = apply_partial(npart >= 1, i2, bo, bl, aux)
    bo, bl, a1, aux = apply_partial(npart == 2, i1, bo, bl, aux)
    if aux is None:
        return bo, bl, a1 + a2, tot
    return bo, bl, a1 + a2, tot, aux


def _rle_kernel(
    pos_ref, dlen_ref, ilen_ref, start_ref,     # [CHUNK] SMEM op columns
    w_ref,                                      # [CHUNK] SMEM rows_per_step
    ol_ref, or_ref,                             # [1,CHUNK,B] VMEM outputs
    ordp, lenp,                                 # [CAP,B] state planes (OUT
                                                #   blocks used as working
                                                #   state — halves VMEM)
    blk_out, rows_out, meta_out, err_ref,       # tables + flags
    blkord, rws, liv, cumliv, meta,             # persistent scratch
    cumbuf,                                     # [K,B] staged cum plane
    *, K: int, NB: int, NBL: int, CHUNK: int, WMAX: int,
):
    B = ordp.shape[1]
    g = pl.program_id(0)
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1
    idx_k = lax.broadcasted_iota(jnp.int32, (K, B), 0)
    idx_l = lax.broadcasted_iota(jnp.int32, rws.shape, 0)
    root_u = jnp.uint32(ROOT_ORDER)

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when((g == 0) & (i == 0))
    def _init_err():
        err_ref[:] = jnp.zeros_like(err_ref)

    @pl.when(i == 0)
    def _init():
        # Fresh group: empty document, one empty block in logical slot 0.
        ordp[:] = jnp.zeros_like(ordp)
        lenp[:] = jnp.zeros_like(lenp)
        blkord[:] = jnp.zeros_like(blkord)
        rws[:] = jnp.zeros_like(rws)
        liv[:] = jnp.zeros_like(liv)
        cumliv[:] = jnp.zeros_like(cumliv)
        meta[0] = 1  # blocks in use (logical slots == physical blocks)

    def row_at(ref, r):
        """Row ``r`` of a lane-replicated VMEM plane, as one scalar: a
        one-row load, not a masked reduction over the plane."""
        return _lane_scalar(ref[pl.ds(r, 1), :])

    def block_rows(b):
        """The shared block math's row-read primitive for physical block
        ``b`` (the ``_masked_run_rows`` contract): stored rows are
        one-row loads of ``ordp``/``lenp``, read before the block is
        stored back; ``cum`` is staged once in ``cumbuf`` and a row's
        own live length follows from its order and length.  Rows are
        clamped into the block: every read outside it is masked by the
        caller."""

        def rows(cum, lv):
            cumbuf[:] = cum

            def read(r):
                r = jnp.clip(r, 0, K - 1)
                o = row_at(ordp, b * K + r)
                ln = row_at(lenp, b * K + r)
                return o, ln, row_at(cumbuf, r) - jnp.where(o > 0, ln, 0)
            return read
        return rows

    def live_before_slot(l):
        return row_at(cumliv, l) - row_at(liv, l)

    def slot_of_live_rank(rank1):
        """Smallest logical slot whose cumulative live-char count reaches
        ``rank1`` (the B-tree descent `root.rs:54-88` over block sums).

        ``cumliv`` is the inclusive live prefix per slot, maintained
        INCREMENTALLY (one masked add per op; splits shift it with the
        other tables) instead of recomputed by an 8-roll cumsum on every
        descent — the remaining sequencing-cost lever PERF.md §6 named.
        Slots >= nlog may hold stale values; the mask excludes them."""
        nlog = meta[0]
        hit = (cumliv[:] < rank1) & (idx_l < nlog)
        return jnp.minimum(
            jnp.max(jnp.sum(hit.astype(jnp.int32), axis=0)), nlog - 1)

    def split(l):
        """Leaf split (`mutations.rs:623-669`): move the top half of slot
        ``l``'s rows to a fresh physical block and splice it into the
        logical order at ``l+1``. O(K); never a global rebalance.

        At table capacity the split is a NO-OP with the error flag
        raised (advisor r3: proceeding overwrote an in-use physical
        block and left duplicate blkord entries — silent corruption for
        raw-state readers that skip ``check()``)."""
        nlog = meta[0]

        @pl.when(nlog >= NB)
        def _cap():
            err_ref[0:1, :] = jnp.ones((1, B), jnp.int32)

        @pl.when(nlog < NB)
        def _do():
            b = row_at(blkord, l)
            r = row_at(rws, l)
            keep = r // 2
            mv = r - keep
            nb = nlog  # fresh physical block id
            bo = ordp[pl.ds(b * K, K), :]
            bl = lenp[pl.ds(b * K, K), :]
            liv_hi = _lane_scalar(jnp.where(
                (idx_k >= keep) & (idx_k < r) & (bo > 0), bl, 0))
            liv_lo = row_at(liv, l) - liv_hi

            up_o = _shift_rows_up(bo, keep, K)
            up_l = _shift_rows_up(bl, keep, K)
            new_mask = idx_k < mv
            ordp[pl.ds(nb * K, K), :] = jnp.where(new_mask, up_o, 0)
            lenp[pl.ds(nb * K, K), :] = jnp.where(new_mask, up_l, 0)
            keep_mask = idx_k < keep
            ordp[pl.ds(b * K, K), :] = jnp.where(keep_mask, bo, 0)
            lenp[pl.ds(b * K, K), :] = jnp.where(keep_mask, bl, 0)

            # Splice the new block into the logical order at slot l+1.
            # cumliv shifts with the tables: slots > l take the old
            # predecessor prefix (slot l+1 inherits old c_l, which IS
            # its inclusive prefix after the split); slot l's inclusive
            # prefix loses the moved-out top half.
            for tbl in (blkord, rws, liv, cumliv):
                shifted = _shift_rows(tbl[:], 1, 1)
                tbl[:] = jnp.where(idx_l <= l, tbl[:], shifted)
            rws[pl.ds(l, 1), :] = jnp.broadcast_to(keep, (1, B))
            liv[pl.ds(l, 1), :] = jnp.broadcast_to(liv_lo, (1, B))
            cumliv[pl.ds(l, 1), :] = cumliv[pl.ds(l, 1), :] - liv_hi
            blkord[pl.ds(l + 1, 1), :] = jnp.broadcast_to(nb, (1, B))
            rws[pl.ds(l + 1, 1), :] = jnp.broadcast_to(mv, (1, B))
            liv[pl.ds(l + 1, 1), :] = jnp.broadcast_to(liv_hi, (1, B))
            meta[0] = nlog + 1

    def find_insert_slot(p):
        l = jnp.where(p == 0, 0, slot_of_live_rank(p))
        return l, row_at(rws, l)

    def do_insert(k, p, il, st, w):
        """Insert an ``il``-char run (or, fused, ``w`` runs of stride
        ``il//w``) after live rank ``p`` (`mutations.rs:17-179`):
        ≤ w+2 touched rows regardless of ``il``.  One split always
        makes room: the builder enforces WMAX <= K//2 - 1, so a
        freshly-split slot (≤ ⌈K/2⌉ rows) fits w+1 more."""
        l, r0 = find_insert_slot(p)

        @pl.when(r0 + w + 1 > K)
        def _():
            split(l)

        l, r0 = find_insert_slot(p)
        b = row_at(blkord, l)
        base = live_before_slot(l)
        local = p - base
        bo = ordp[pl.ds(b * K, K), :]
        bl = lenp[pl.ds(b * K, K), :]
        i_r, o_r, l_r, off = _locate_run(bo, bl, idx_k, r0, local,
                                         block_rows(b))
        no, nl, amt, _mrg, is_split = _insert_splice(
            bo, bl, idx_k, p, i_r, o_r, l_r, off, il, st, w, WMAX)

        left = jnp.where(p == 0, root_u,
                         ((o_r - 1) + (off - 1)).astype(jnp.uint32))
        # Raw successor (`doc.rs:452`: tombstones not skipped); read from
        # the PRE-splice block, before it is stored back below.  Row
        # i_r+1 is the successor only below r0, and the next slot's row
        # 0 only while that slot exists: the selects below mask the rest.
        nxt_in_blk = row_at(ordp, b * K + jnp.minimum(i_r + 1, K - 1))
        nlog = meta[0]
        b2 = row_at(blkord, jnp.minimum(l + 1, NBL - 1))
        nxt_slot_o = row_at(ordp, b2 * K)
        succ_signed = jnp.where(
            i_r + 1 < r0, nxt_in_blk,
            jnp.where(l + 1 < nlog, nxt_slot_o, 0))
        first_o = row_at(ordp, b * K)  # p == 0: the raw doc head
        succ_p0 = jnp.where(r0 > 0, first_o, 0)
        succ = jnp.where(p == 0, succ_p0,
                         jnp.where(is_split, o_r + off, succ_signed))
        right = jnp.where(succ == 0, root_u,
                          (jnp.abs(succ) - 1).astype(jnp.uint32))

        ordp[pl.ds(b * K, K), :] = no
        lenp[pl.ds(b * K, K), :] = nl
        rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + amt
        liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] + il
        cumliv[:] = jnp.where(idx_l >= l, cumliv[:] + il, cumliv[:])

        ol_ref[:, pl.ds(k, 1), :] = jnp.broadcast_to(left, (1, 1, B))
        or_ref[:, pl.ds(k, 1), :] = jnp.broadcast_to(right, (1, 1, B))

    def do_delete(p, d):
        """Tombstone ``d`` live chars after live rank ``p``: per block,
        flip fully-covered runs and split at most the two boundary runs
        (`mutations.rs:520-570`; `doc.rs:311-334` fragmentation)."""

        def body(carry):
            rem, iters = carry
            l = slot_of_live_rank(p + 1)

            @pl.when(row_at(rws, l) + 2 > K)
            def _():
                split(l)

            l = slot_of_live_rank(p + 1)
            b = row_at(blkord, l)
            base = live_before_slot(l)
            bo = ordp[pl.ds(b * K, K), :]
            bl = lenp[pl.ds(b * K, K), :]
            no, nl, added, tot = _delete_block_math(
                bo, bl, idx_k, K, base, p, rem, rows=block_rows(b))
            ordp[pl.ds(b * K, K), :] = no
            lenp[pl.ds(b * K, K), :] = nl
            rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + added
            liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] - tot
            cumliv[:] = jnp.where(idx_l >= l, cumliv[:] - tot, cumliv[:])
            return rem - tot, iters + 1

        # Each iteration clears one block's covered span; > 2*NBL
        # iterations means the delete ran off the document.
        rem, _ = lax.while_loop(
            lambda c: (c[0] > 0) & (c[1] <= 2 * NBL), body, (d, 0))

        @pl.when(rem > 0)
        def _bad_delete():
            err_ref[1:2, :] = jnp.ones((1, B), jnp.int32)

    def op_body(k, _):
        p = pos_ref[k]
        d = dlen_ref[k]
        il = ilen_ref[k]
        st = start_ref[k]
        w = jnp.maximum(w_ref[k], 1)  # no-op pad rows carry 0

        @pl.when(d > 0)
        def _():
            do_delete(p, d)

        @pl.when(il > 0)
        def _():
            do_insert(k, p, il, st, w)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)

    @pl.when(i == last)
    def _flush():
        blk_out[:] = blkord[:][jnp.newaxis]
        rows_out[:] = rws[:][jnp.newaxis]
        row0 = lax.broadcasted_iota(jnp.int32, (1, 8, B), 1) == 0
        meta_out[:] = jnp.where(row0, meta[0], 0)


@dataclasses.dataclass
class RleResult:
    """Device outputs of one RLE replay (one doc group)."""

    ordp: jax.Array     # i32[CAP, B] ±(start_order+1) per run row
    lenp: jax.Array     # i32[CAP, B] run char length
    blkord: jax.Array   # i32[NBLp, B] logical slot -> physical block
    rows: jax.Array     # i32[NBLp, B] occupied rows per logical slot
    meta: jax.Array     # i32[8, B]   row 0: blocks in use
    ol: jax.Array       # u32[S, B]   per-op run-head origin_left
    orr: jax.Array      # u32[S, B]   per-op origin_right
    err: jax.Array      # i32[8, B]   0: block capacity; 1: bad delete
    block_k: int
    num_blocks: int
    batch: int

    def check(self) -> None:
        err = np.asarray(self.err)
        if err[0].max() != 0:
            raise RuntimeError(
                "rle engine out of blocks (every split consumed); raise "
                "capacity")
        if err[1].max() != 0:
            raise RuntimeError(
                "delete ran past the end of the document (invalid op "
                "stream)")


def make_replayer_rle(
    ops,
    capacity: int,
    batch: int = 128,
    block_k: int = 256,
    chunk: int = 1024,
    interpret: bool = False,
):
    """Build a jitted replayer for one local-edit stream (or a SEQUENCE
    of streams — divergent doc groups on a leading grid dimension, the
    group contract ``ops.rle_hbm`` shares).

    ``capacity`` counts RUN ROWS, not characters: automerge-paper peaks
    at 13,218 rows (vs 524,288 char rows) — compile the stream with
    ``merge_patches`` first or every keystroke costs a row.
    """
    grouped = isinstance(ops, (list, tuple))
    streams = list(ops) if grouped else [ops]
    G = len(streams)
    _require(G >= 1, "need at least one op stream")
    for st in streams:
        kinds = np.asarray(st.kind)
        _require(kinds.ndim == 1, "rle engine takes per-group shared "
                 "streams (no per-lane batching inside a group)")
        _require(bool((kinds == KIND_LOCAL).all()),
                 "rle engine replays local streams; remote ops -> "
                 "ops.rle_mixed / ops.flat")
    _require(capacity % block_k == 0,
             f"capacity ({capacity}) must be a multiple of block_k "
             f"({block_k})")
    _require(interpret or chunk % 1024 == 0 or (
        jax.default_backend() != "tpu"),
        "chunk must be a multiple of 1024 on TPU")
    NB = capacity // block_k
    _require(NB >= 1, "need at least one block")
    _require(block_k >= 8, "block_k must hold a few runs")
    NBLp = max(8, NB)
    WMAX = fused_width_checked(streams, block_k)

    lens = [st.num_steps for st in streams]
    s_pad = max(((max(lens) + chunk - 1) // chunk) * chunk, chunk)

    def staged_col(get):
        cols = []
        for st in streams:
            a = np.asarray(get(st), dtype=np.int32)
            cols.append(np.pad(a, ((0, s_pad - len(a)),)))
        # Flat [G*s_pad]: grouped 2-D SMEM blocks are not a legal TPU
        # layout (block second-minor must divide by 8 or equal the
        # array dim); 1-D chunk blocks indexed g*(s_pad//chunk)+i are.
        return jnp.asarray(np.concatenate(cols))

    with span("rle.stage"):
        staged = (staged_col(lambda o: o.pos),
                  staged_col(lambda o: o.del_len),
                  staged_col(lambda o: o.ins_len),
                  staged_col(lambda o: o.ins_order_start),
                  staged_col(lambda o: o.rows_per_step))

    jitted = _build_call(G, s_pad, batch, capacity, block_k, chunk,
                         WMAX, interpret)
    built = False

    def run():
        # The first call builds the program (trace, lower, compile or
        # compile-cache load); every later one enqueues it.  The unpack
        # can block behind the device once the runtime's queue is full,
        # so the span reads thread CPU time too: the host's own work.
        nonlocal built
        with span("rle.dispatch" if built else "rle.build", cpu=True):
            ol, orr, ordp, lenp, blk, rows, meta, err = jitted(*staged)
            results = [
                RleResult(
                    ordp=ordp[gi * capacity:(gi + 1) * capacity],
                    lenp=lenp[gi * capacity:(gi + 1) * capacity],
                    blkord=blk[gi], rows=rows[gi], meta=meta[gi],
                    ol=ol[gi, :lens[gi]], orr=orr[gi, :lens[gi]], err=err,
                    block_k=block_k, num_blocks=NB, batch=batch)
                for gi in range(G)
            ]
        built = True
        return results if grouped else results[0]

    return run


@functools.lru_cache(maxsize=32)
def _build_call(G: int, s_pad: int, batch: int, capacity: int,
                block_k: int, chunk: int, wmax: int, interpret: bool):
    """Shape-keyed cache (the ``rle_lanes._build_call`` pattern): every
    same-shape replay shares one traced kernel — a per-call ``jax.jit``
    re-traces the whole interpret program each time, which dominates
    the fixed-shape test suites.  The call is named ``rle_replay``, in
    its jit and its Pallas kernel alike, so a profile names it."""
    NB = capacity // block_k
    NBLp = max(8, NB)
    blocks_per_g = s_pad // chunk
    smem = lambda: pl.BlockSpec(
        (chunk,), lambda g, i: (g * blocks_per_g + i,),
        memory_space=pltpu.SMEM)

    call = pl.pallas_call(
        partial(_rle_kernel, K=block_k, NB=NB, NBL=NBLp, CHUNK=chunk,
                WMAX=wmax),
        grid=(G, s_pad // chunk),
        in_specs=[smem(), smem(), smem(), smem(), smem()],
        out_specs=[
            pl.BlockSpec((1, chunk, batch), lambda g, i: (g, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, batch), lambda g, i: (g, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((capacity, batch), lambda g, i: (g, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((capacity, batch), lambda g, i: (g, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, NBLp, batch), lambda g, i: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, NBLp, batch), lambda g, i: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, batch), lambda g, i: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((8, batch), lambda g, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, s_pad, batch), jnp.uint32),
            jax.ShapeDtypeStruct((G, s_pad, batch), jnp.uint32),
            jax.ShapeDtypeStruct((G * capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((G * capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((G, NBLp, batch), jnp.int32),
            jax.ShapeDtypeStruct((G, NBLp, batch), jnp.int32),
            jax.ShapeDtypeStruct((G, 8, batch), jnp.int32),
            jax.ShapeDtypeStruct((8, batch), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((NBLp, batch), jnp.int32),       # blkord
            pltpu.VMEM((NBLp, batch), jnp.int32),       # rws
            pltpu.VMEM((NBLp, batch), jnp.int32),       # liv
            pltpu.VMEM((NBLp, batch), jnp.int32),       # cumliv
            pltpu.SMEM((2,), jnp.int32),                # meta
            pltpu.VMEM((block_k, batch), jnp.int32),    # cumbuf
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
        name="rle_replay",
    )

    def rle_replay(pos, dlen, ilen, start, width):
        return call(pos, dlen, ilen, start, width)

    return jax.jit(rle_replay)


def replay_local_rle(ops, capacity: int, **kw):
    """One-shot convenience wrapper over ``make_replayer_rle``."""
    return make_replayer_rle(ops, capacity, **kw)()


def simulate_run_rows(patches) -> tuple:
    """Host dry-run of the kernel's row algebra over a (merged) patch
    list: returns ``(peak_rows, final_rows)``. Used for capacity planning
    — blocks fragment to ~50% after splits, so size the device capacity
    at ~2.5x the peak. Mirrors the kernel exactly: delete = flip covered
    runs + boundary splits; insert = append-merge / splice / 3-way split.
    """
    runs = []  # (order_start, char_len, live)
    next_order = 0
    peak = 0
    for p in patches:
        if p.del_len:
            rem = p.del_len
            before = 0
            i = 0
            while rem > 0 and i < len(runs):
                o, l, live = runs[i]
                lv = l if live else 0
                cs = min(max(p.pos - before, 0), lv)
                ce = min(max(p.pos + rem - before, 0), lv)
                cov = ce - cs
                if cov > 0:
                    parts = []
                    if cs > 0:
                        parts.append((o, cs, True))
                    parts.append((o + cs, cov, False))
                    if ce < l:
                        parts.append((o + ce, l - ce, True))
                    runs[i:i + 1] = parts
                    i += len(parts)
                    rem -= cov
                else:
                    i += 1
                before += lv - cov
            next_order += p.del_len
        il = len(p.ins_content)
        if il:
            st = next_order
            if p.pos == 0:
                runs.insert(0, (st, il, True))
            else:
                before = 0
                for i, (o, l, live) in enumerate(runs):
                    lv = l if live else 0
                    if before + lv >= p.pos:
                        off = p.pos - before
                        if off == l and live and st == o + l:
                            runs[i] = (o, l + il, True)
                        elif off == lv:
                            runs.insert(i + 1, (st, il, True))
                        else:
                            runs[i:i + 1] = [(o, off, True), (st, il, True),
                                             (o + off, l - off, True)]
                        break
                    before += lv
            next_order += il
        peak = max(peak, len(runs))
    return peak, len(runs)


def expand_runs(res: RleResult, doc_index: int = 0) -> np.ndarray:
    """Run rows -> per-char ±(order+1) column in document order (the
    ``FlatDoc.signed`` layout), host-side numpy."""
    res.check()
    K = res.block_k
    # Slice the lane ON DEVICE before downloading: np.asarray on the
    # whole plane would pull capacity x batch through the host link
    # (10.7 GB at kevin-5M scale) for one lane's worth of data.
    ordc = np.asarray(res.ordp[:, doc_index])
    lenc = np.asarray(res.lenp[:, doc_index])
    blk = np.asarray(res.blkord[:, doc_index])
    rows = np.asarray(res.rows[:, doc_index])
    nlog = int(np.asarray(res.meta[0, doc_index]))
    o_parts, l_parts = [], []
    for l in range(nlog):
        b, r = int(blk[l]), int(rows[l])
        o_parts.append(ordc[b * K: b * K + r])
        l_parts.append(lenc[b * K: b * K + r])
    if not o_parts:
        return np.zeros(0, np.int32)
    o = np.concatenate(o_parts).astype(np.int64)
    ln = np.concatenate(l_parts).astype(np.int64)
    assert (ln > 0).all(), "occupied run with non-positive length"
    reps = ln
    total = int(reps.sum())
    starts = np.abs(o)
    sign = np.sign(o)
    base = np.repeat(starts, reps)
    within = np.arange(total) - np.repeat(np.cumsum(reps) - reps, reps)
    return (np.repeat(sign, reps) * (base + within)).astype(np.int32)


def rle_to_flat(
    ops: OpTensors,
    res: RleResult,
    capacity: int | None = None,
    order_capacity: int | None = None,
    doc_index: int = 0,
) -> FlatDoc:
    """Kernel result -> a standard ``FlatDoc`` (one doc of the batch):
    expand runs to char rows, prefill the by-order logs, then merge the
    kernel's per-op local origins (run heads; the in-run chain is the
    compile-time prefill, `span.rs:24-28`)."""
    flat = expand_runs(res, doc_index)
    n = len(flat)
    if capacity is None:
        capacity = max(2 << max(n - 1, 5).bit_length(), n)
    doc = make_flat_doc(capacity, order_capacity)
    doc = prefill_logs(doc, ops)
    ol_log = np.array(doc.ol_log)
    or_log = np.array(doc.or_log)
    ol_np = np.asarray(res.ol)[:, doc_index]
    or_np = np.asarray(res.orr)[:, doc_index]
    if len(ol_np) < ops.num_steps:
        raise ValueError(
            f"rle_to_flat needs per-op origins for all {ops.num_steps} "
            f"steps but the result carries {len(ol_np)} — was the engine "
            "built with store_origins=False? (zip truncation would "
            "silently skip the origin merges)")
    merge_fused_origins(ol_log, or_log, ops, ol_np, or_np)

    signed_col = np.zeros(capacity, np.int32)
    signed_col[:n] = flat
    advance = int(np.asarray(ops.order_advance, dtype=np.int64).sum())
    return dataclasses.replace(
        doc,
        signed=jnp.asarray(signed_col),
        ol_log=jnp.asarray(ol_log),
        or_log=jnp.asarray(or_log),
        n=jnp.asarray(n, I32),
        next_order=jnp.asarray(advance, U32),
    )
