"""Per-lane divergent RLE replay: B distinct documents, B distinct ops
per kernel step.

The blocked engines batch IDENTICAL docs in the lane dim (`_lane_scalar`
collapses lanes into one control stream), so divergent small docs — the
config-5 streaming shape — fell back to ``ops.flat``'s one-XLA-dispatch-
per-step scan (r2 verdict weak #4). This engine removes the identical-
lane assumption instead of the batching:

- every document is ONE un-blocked run column (``CAP`` run rows packed
  at the front) — config-5 docs are hundreds of runs, so the in-block
  position scan covers the whole doc and the block machinery (descent,
  splits, windows) disappears;
- every op scalar of the blocked engines (``i_r``, ``off``, splice
  shift, …) becomes a ``[1, B]`` lane VECTOR; the splice shift is ≤2
  rows regardless of text length (the RLE insert property), so per-lane
  dynamic shifts are two static ``pltpu.roll``s blended by per-lane
  masks — the trick that makes divergence free;
- a delete needs NO walk: the whole doc is in view, so one
  flip+boundary-split pass retires any span (`mutations.rs:520-570`);
- state planes are kernel INPUTS as well as outputs — chunk N+1 resumes
  from chunk N's downloaded (or never-downloaded) state, the warm start
  the blocked engines lack (r2 verdict weak #4/#5: "blocked engines only
  cold-start").

Per step the kernel applies B independent ops (one per lane), so wall
per op is ~1/B of a blocked-engine step on the same shapes. Local ops
only (KIND_LOCAL); per-lane REMOTE streams run on the unified
``ops.rle_lanes_mixed`` engine built on these same primitives.
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
    fused_width,
    fused_width_checked,
    merge_fused_origins,
    prefill_logs,
)
from .rle import fused_splice_rows
from .span_arrays import FlatDoc, I32, U32, make_flat_doc


def _vcumsum(x) -> jax.Array:
    """Inclusive cumsum along rows (axis 0) via log2 roll-adds."""
    n = x.shape[0]
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    out = x
    shift = 1
    while shift < n:
        out = out + jnp.where(row >= shift, pltpu.roll(out, shift, axis=0), 0)
        shift *= 2
    return out


def _vrow(arr, r):
    """Per-lane row extraction: ``arr[r[0, b], b]`` as a [1, B] vector."""
    idx = lax.broadcasted_iota(jnp.int32, arr.shape, 0)
    return jnp.sum(jnp.where(idx == r, arr, 0), axis=0, keepdims=True)


def _vshift(x, amt, max_amt: int = 2):
    """Rows shifted down by per-lane ``amt`` in [0, max_amt] ([1, B]):
    one static roll per bit, selected per lane (the down-shift twin of
    ``lane_blocks.vshift_up``).  ``max_amt`` defaults to the plain-
    splice bound (2: new run + split tail); fused W-row splices pass
    their static ``WMAX + 1``."""
    n = x.shape[0]
    out = x
    for bit in range(max(max_amt, 1).bit_length()):
        s = (1 << bit) % n
        if s:
            out = jnp.where((amt >> bit) & 1 != 0,
                            pltpu.roll(out, s, axis=0), out)
    return out


def _live_prefix(bo, bl):
    """(lv, cum): live char counts per run row and their inclusive
    prefix — the most expensive pass of a step (log2(CAP) roll-adds)."""
    lv = jnp.where(bo > 0, bl, 0)
    return lv, _vcumsum(lv)


def _shared_cum_gate(step_has_del, step_has_ins, s_pad: int) -> bool:
    """Hoist one live prefix per step iff it pays: sound only when no
    lane deletes AND inserts in the same step (callers check that
    separately), and worth it only when steps running BOTH branches
    (two cumsums -> one) outnumber steps running NEITHER (zero
    cumsums -> one: remote-only or padding steps)."""
    both = int((step_has_del & step_has_ins).sum())
    neither = int((~(step_has_del | step_has_ins)).sum())
    neither += s_pad - len(step_has_del)  # padded no-op steps
    return both > neither


def _rle_lanes_kernel(
    pos_ref, dlen_ref, ilen_ref, start_ref,     # [CHUNK,B] VMEM op columns
    w_ref,                                      # [CHUNK,B] rows_per_step
    ord0_ref, len0_ref, rows0_ref,              # warm-start state inputs
    ol_ref, or_ref,                             # [CHUNK,B] outputs
    ordp, lenp, rowsv, err_ref,                 # state outputs (working)
    *, CAP: int, CHUNK: int, WMAX: int = 1, SHARED_CUM: bool = False,
):
    B = ordp.shape[1]
    # Grid = (lane blocks, chunks): lanes are independent documents, so
    # wide batches tile the lane axis (a 2048-lane whole-array kernel
    # spills ~105MB of registers and fails to compile); each lane block
    # runs ALL its chunks before the next block starts, preserving the
    # chunk-sequential state contract per lane.
    i = pl.program_id(1)
    idx = lax.broadcasted_iota(jnp.int32, (CAP, B), 0)
    root_u = jnp.uint32(ROOT_ORDER)

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when(i == 0)
    def _init():
        ordp[:] = ord0_ref[:]
        lenp[:] = len0_ref[:]
        rowsv[:] = rows0_ref[:]
        err_ref[:] = jnp.zeros_like(err_ref)

    def do_delete(p, d, lv=None, cum=None):
        """Whole-doc single-pass delete, per-lane (active where d > 0).
        ``lv``/``cum`` may be the step-hoisted live prefix (see
        ``op_body``); the delete runs first, so they are always fresh."""
        active = d > 0
        rows = rowsv[:]

        @pl.when(jnp.any(active & (rows + 2 > CAP)))
        def _cap():
            err_ref[0:1, :] = jnp.where(active & (rows + 2 > CAP), 1,
                                        err_ref[0:1, :])

        bo = ordp[:]
        bl = lenp[:]
        if cum is None:
            lv, cum = _live_prefix(bo, bl)
        before = cum - lv
        rem = jnp.where(active, d, 0)
        cs = jnp.clip(p - before, 0, lv)
        ce = jnp.clip(p + rem - before, 0, lv)
        cov = ce - cs
        tot = jnp.sum(cov, axis=0, keepdims=True)

        @pl.when(jnp.any(active & (tot < rem)))
        def _bad():
            err_ref[1:2, :] = jnp.where(active & (tot < rem), 1,
                                        err_ref[1:2, :])

        full = (cov > 0) & (cov == bl)
        part = (cov > 0) & jnp.logical_not(full)
        npart = jnp.sum(part.astype(jnp.int32), axis=0, keepdims=True)
        i1 = jnp.min(jnp.where(part, idx, CAP), axis=0, keepdims=True)
        i2 = jnp.max(jnp.where(part, idx, -1), axis=0, keepdims=True)

        bo = jnp.where(full, -bo, bo)

        def apply_partial(act, i_p, bo, bl):
            o = _vrow(bo, i_p)
            ln = _vrow(bl, i_p)
            cs_i = _vrow(cs, i_p)
            ce_i = _vrow(ce, i_p)
            cov_i = ce_i - cs_i
            has_head = (cs_i > 0) & act
            has_tail = (ce_i < ln) & act
            amt = has_head.astype(jnp.int32) + has_tail.astype(jnp.int32)
            so = _vshift(bo, amt)
            sl = _vshift(bl, amt)
            no = jnp.where(idx <= i_p, bo, so)
            nl = jnp.where(idx <= i_p, bl, sl)
            p0o = jnp.where(has_head, o, -(o + cs_i))
            p0l = jnp.where(has_head, cs_i, cov_i)
            p1o = jnp.where(has_head, -(o + cs_i), o + ce_i)
            p1l = jnp.where(has_head, cov_i, ln - ce_i)
            w0 = act & (idx == i_p)
            no = jnp.where(w0, p0o, no)
            nl = jnp.where(w0, p0l, nl)
            w1 = act & (idx == i_p + 1) & (amt >= 1)
            no = jnp.where(w1, p1o, no)
            nl = jnp.where(w1, p1l, nl)
            w2 = act & (idx == i_p + 2) & (amt == 2)
            no = jnp.where(w2, o + ce_i, no)
            nl = jnp.where(w2, ln - ce_i, nl)
            return no, nl, amt

        bo, bl, a2 = apply_partial(active & (npart >= 1), i2, bo, bl)
        bo, bl, a1 = apply_partial(active & (npart == 2), i1, bo, bl)
        ordp[:] = bo
        lenp[:] = bl
        rowsv[:] = rowsv[:] + jnp.where(active, a1 + a2, 0)

    def do_insert(k, p, il, st, w, lv=None, cum=None):
        """Per-lane insert splice (active where il > 0).

        ``w`` > 1 is a FUSED backwards-burst step (``rows_per_step``):
        W run rows of stride ``L = il // w`` land in ONE shift — row j
        of the spliced window holds orders ``st + il - (j+1)*L`` (patch
        order DESCENDS in document order), the ``ops.rle``
        ``_insert_splice`` contract.  ``w == 1`` is exactly the old
        splice.  The in-place merge stays w==1-only (a burst's first
        patch merging would be un-done by its second patch's split).

        ``lv``/``cum`` may be the step-hoisted PRE-DELETE live prefix:
        valid for this branch's active lanes because the shared-cum
        mode statically guarantees no lane deletes AND inserts in the
        same step, so an insert-active lane's column was untouched by
        the delete branch.  ``bo``/``bl`` are always read FRESH —
        the transform writes whole planes and must preserve the delete
        branch's results on the OTHER lanes."""
        active = il > 0
        rows = rowsv[:]

        @pl.when(jnp.any(active & (rows + w + 1 > CAP)))
        def _cap():
            err_ref[0:1, :] = jnp.where(active & (rows + w + 1 > CAP), 1,
                                        err_ref[0:1, :])

        bo = ordp[:]
        bl = lenp[:]
        if cum is None:
            lv, cum = _live_prefix(bo, bl)
        local = jnp.where(active, p, 0)
        i_r = jnp.sum(((cum < local) & (idx < rows)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(bo, i_r)
        l_r = _vrow(bl, i_r)
        off = local - (_vrow(cum, i_r) - _vrow(lv, i_r))

        left = jnp.where(p == 0, root_u,
                         ((o_r - 1) + (off - 1)).astype(jnp.uint32))
        no, nl, amt, mrg, is_split, _lrun = fused_splice_rows(
            bo, bl, idx, p, i_r, o_r, l_r, off, il, st, w, WMAX,
            _vshift, active=active)

        nxt_in_blk = _vrow(bo, i_r + 1)
        first_o = _vrow(bo, 0)
        succ_p0 = jnp.where(rows > 0, first_o, 0)
        succ_after = jnp.where(i_r + 1 < rows, nxt_in_blk, 0)
        succ = jnp.where(p == 0, succ_p0,
                         jnp.where(is_split, o_r + off, succ_after))
        right = jnp.where(succ == 0, root_u,
                          (jnp.abs(succ) - 1).astype(jnp.uint32))
        # Lanes with amt == 0 and no merge keep bo/bl exactly (masks are
        # all False there and _vshift(amt=0) is the identity).
        ordp[:] = no
        lenp[:] = nl
        rowsv[:] = rows + amt

        ol_ref[pl.ds(k, 1), :] = jnp.where(active, left, 0)
        or_ref[pl.ds(k, 1), :] = jnp.where(active, right, 0)

    def op_body(k, _):
        p = pos_ref[pl.ds(k, 1), :]
        d = dlen_ref[pl.ds(k, 1), :]
        il = ilen_ref[pl.ds(k, 1), :]
        st = start_ref[pl.ds(k, 1), :]
        w = jnp.maximum(w_ref[pl.ds(k, 1), :], 1)  # pad rows carry 0

        if SHARED_CUM:
            # One live prefix serves BOTH branches: the builder proved
            # statically that no lane deletes AND inserts in the same
            # step (so the insert branch's active lanes see exactly
            # this pre-delete prefix) AND that both-branch steps
            # outnumber no-op steps (so the unconditional hoist pays).
            lv, cum = _live_prefix(ordp[:], lenp[:])
        else:
            lv = cum = None

        @pl.when(jnp.any(d > 0))
        def _():
            do_delete(p, d, lv, cum)

        @pl.when(jnp.any(il > 0))
        def _():
            do_insert(k, p, il, st, w, lv, cum)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)


@dataclasses.dataclass
class LanesResult:
    """Device outputs: per-lane divergent documents."""

    ordp: jax.Array     # i32[CAP, B]
    lenp: jax.Array     # i32[CAP, B]
    rows: jax.Array     # i32[1, B] occupied run rows per lane
    ol: jax.Array       # u32[S, B]
    orr: jax.Array      # u32[S, B]
    err: jax.Array      # i32[8, B]  0: capacity; 1: bad delete (per lane)
    batch: int

    def check(self) -> None:
        err = np.asarray(self.err)
        if err[0].max() != 0:
            raise RuntimeError(
                f"rle_lanes capacity exhausted on lanes "
                f"{np.nonzero(err[0])[0][:8].tolist()}; raise capacity")
        if err[1].max() != 0:
            raise RuntimeError(
                f"delete ran past the end of the document on lanes "
                f"{np.nonzero(err[1])[0][:8].tolist()}")

    def state(self):
        """(ordp, lenp, rows) — feed as ``init`` to the next chunk's
        replayer (stays on device; the warm-start chain)."""
        return self.ordp, self.lenp, self.rows


def _lane_tile(B: int) -> int:
    """Largest lane-block width <= 512 dividing B (full B when small).

    VMEM bounds the tile: the blocked kernel holds four single-buffered
    ``[capacity, T]`` int32 state planes (4 x capacity x T x 4 B) beside
    its double-buffered ``[chunk, T]`` columns within its 110 MiB, so at
    chunk 1024 T 512 fits planes of up to ~10k rows and T 128 up to ~50k
    (the editing traces' fleet: 22,656 rows at T 128, 46.4 MB).
    The whole-B alternative spills registers past the VMEM budget at
    2048 lanes (the round-3 config-5 compile failure)."""
    if B <= 512:
        return B
    for t in (512, 384, 256, 128):
        if B % t == 0:
            return t
    return B  # odd widths: no tiling (small-B test shapes)


@functools.lru_cache(maxsize=32)
def _build_call(s_pad: int, B: int, capacity: int, chunk: int,
                interpret: bool, lane_tile: int | None = None,
                shared_cum: bool = False, wmax: int = 1):
    """Shape-keyed cache: streaming chunks share one compiled kernel
    (a per-chunk pallas_call would re-trace and re-compile ~5-30s each —
    the whole point of warm starts is that chunk N+1 is cheap)."""
    T = lane_tile or _lane_tile(B)
    _require(B % T == 0, f"lane_tile {T} must divide batch {B}")
    col = lambda: pl.BlockSpec((chunk, T), lambda lb, i: (i, lb),
                               memory_space=pltpu.VMEM)
    whole = lambda shape: pl.BlockSpec(
        (shape[0], T), lambda lb, i: (0, lb), memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        partial(_rle_lanes_kernel, CAP=capacity, CHUNK=chunk,
                WMAX=wmax, SHARED_CUM=shared_cum),
        grid=(B // T, s_pad // chunk),
        in_specs=[col(), col(), col(), col(), col(),
                  whole((capacity, B)), whole((capacity, B)),
                  whole((1, B))],
        out_specs=[
            col(), col(),
            whole((capacity, B)), whole((capacity, B)),
            whole((1, B)), whole((8, B)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, B), jnp.uint32),
            jax.ShapeDtypeStruct((s_pad, B), jnp.uint32),
            jax.ShapeDtypeStruct((capacity, B), jnp.int32),
            jax.ShapeDtypeStruct((capacity, B), jnp.int32),
            jax.ShapeDtypeStruct((1, B), jnp.int32),
            jax.ShapeDtypeStruct((8, B), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )
    return jax.jit(lambda *a: call(*a))


def make_replayer_lanes(
    ops: OpTensors,
    capacity: int,
    chunk: int = 128,
    init=None,
    interpret: bool = False,
    lane_tile: int | None = None,
):
    """Build a jitted per-lane replayer for a BATCHED op stream
    (``stack_ops`` output: every column [S, B]).

    ``capacity`` counts RUN rows per document. ``init`` is an optional
    ``(ordp, lenp, rows)`` triple from a previous ``LanesResult.state()``
    — the warm start; None = empty documents.
    """
    kinds = np.asarray(ops.kind)
    _require(kinds.ndim == 2, "rle_lanes takes stacked per-doc streams "
             "([S, B] columns; see batch.stack_ops)")
    _require(bool((kinds == KIND_LOCAL).all()),
             "rle_lanes replays local streams; per-lane remote "
             "streams -> ops.rle_lanes_mixed")
    S, B = kinds.shape
    _require(capacity >= 8, "capacity must hold a few runs")
    wmax = fused_width(ops)
    _require(wmax + 1 < capacity,
             f"fused rows_per_step {wmax} cannot fit capacity "
             f"{capacity}")
    s_pad = max(((S + chunk - 1) // chunk) * chunk, chunk)

    def staged_col(get):
        a = np.asarray(get(ops), dtype=np.int32)
        return jnp.asarray(np.pad(a, ((0, s_pad - S), (0, 0))))

    staged = (staged_col(lambda o: o.pos),
              staged_col(lambda o: o.del_len),
              staged_col(lambda o: o.ins_len),
              staged_col(lambda o: o.ins_order_start),
              staged_col(lambda o: o.rows_per_step))

    if init is None:
        init = (jnp.zeros((capacity, B), jnp.int32),
                jnp.zeros((capacity, B), jnp.int32),
                jnp.zeros((1, B), jnp.int32))
    else:
        init = _grow_planes(init, capacity, B)

    # One live prefix can serve both branches of a step iff NO lane
    # deletes AND inserts in the same step (a compiled replace patch),
    # and the hoist only pays on streams where mixed-kind steps
    # dominate (see _shared_cum_gate).
    dn = np.asarray(ops.del_len)
    iln = np.asarray(ops.ins_len)
    shared_cum = (not bool(np.any((dn > 0) & (iln > 0)))
                  and _shared_cum_gate((dn > 0).any(axis=1),
                                       (iln > 0).any(axis=1), s_pad))
    jitted = _build_call(s_pad, B, capacity, chunk, interpret, lane_tile,
                         shared_cum, wmax)

    def run(state=None) -> LanesResult:
        ini = init if state is None else _grow_planes(state, capacity, B)
        ol, orr, ordp, lenp, rows, err = jitted(*staged, *ini)
        return LanesResult(ordp=ordp, lenp=lenp, rows=rows,
                           ol=ol[:S], orr=orr[:S], err=err, batch=B)

    return run


def _grow_planes(state, capacity: int, B: int):
    """Zero-pad a prior chunk's (ordp, lenp, rows) up to this chunk's
    row capacity (run rows pack at the front, so padding is free) —
    streaming chunks may GROW capacity as documents accumulate rows
    instead of paying the final capacity from chunk 0."""
    o0, l0, r0 = state
    o0 = jnp.asarray(o0, jnp.int32)
    l0 = jnp.asarray(l0, jnp.int32)
    _require(o0.shape[0] <= capacity and o0.shape[1] == B,
             f"init state shape {o0.shape} incompatible with "
             f"({capacity}, {B})")
    if o0.shape[0] < capacity:
        pad = jnp.zeros((capacity - o0.shape[0], B), jnp.int32)
        o0 = jnp.concatenate([o0, pad], axis=0)
        l0 = jnp.concatenate([l0, pad], axis=0)
    return (o0, l0, jnp.asarray(r0, jnp.int32).reshape(1, B))


def replay_lanes(ops: OpTensors, capacity: int, **kw) -> LanesResult:
    """One-shot convenience wrapper over ``make_replayer_lanes``."""
    return make_replayer_lanes(ops, capacity, **kw)()


# ---------------------------------------------------------------------------
# BLOCKED per-lane engine: ops.rle's K-row block structure carried into
# the divergent-lanes world (ISSUE 2 tentpole).  Runs live in K-row
# physical blocks; per-lane logical tables (blkord/rws/liv + the
# incrementally-maintained inclusive prefix cumliv) order them; a step
# descends over NB block sums and splices ONE gathered K-row block —
# O(NB + K) touched rows instead of log2(CAP) rolls over [CAP, B].
# Full blocks SPLIT into the logical order table (no global rebalance).
# Bit-identical to the un-blocked kernel above: block splits move rows,
# never runs, so the logical run sequence (and every emitted origin) is
# the same at every step.
# ---------------------------------------------------------------------------


def _lanes_blocked_kernel(
    pos_ref, dlen_ref, ilen_ref, start_ref,     # [CHUNK, B] VMEM op columns
    w_ref,                                      # [CHUNK, B] rows_per_step
    ord0_ref, len0_ref, nlog0_ref,              # warm-start state inputs
    blk0_ref, rws0_ref, liv0_ref,
    ol_ref, or_ref,                             # [CHUNK, B] outputs
    ordp, lenp, nlogv, blkord, rws, liv,        # state outputs (working)
    err_ref, stats_ref,                         # [8, B] flags; walk counts
    cumliv,                                     # [NBT, B] scratch prefix
    *, K: int, NB: int, NBT: int, CHUNK: int, WMAX: int = 1,
):
    """One lane tile's stream over its resident blocked state.

    Invariant: every physical block a lane's ``blkord[0:nlog]`` names is
    below its ``nlog``.  Blocks are allocated in order (a split's fresh
    block is ``nlog``) and never freed, so fresh lanes, identity packs
    (``lane_blocks.pack_lane_blocks``) and zero-padded growth
    (``_grow_blocked_state``) keep it.  Hence no lane of the tile selects
    a block at or above ``max(nlog)``, and every per-lane block walk is
    bounded by ``min(max(nlog) + 1, NB)``, read from ``nlogv`` after any
    split of the step (the ``+ 1`` is the fresh block a split writes
    before ``nlog`` grows).  ``stats_ref`` rows 0 and 1 count the blocks
    the walks visit and the blocks they would visit at ``NB``.
    """
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
    root_u = jnp.uint32(ROOT_ORDER)

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when(i == 0)
    def _init():
        ordp[:] = ord0_ref[:]
        lenp[:] = len0_ref[:]
        # Fresh lanes hold one empty block in logical slot 0.
        nlogv[:] = jnp.maximum(nlog0_ref[:], 1)
        blkord[:] = blk0_ref[:]
        rws[:] = rws0_ref[:]
        liv[:] = liv0_ref[:]
        cumliv[:] = _vcumsum(liv0_ref[:])
        err_ref[:] = jnp.zeros_like(err_ref)
        stats_ref[:] = jnp.zeros_like(stats_ref)

    def walk_bound(walks: int):
        """The tile's block-walk bound for the next ``walks`` walks (no
        split between them), counted in ``stats_ref``."""
        hi = jnp.minimum(jnp.max(nlogv[:]) + 1, NB)
        stats_ref[0:1, :] = stats_ref[0:1, :] + walks * hi
        stats_ref[1:2, :] = stats_ref[1:2, :] + walks * NB
        return hi

    def trow(tbl, l):
        """Per-lane slot read: ``tbl[l[0, b], b]`` as [1, B]."""
        return jnp.sum(jnp.where(tidx == l, tbl[:], 0), axis=0,
                       keepdims=True)

    def slot_of_live_rank(rank1):
        """Smallest logical slot whose cumulative live count reaches
        ``rank1``, per lane (the `root.rs:54-88` descent over block
        sums; slots >= nlog hold stale prefixes, masked out)."""
        nl = nlogv[:]
        hit = (cumliv[:] < rank1) & (tidx < nl)
        return jnp.minimum(
            jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True), nl - 1)

    def live_before(l):
        return trow(cumliv, l) - trow(liv, l)

    def split(act, l):
        """Per-lane leaf split (`mutations.rs:623-669`): move the top
        half of slot ``l``'s rows to a fresh physical block spliced
        into the logical order at ``l+1``.  Lanes at table capacity
        raise the error flag and skip (a proceeding split would
        overwrite a live block — the ops.rle advisor-r3 rule)."""
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
            nbv = nlogv[:]  # per-lane fresh physical block id
            hi = walk_bound(4)
            ws_o = gather_block(ordp, b, K, NB, hi)
            ws_l = gather_block(lenp, b, K, NB, hi)
            liv_hi = jnp.sum(
                jnp.where((kdx >= keep) & (kdx < r) & (ws_o > 0), ws_l,
                          0), axis=0, keepdims=True)
            up_o = vshift_up(ws_o, keep, K)
            up_l = vshift_up(ws_l, keep, K)
            scatter_block2(
                ordp, b, jnp.where(kdx < keep, ws_o, 0),
                nbv, jnp.where(kdx < mv, up_o, 0), do, K, NB, hi)
            scatter_block2(
                lenp, b, jnp.where(kdx < keep, ws_l, 0),
                nbv, jnp.where(kdx < mv, up_l, 0), do, K, NB, hi)
            # Logical tables: slots > l shift one down; cumliv shifts
            # with them (slot l+1 inherits old c_l — its correct
            # inclusive prefix after the split), slot l loses the
            # moved-out top half.
            for tbl in (blkord, rws, liv, cumliv):
                sh = pltpu.roll(tbl[:], 1, axis=0)
                tbl[:] = jnp.where(do & (tidx > l), sh, tbl[:])
            w_l = do & (tidx == l)
            w_l1 = do & (tidx == l + 1)
            rws[:] = jnp.where(w_l, keep, jnp.where(w_l1, mv, rws[:]))
            liv[:] = jnp.where(w_l, liv[:] - liv_hi,
                               jnp.where(w_l1, liv_hi, liv[:]))
            cumliv[:] = jnp.where(w_l, cumliv[:] - liv_hi, cumliv[:])
            blkord[:] = jnp.where(w_l1, nbv, blkord[:])
            nlogv[:] = nlogv[:] + do.astype(jnp.int32)

    def find_insert_slot(p):
        l = jnp.where(p == 0, 0, slot_of_live_rank(p))
        return l, trow(rws, l)

    def do_insert(k, act, p, il, st, w):
        """Per-lane blocked insert: descend, gather ONE block, splice
        <= w+2 rows, scatter back (`mutations.rs:17-179`).  ``w`` > 1
        is a FUSED backwards-burst step landing W stride-L rows in one
        shift (the ``ops.rle`` ``_insert_splice`` contract; WMAX <=
        K//2 - 1 so the one leaf split below always makes room)."""
        l, r0 = find_insert_slot(p)
        need = act & (r0 + w + 1 > K)

        @pl.when(jnp.any(need))
        def _():
            split(need, l)

        # Re-descend only when a split actually moved slots (pure table
        # reads, so the cond branch is Mosaic-safe).
        l, r0 = lax.cond(jnp.any(need),
                         lambda: find_insert_slot(p), lambda: (l, r0))
        b = trow(blkord, l)
        local = jnp.where(act, p - live_before(l), 0)
        hi = walk_bound(6)
        ws_o = gather_block(ordp, b, K, NB, hi)
        ws_l = gather_block(lenp, b, K, NB, hi)
        lv = jnp.where(ws_o > 0, ws_l, 0)
        cum = _vcumsum(lv)
        i_r = jnp.sum(((cum < local) & (kdx < r0)).astype(jnp.int32),
                      axis=0, keepdims=True)
        o_r = _vrow(ws_o, i_r)
        l_r = _vrow(ws_l, i_r)
        off = local - (_vrow(cum, i_r) - _vrow(lv, i_r))

        left = jnp.where(p == 0, root_u,
                         ((o_r - 1) + (off - 1)).astype(jnp.uint32))
        no, nl, amt, mrg, is_split, _lrun = fused_splice_rows(
            ws_o, ws_l, kdx, p, i_r, o_r, l_r, off, il, st, w, WMAX,
            _vshift, active=act)

        # Raw successor (`doc.rs:452`): next row of this block, else the
        # head row of the NEXT logical slot's block.
        nxt_in_blk = _vrow(ws_o, i_r + 1)
        b2 = trow(blkord, jnp.minimum(l + 1, NBT - 1))
        nxt_slot_o = gather_head(ordp, b2, K, NB, hi)
        first_o = gather_head(ordp, trow(blkord, 0), K, NB, hi)
        succ_p0 = jnp.where(trow(rws, 0) > 0, first_o, 0)
        succ_after = jnp.where(i_r + 1 < r0, nxt_in_blk,
                               jnp.where(l + 1 < nlogv[:], nxt_slot_o, 0))
        succ = jnp.where(p == 0, succ_p0,
                         jnp.where(is_split, o_r + off, succ_after))
        right = jnp.where(succ == 0, root_u,
                          (jnp.abs(succ) - 1).astype(jnp.uint32))
        scatter_block(ordp, b, no, act, K, NB, hi)
        scatter_block(lenp, b, nl, act, K, NB, hi)
        w_l = act & (tidx == l)
        rws[:] = jnp.where(w_l, rws[:] + amt, rws[:])
        liv[:] = jnp.where(w_l, liv[:] + il, liv[:])
        cumliv[:] = jnp.where(act & (tidx >= l), cumliv[:] + il,
                              cumliv[:])

        ol_ref[pl.ds(k, 1), :] = jnp.where(act, left, 0)
        or_ref[pl.ds(k, 1), :] = jnp.where(act, right, 0)

    def do_delete(act, p, d):
        """Per-lane blocked delete: per iteration each active lane
        clears its target block's covered span (flip full covers, split
        the <= 2 boundary runs); lanes advance block-to-block through
        the incrementally updated prefix (`mutations.rs:520-570`)."""

        def body(carry):
            rem, iters = carry
            a = act & (rem > 0)
            l = slot_of_live_rank(p + 1)
            need = a & (trow(rws, l) + 2 > K)

            @pl.when(jnp.any(need))
            def _():
                split(need, l)

            l = lax.cond(jnp.any(need),
                         lambda: slot_of_live_rank(p + 1), lambda: l)
            b = trow(blkord, l)
            base = live_before(l)
            hi = walk_bound(4)
            ws_o = gather_block(ordp, b, K, NB, hi)
            ws_l = gather_block(lenp, b, K, NB, hi)
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
            scatter_block(ordp, b, ws_o, a, K, NB, hi)
            scatter_block(lenp, b, ws_l, a, K, NB, hi)
            w_l = a & (tidx == l)
            rws[:] = jnp.where(w_l, rws[:] + a1 + a2, rws[:])
            liv[:] = jnp.where(w_l, liv[:] - tot, liv[:])
            cumliv[:] = jnp.where(a & (tidx >= l), cumliv[:] - tot,
                                  cumliv[:])
            return rem - jnp.where(a, tot, 0), iters + 1

        # Each iteration clears one block's covered span per lane;
        # > 2*NBT iterations without draining means some lane's delete
        # ran off its document.
        rem, _ = lax.while_loop(
            lambda c: jnp.any(act & (c[0] > 0)) & (c[1] <= 2 * NBT),
            body, (jnp.where(act, d, 0), 0))

        @pl.when(jnp.any(act & (rem > 0)))
        def _bad():
            err_ref[1:2, :] = jnp.where(act & (rem > 0), 1,
                                        err_ref[1:2, :])

    def op_body(k, _):
        p = pos_ref[pl.ds(k, 1), :]
        d = dlen_ref[pl.ds(k, 1), :]
        il = ilen_ref[pl.ds(k, 1), :]
        st = start_ref[pl.ds(k, 1), :]
        w = jnp.maximum(w_ref[pl.ds(k, 1), :], 1)  # pad rows carry 0

        @pl.when(jnp.any(d > 0))
        def _():
            do_delete(d > 0, p, d)

        @pl.when(jnp.any(il > 0))
        def _():
            do_insert(k, il > 0, p, il, st, w)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)


@dataclasses.dataclass
class BlockedLanesResult:
    """Device outputs of the BLOCKED per-lane engine: per-lane K-row
    physical blocks + logical block tables."""

    ordp: jax.Array     # i32[CAP, B]  physical K-row blocks
    lenp: jax.Array     # i32[CAP, B]
    nlog: jax.Array     # i32[1, B]    logical blocks in use per lane
    blkord: jax.Array   # i32[NBT, B]  logical slot -> physical block
    rws: jax.Array      # i32[NBT, B]  occupied rows per logical slot
    liv: jax.Array      # i32[NBT, B]  live chars per logical slot
    ol: jax.Array       # u32[S, B]
    orr: jax.Array      # u32[S, B]
    err: jax.Array      # i32[8, B]  0: out of blocks; 1: bad delete
    stats: jax.Array    # i32[8, B]  0: blocks walked; 1: ... at NB
    batch: int
    block_k: int

    def check(self) -> None:
        err = np.asarray(self.err)
        if err[0].max() != 0:
            raise RuntimeError(
                f"blocked rle_lanes out of blocks on lanes "
                f"{np.nonzero(err[0])[0][:8].tolist()}; raise capacity")
        if err[1].max() != 0:
            raise RuntimeError(
                f"delete ran past the end of the document on lanes "
                f"{np.nonzero(err[1])[0][:8].tolist()}")

    def state(self):
        """(ordp, lenp, nlog, blkord, rws, liv) — the next chunk's
        ``init`` (stays on device; the warm-start chain)."""
        return (self.ordp, self.lenp, self.nlog, self.blkord, self.rws,
                self.liv)

    @property
    def rows(self):
        """Total occupied rows per lane (compat with ``LanesResult``)."""
        return jnp.sum(self.rws, axis=0, keepdims=True)

    @property
    def block_walk_share(self) -> float:
        """Blocks the pass's per-lane block walks visited ÷ the blocks
        the same walks visit unbounded (all NB), over every lane tile:
        1.0 where each tile's fullest lane held every block.  Each tile
        writes its counts to all of its lanes, and tiles are equal, so
        the lane sums keep the ratio."""
        stats = np.asarray(self.stats, np.int64)
        return float(stats[0].sum() / max(int(stats[1].sum()), 1))


@functools.lru_cache(maxsize=32)
def _build_blocked_call(s_pad: int, B: int, capacity: int, block_k: int,
                        chunk: int, interpret: bool,
                        lane_tile: int | None = None, wmax: int = 1):
    """Shape-keyed cache for the blocked kernel (streaming chunks of one
    geometry share one compiled kernel).  The call is named
    ``rle_lanes_replay``, in its jit and its Pallas kernel alike, so a
    profile names it."""
    K = block_k
    NB = capacity // K
    NBT = max(8, NB)
    T = lane_tile or _lane_tile(B)
    _require(B % T == 0, f"lane_tile {T} must divide batch {B}")
    col = lambda: pl.BlockSpec((chunk, T), lambda lb, i: (i, lb),
                               memory_space=pltpu.VMEM)
    # One buffer for the state blocks: they move only between lane
    # tiles, so a second buffer would only double the resident planes.
    whole = lambda rows: pl.BlockSpec(
        (rows, T), lambda lb, i: (0, lb), pipeline_mode=pl.Buffered(1),
        memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        partial(_lanes_blocked_kernel, K=K, NB=NB, NBT=NBT, CHUNK=chunk,
                WMAX=wmax),
        grid=(B // T, s_pad // chunk),
        in_specs=[col(), col(), col(), col(), col(),
                  whole(capacity), whole(capacity), whole(1),
                  whole(NBT), whole(NBT), whole(NBT)],
        out_specs=[
            col(), col(),
            whole(capacity), whole(capacity), whole(1),
            whole(NBT), whole(NBT), whole(NBT),
            whole(8), whole(8),
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
            jax.ShapeDtypeStruct((8, B), jnp.int32),
            jax.ShapeDtypeStruct((8, B), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((NBT, T), jnp.int32),    # cumliv
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
        name="rle_lanes_replay",
    )

    def rle_lanes_replay(*args):
        return call(*args)

    return jax.jit(rle_lanes_replay)


@dataclasses.dataclass(frozen=True)
class LaneSteps:
    """A pass's lane-steps: ``real`` is the steps the lanes' own streams
    hold (the sum of their ``num_steps`` before ``stack_ops`` padded
    them to the longest), ``stepped`` the steps the kernel runs
    (``s_pad * B``).  The difference is padding: no-op steps of the
    lanes whose histories are shorter."""

    real: int
    stepped: int


def make_replayer_lanes_blocked(
    ops: OpTensors,
    capacity: int,
    block_k: int = 64,
    chunk: int = 128,
    init=None,
    interpret: bool = False,
    lane_tile: int | None = None,
):
    """Build a jitted BLOCKED per-lane replayer (``stack_ops`` streams,
    local ops only) — bit-identical final state and per-op origins to
    ``make_replayer_lanes``, at O(NB + K) touched rows per step.

    ``capacity`` counts run rows per lane and must be a ``block_k``
    multiple; growing per-chunk capacities grow NB at fixed K.  ``init``
    is a prior ``BlockedLanesResult.state()`` 6-tuple; one given as
    numpy (a host-packed state) must keep the kernel's block invariant,
    every block that ``blkord[0:nlog]`` names below ``nlog``.

    VMEM bounds ``capacity * T`` (T the lane tile, ``_lane_tile``): the
    kernel keeps four ``[capacity, T]`` int32 state planes resident for
    a lane tile's whole stream, single-buffered (the ``ord0``/``len0``
    inputs and the ``ordp``/``lenp`` outputs: 4 x 1 x capacity x T x
    4 B), beside the double-buffered ``[chunk, T]`` op columns and
    origin outputs, within the kernel's 110 MiB.  The block gathers and
    scatters loop over the NB blocks, so no temporary is plane-sized and
    the code does not grow with NB.  Compiled for a described v5e at
    T 128, block_k 128, chunk 1024: capacity 22,656 (177 blocks, the
    editing traces' fleet) holds 46.4 MB of planes, and 51,200 rows
    still fit.

    The replayer's ``lane_steps`` (a ``LaneSteps``) counts the pass's
    real and stepped lane-steps; a result's ``block_walk_share`` the
    share of all NB blocks its block walks visited.  The first call is
    the ``rle.build`` span, every later one ``rle.dispatch``; staging is
    ``rle.stage``.
    """
    kinds = np.asarray(ops.kind)
    _require(kinds.ndim == 2, "rle_lanes takes stacked per-doc streams "
             "([S, B] columns; see batch.stack_ops)")
    _require(bool((kinds == KIND_LOCAL).all()),
             "rle_lanes replays local streams; per-lane remote "
             "streams -> ops.rle_lanes_mixed")
    S, B = kinds.shape
    _require(block_k >= 8, "block_k must hold a few runs")
    _require(capacity % block_k == 0,
             f"capacity ({capacity}) must be a multiple of block_k "
             f"({block_k})")
    wmax = fused_width_checked([ops], block_k)
    s_pad = max(((S + chunk - 1) // chunk) * chunk, chunk)

    def staged_col(get):
        a = np.asarray(get(ops), dtype=np.int32)
        return jnp.asarray(np.pad(a, ((0, s_pad - S), (0, 0))))

    NBT = max(8, capacity // block_k)
    with span("rle.stage"):
        staged = (staged_col(lambda o: o.pos),
                  staged_col(lambda o: o.del_len),
                  staged_col(lambda o: o.ins_len),
                  staged_col(lambda o: o.ins_order_start),
                  staged_col(lambda o: o.rows_per_step))
        if init is None:
            init = _empty_blocked_state(capacity, NBT, B)
        else:
            init = _grow_blocked_state(init, capacity, block_k, B)
    jitted = _build_blocked_call(s_pad, B, capacity, block_k, chunk,
                                 interpret, lane_tile, wmax)
    built = False

    def run(state=None) -> BlockedLanesResult:
        # As ``ops.rle``'s replayer: the first call builds the program,
        # every later one enqueues it (wall and thread CPU time).
        nonlocal built
        with span("rle.dispatch" if built else "rle.build", cpu=True):
            ini = init if state is None else _grow_blocked_state(
                state, capacity, block_k, B)
            ol, orr, ordp, lenp, nlog, blk, rws, liv, err, stats = jitted(
                *staged, *ini)
            res = BlockedLanesResult(
                ordp=ordp, lenp=lenp, nlog=nlog, blkord=blk, rws=rws,
                liv=liv, ol=ol[:S], orr=orr[:S], err=err, stats=stats,
                batch=B, block_k=block_k)
        built = True
        return res

    # Padding rows carry rows_per_step 0; every compiled step carries 1+.
    run.lane_steps = LaneSteps(
        real=int((np.asarray(ops.rows_per_step) > 0).sum()),
        stepped=s_pad * B)
    return run


def _empty_blocked_state(capacity: int, NBT: int, B: int):
    z = lambda r: jnp.zeros((r, B), jnp.int32)
    return (z(capacity), z(capacity), z(1), z(NBT), z(NBT), z(NBT))


def _grow_blocked_state(state, capacity: int, block_k: int, B: int):
    """Pad a prior chunk's blocked 6-tuple up to this chunk's capacity:
    fresh physical blocks append at the end (allocation order == block
    id, so zero-padding is free), logical tables zero-pad past nlog."""
    o0, l0, nlog, blk, rws, liv = state
    o0 = jnp.asarray(o0, jnp.int32)
    l0 = jnp.asarray(l0, jnp.int32)
    _require(o0.shape[0] <= capacity and o0.shape[1] == B,
             f"init state shape {o0.shape} incompatible with "
             f"({capacity}, {B})")
    _require(o0.shape[0] % block_k == 0,
             f"prior capacity {o0.shape[0]} is not a block_k "
             f"({block_k}) multiple — geometry K must not change "
             "between chunks")
    NBT = max(8, capacity // block_k)
    if isinstance(nlog, np.ndarray) and isinstance(blk, np.ndarray):
        _require_blocks_below_nlog(nlog, blk, B)

    def padp(a):
        a = jnp.asarray(a, jnp.int32)
        if a.shape[0] < capacity:
            a = jnp.concatenate(
                [a, jnp.zeros((capacity - a.shape[0], B), jnp.int32)],
                axis=0)
        return a

    def padt(a):
        a = jnp.asarray(a, jnp.int32)
        _require(a.shape[0] <= NBT,
                 f"table rows {a.shape[0]} exceed {NBT}")
        if a.shape[0] < NBT:
            a = jnp.concatenate(
                [a, jnp.zeros((NBT - a.shape[0], B), jnp.int32)], axis=0)
        return a

    return (padp(o0), padp(l0),
            jnp.asarray(nlog, jnp.int32).reshape(1, B),
            padt(blk), padt(rws), padt(liv))


def _require_blocks_below_nlog(nlog, blk, B: int) -> None:
    """The blocked kernel's invariant on a host-given state: each lane's
    ``blkord[0:nlog]`` names blocks in ``[0, nlog)`` (``nlog`` 0 counts
    as the one empty block of a fresh lane), so its block walks, bounded
    by the tile's largest ``nlog``, miss no block in use."""
    n = np.maximum(np.asarray(nlog, np.int64).reshape(B), 1)
    blk = np.asarray(blk, np.int64)
    named = np.arange(blk.shape[0])[:, None] < n[None, :]
    bad = named & ((blk < 0) | (blk >= n[None, :]))
    _require(not bad.any(),
             f"init blkord names blocks at or above nlog on lanes "
             f"{np.nonzero(bad.any(axis=0))[0][:8].tolist()}")


def expand_lane_blocked(res, doc_index: int) -> np.ndarray:
    """One lane of a blocked result -> per-char ±(order+1) column in doc
    order (walk the logical block table)."""
    res.check()
    K = res.block_k
    ordc = np.asarray(res.ordp[:, doc_index])
    lenc = np.asarray(res.lenp[:, doc_index])
    blk = np.asarray(res.blkord[:, doc_index])
    rows = np.asarray(res.rws[:, doc_index])
    nlog = int(np.asarray(res.nlog[0, doc_index]))
    o_parts, l_parts = [], []
    for l in range(nlog):
        b, r = int(blk[l]), int(rows[l])
        o_parts.append(ordc[b * K: b * K + r])
        l_parts.append(lenc[b * K: b * K + r])
    if not o_parts:
        return np.zeros(0, np.int32)
    o = np.concatenate(o_parts).astype(np.int64)
    ln = np.concatenate(l_parts).astype(np.int64)
    if len(o) == 0:
        return np.zeros(0, np.int32)
    assert (ln > 0).all(), "occupied run with non-positive length"
    total = int(ln.sum())
    base = np.repeat(np.abs(o), ln)
    within = np.arange(total) - np.repeat(np.cumsum(ln) - ln, ln)
    return (np.repeat(np.sign(o), ln) * (base + within)).astype(np.int32)


def expand_lane(res, doc_index: int) -> np.ndarray:
    """One lane's run rows -> per-char ±(order+1) column in doc order
    (dispatches on the blocked-layout results too)."""
    if hasattr(res, "blkord"):
        return expand_lane_blocked(res, doc_index)
    res.check()
    r = int(np.asarray(res.rows)[0, doc_index])
    o = np.asarray(res.ordp)[:r, doc_index].astype(np.int64)
    ln = np.asarray(res.lenp)[:r, doc_index].astype(np.int64)
    if r == 0:
        return np.zeros(0, np.int32)
    assert (ln > 0).all(), "occupied run with non-positive length"
    total = int(ln.sum())
    base = np.repeat(np.abs(o), ln)
    within = np.arange(total) - np.repeat(np.cumsum(ln) - ln, ln)
    return (np.repeat(np.sign(o), ln) * (base + within)).astype(np.int32)


def lanes_to_flat(
    ops: OpTensors,
    res: LanesResult,
    doc_index: int,
    capacity: int | None = None,
    order_capacity: int | None = None,
) -> FlatDoc:
    """One lane -> a standard ``FlatDoc`` (prefill + per-op origins)."""
    flat = expand_lane(res, doc_index)
    n = len(flat)
    if capacity is None:
        capacity = max(2 << max(n - 1, 5).bit_length(), n)
    per_doc = jax.tree.map(lambda a: np.asarray(a)[:, doc_index], ops)
    doc = make_flat_doc(capacity, order_capacity)
    doc = prefill_logs(doc, per_doc)
    ol_log = np.array(doc.ol_log)
    or_log = np.array(doc.or_log)
    ol_np = np.asarray(res.ol)[:, doc_index]
    or_np = np.asarray(res.orr)[:, doc_index]
    merge_fused_origins(ol_log, or_log, per_doc, ol_np, or_np)

    signed_col = np.zeros(capacity, np.int32)
    signed_col[:n] = flat
    advance = int(np.asarray(per_doc.order_advance, dtype=np.int64).sum())
    return dataclasses.replace(
        doc,
        signed=jnp.asarray(signed_col),
        ol_log=jnp.asarray(ol_log),
        or_log=jnp.asarray(or_log),
        n=jnp.asarray(n, I32),
        next_order=jnp.asarray(advance, U32),
    )
