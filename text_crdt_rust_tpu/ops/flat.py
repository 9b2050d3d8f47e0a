"""Flat device engine: every CRDT op as fully-vectorized array work.

The device twin of ``models.oracle.ListCRDT`` — same flattened item layout,
same semantics, jit/vmap/scan-compatible. Each step is O(capacity) of
branch-free vector work with **no arbitrary gathers** (TPU gathers run near
one element/cycle and dominated the first version of this engine):

- B-tree descent `root.rs:54-88` -> one ``cumsum`` over the live mask + a
  compare-and-sum (position -> row), instead of searchsorted's binary-search
  gathers;
- the splice `mutations.rs:17-179` -> a log2(lmax) chain of static
  ``jnp.roll``s selected by the insert length's bits, plus iota arithmetic
  for the new run (orders are consecutive, `span.rs:9-13`) — the entire
  mutable state is the one ``signed`` column (see ``span_arrays``);
- order -> leaf-ptr SpaceIndex `split_list/mod.rs:440` -> ``argmax`` over an
  equality mask (order -> row);
- tombstoning `span.rs:110-119` -> sign flip of ``signed`` (local deletes
  select a live-rank window via the cumsum; remote deletes select an order
  range, which also makes the fragmented-target walk `doc.rs:311-334` a
  single mask op);
- the YATA integrate scan `doc.rs:167-234` -> a ``lax.while_loop`` from the
  origin cursor reading per-item origins/ranks through the by-order logs,
  with the scanning/scan_start backtrack carried as loop state (scalar
  reads; the loop runs zero iterations unless same-origin concurrent
  inserts exist, `doc.rs:192-194`).

Immutable per-item metadata (origins, ranks, chars) lives in by-order logs
prefilled with everything the op compiler already knows, by either of two
equivalent paths (bit-identical, pinned by ``tests/test_device_prefill.py``):

- **host prefill** (``batch.prefill_logs``): materialize the logs host-side,
  scatter with numpy, re-upload — the build-time path the replay engines
  (``ops.rle``/``ops.rle_mixed``/``parallel.mesh``) use, where the doc is
  being constructed on host anyway;
- **device-resident delta prefill** (``batch.prefill_delta`` +
  ``apply_prefill_delta``, ISSUE 14): ship only the fixed-shape padded
  (positions, values) scatter and apply it on device ahead of the step
  scan — the serve tick's path (``ServeConfig.device_prefill``), where the
  logs live on device across ticks and a full-log round trip would cost
  O(state) per O(ops) tick (and a hidden host sync under async dispatch).

A local-insert step then writes only the two origins it discovers at apply
time.

Frontier/time-DAG bookkeeping stays host-side (``models.oracle`` /
``parallel.causal``), per SURVEY §7 "keep on host".
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..common import ROOT_ORDER
from .batch import (
    KIND_LOCAL,
    KIND_REMOTE_DEL,
    KIND_REMOTE_INS,
    OpTensors,
    require_unfused,
)
from .span_arrays import FlatDoc, I32, U32

# numpy (not jnp) scalar: a module-level jnp constant would initialize the
# default backend at import time, before callers can force a platform.
import numpy as np

_ROOT = np.uint32(ROOT_ORDER)


def _order_of(signed: jax.Array) -> jax.Array:
    """Magnitude decode: row content -> order (u32; garbage on empty rows,
    callers mask with ``signed != 0``)."""
    return (jnp.abs(signed) - 1).astype(U32)


def _row_of_order(doc: FlatDoc, order: jax.Array) -> jax.Array:
    """Row index of the item with dense id ``order`` (must exist).
    The SpaceIndex lookup (`doc.rs:101-107`) as one equality-mask argmax."""
    hit = (doc.signed != 0) & (_order_of(doc.signed) == order)
    return jnp.argmax(hit).astype(I32)


def _cursor_after(doc: FlatDoc, order: jax.Array) -> jax.Array:
    """Raw cursor just after item ``order`` (`doc.rs:121-136`)."""
    return jnp.where(order == _ROOT, 0, _row_of_order(doc, order) + 1)


def _shift_right(col: jax.Array, ilen: jax.Array, lmax: int) -> jax.Array:
    """``col`` shifted right by dynamic ``ilen`` (0..lmax) along the last
    axis: a static roll per set bit — no gather. Wrapped-around garbage
    lands below ``cursor + ilen`` where callers overwrite it."""
    out = col
    for b in range(max(lmax, 1).bit_length()):
        out = jnp.where((ilen >> b) & 1 != 0,
                        jnp.roll(out, 1 << b, axis=-1), out)
    return out


def _integrate_cursor(doc: FlatDoc, my_rank: jax.Array,
                      origin_left: jax.Array, origin_right: jax.Array,
                      active: jax.Array) -> jax.Array:
    """YATA conflict scan (`doc.rs:167-234`): final insert row for a remote
    run. Runs zero iterations unless there are concurrent same-origin items
    (`doc.rs:192-194` notes they are rare)."""
    cursor0 = _cursor_after(doc, origin_left)
    left_cursor = cursor0
    cap = doc.capacity

    def read_log(log, order):
        return log[jnp.clip(order.astype(I32), 0, doc.order_capacity - 1)]

    def cond(state):
        cursor, scanning, scan_start, done = state
        return ~done & (cursor < doc.n)

    def body(state):
        cursor, scanning, scan_start, done = state
        c = jnp.clip(cursor, 0, cap - 1)
        other_order = _order_of(doc.signed[c])
        other_left = read_log(doc.ol_log, other_order)
        other_right = read_log(doc.or_log, other_order)
        other_rank = read_log(doc.rank_log, other_order)
        olc = _cursor_after(doc, other_left)
        # Break conditions, in the reference's order (`doc.rs:183-222`).
        brk = (other_order == origin_right) | (olc < left_cursor)
        eq = ~brk & (olc == left_cursor)
        gt = my_rank > other_rank          # name tiebreak (`doc.rs:206-209`)
        brk = brk | (eq & ~gt & (origin_right == other_right))
        starts_scan = eq & ~gt & (origin_right != other_right)
        new_scan_start = jnp.where(starts_scan & ~scanning, cursor, scan_start)
        new_scanning = jnp.where(
            eq, jnp.where(gt, False, jnp.where(
                origin_right == other_right, scanning, True)),
            scanning,
        )
        return (jnp.where(brk, cursor, cursor + 1), new_scanning,
                new_scan_start, brk)

    init = (cursor0, jnp.asarray(False), cursor0, ~active)
    cursor, scanning, scan_start, _ = lax.while_loop(cond, body, init)
    return jnp.where(scanning, scan_start, cursor)


def step(doc: FlatDoc, op, local_only: bool = False) -> FlatDoc:
    """Apply one compiled op (see ``batch.OpTensors``) to one document.

    ``local_only=True`` (static) compiles out the remote paths — the YATA
    while_loop and remote masks — for pure local-edit streams (the trace
    replay hot path, `benches/yjs.rs:32-49`).
    """
    cap = doc.capacity
    # Shift budget and log-write window follow the op stream's static chunk
    # width, so a compile-time lmax can never outrun the write window.
    lmax = op.chars.shape[-1]
    j = jnp.arange(cap, dtype=I32)
    is_local = op.kind == KIND_LOCAL
    is_rins = op.kind == KIND_REMOTE_INS
    is_rdel = op.kind == KIND_REMOTE_DEL
    pos = op.pos.astype(I32)
    dlen = op.del_len.astype(I32)
    ilen = jnp.where(is_rdel, 0, op.ins_len.astype(I32))

    signed = doc.signed
    live = signed > 0
    cum = jnp.cumsum(live.astype(I32))

    # ---- delete phase (tombstone sign flips, `span.rs:110-119`) -----------
    # Local: the del-span live-rank window (`mutations.rs:520-570` +
    # `doc.rs:392-433`). Remote: the order-range mask — fragmentation in doc
    # order (`doc.rs:311-334`) is free here. Already-deleted rows stay
    # deleted (idempotence; excess counts are host-side double_deletes).
    local_mask = live & (cum > pos) & (cum <= pos + dlen)
    if local_only:
        del_mask = local_mask
    else:
        orders = _order_of(signed)
        remote_mask = (signed != 0) & ((orders - op.del_target) < op.del_len)
        del_mask = jnp.where(is_local, local_mask,
                             jnp.where(is_rdel, remote_mask, False))
    signed = jnp.where(del_mask, -jnp.abs(signed), signed)

    # Post-delete live prefix counts, without a second cumsum: a local
    # delete removes the live-rank window (pos, pos+dlen], so the first-i
    # live count drops by clip(cum - pos, 0, dlen); remote deletes never
    # precede an insert in the same step (KIND_REMOTE_DEL has ins_len 0).
    cum2 = cum - jnp.where(is_local, jnp.clip(cum - pos, 0, dlen), 0)

    # ---- insert phase -----------------------------------------------------
    # Local cursor/origins from the content position (`doc.rs:435-464`):
    # origin_left is the (pos-1)-th live item post-delete; origin_right is
    # the raw successor *without skipping tombstones* (`doc.rs:452-453`).
    # Predecessor row = first index whose live prefix count equals pos
    # (compare-and-sum; no searchsorted gathers).
    oli = jnp.sum((cum2 < pos).astype(I32))
    safe_oli = jnp.clip(oli, 0, cap - 1)
    l_cursor = jnp.where(pos == 0, 0, oli + 1)
    l_origin_left = jnp.where(pos == 0, _ROOT, _order_of(signed[safe_oli]))

    if local_only:
        cursor = l_cursor
        origin_left = l_origin_left
    else:
        doc_post_del = FlatDoc(
            signed=signed, ol_log=doc.ol_log, or_log=doc.or_log,
            rank_log=doc.rank_log, chars_log=doc.chars_log,
            n=doc.n, next_order=doc.next_order,
        )
        r_cursor = _integrate_cursor(
            doc_post_del, op.rank, op.origin_left, op.origin_right, is_rins)
        cursor = jnp.where(is_rins, r_cursor, l_cursor)
        origin_left = jnp.where(is_rins, op.origin_left, l_origin_left)
    safe_cursor = jnp.clip(cursor, 0, cap - 1)
    l_origin_right = jnp.where(
        cursor < doc.n, _order_of(signed[safe_cursor]), _ROOT)
    if local_only:
        origin_right = l_origin_right
    else:
        origin_right = jnp.where(is_rins, op.origin_right, l_origin_right)

    # Splice (`mutations.rs:17-179` without the node splits): rows >= cursor
    # shift right by ilen via static rolls; the new run is iota arithmetic
    # (+1 for the ±(order+1) encoding).
    shifted = _shift_right(signed, ilen, lmax)
    in_new = (j >= cursor) & (j < cursor + ilen)
    new_signed = (op.ins_order_start.astype(I32) + (j - cursor)) + 1
    signed = jnp.where(j < cursor, signed,
                       jnp.where(in_new, new_signed, shifted))

    # Log writes for what only apply time knows: a local insert's origins
    # (`doc.rs:447-453`). The within-run chain and everything remote is
    # prefilled host-side (``batch.prefill_logs``); padding steps
    # (ilen == 0) write nothing.
    start = jnp.clip(op.ins_order_start.astype(I32), 0,
                     doc.order_capacity - lmax)
    k = jnp.arange(lmax, dtype=I32)
    write = is_local & (k < ilen)
    ol_chunk = lax.dynamic_slice(doc.ol_log, (start,), (lmax,))
    or_chunk = lax.dynamic_slice(doc.or_log, (start,), (lmax,))
    ol_log = lax.dynamic_update_slice(
        doc.ol_log,
        jnp.where(write & (k == 0), origin_left, ol_chunk), (start,))
    or_log = lax.dynamic_update_slice(
        doc.or_log, jnp.where(write, origin_right, or_chunk), (start,))

    return FlatDoc(
        signed=signed,
        ol_log=ol_log,
        or_log=or_log,
        rank_log=doc.rank_log,
        chars_log=doc.chars_log,
        n=doc.n + ilen,
        next_order=doc.next_order + op.order_advance,
    )


def check_capacity_counts(n, next_order, capacity: int,
                          order_capacity: int, ops: OpTensors) -> None:
    """The ONE capacity contract for a flat-doc op stream, against
    caller-supplied occupancy counts (``n``/``next_order`` may be the
    device doc's arrays or the serve backend's host mirrors — the
    bounds must never drift between those two callers).

    The bound is per-document: with a batched doc and per-lane streams
    (the serve batcher's shape) each lane's own occupancy pairs with
    its own stream's growth — a full lane with no traffic must not
    fail the check on behalf of an empty lane with a long stream."""
    require_unfused(ops, "the flat engine")
    need = (np.asarray(n, dtype=np.int64)
            + np.asarray(ops.ins_len, dtype=np.int64).sum(axis=0))
    assert int(np.max(need)) <= capacity, (
        f"op stream needs {int(np.max(need))} rows but capacity is "
        f"{capacity}; allocate a larger FlatDoc"
    )
    o_need = (np.asarray(next_order, dtype=np.int64)
              + np.asarray(ops.order_advance, dtype=np.int64).sum(axis=0))
    # lmax slots of headroom: the log-write window is a static lmax-wide
    # slice whose clipped start must never shift a real write.
    assert int(np.max(o_need)) <= order_capacity - ops.lmax, (
        f"op stream needs {int(np.max(o_need))}+{ops.lmax} orders but "
        f"order capacity is {order_capacity}; allocate a larger FlatDoc"
    )


def _check_capacity(doc: FlatDoc, ops: OpTensors) -> None:
    """Host-side overflow guard: the splice wraps around silently on
    device, so exceeding the static capacities would corrupt, not
    crash.  Reads the doc's device counts; the serve backend's
    device-prefill path runs the same contract against its host
    mirrors (``check_capacity_counts``)."""
    check_capacity_counts(doc.n, doc.next_order, doc.capacity,
                          doc.order_capacity, ops)


# -- device-resident prefill (ISSUE 14) ---------------------------------------
# The by-order log writes the compiler knows at compile time, applied ON
# DEVICE from the fixed-shape padded scatter ``batch.prefill_delta``
# builds — the serve tick's alternative to round-tripping the full
# [B, OCAP] logs through host numpy (``batch.prefill_logs``).  Padding
# positions are out of range (``batch.PREFILL_PAD``) and dropped by
# ``mode="drop"``; real positions are unique within one stream (orders
# are allocated uniquely), so the scatter is order-independent.  All
# three variants are module-level jits (the tcrlint TCR-R002 contract):
# the compile cache is keyed by (OCAP, bucket[, B]) only — the scatter
# program is independent of the tick's step bucket, so the serve
# steady-state compile set is |step buckets| + |scatter buckets|, not
# their product.
#
# tcrlint v2 contract (ISSUE 15): the functions below are this module's
# DEVICE-WRITE PRODUCERS — analysis/checks_mirror.py harvests them from
# this file's AST (``.at[...].set`` / ``dynamic_update_slice`` /
# ``lax.scan`` bodies, closed one call level), and any serve backend
# method that calls one or stores its result on a registered device
# attribute must pair the write with a host-mirror update (TCR-M001).
# They are also TCR-P001 dispatch sinks: a host write aliasing their
# arguments before the staged sync is a lint finding.


def _scatter_cols(ol, orr, rank, chars, ip, cv, rv, olp, olv, orp, orv):
    """Scatter one lane's seven delta rows into its four log columns."""
    chars = chars.at[ip].set(cv, mode="drop")
    rank = rank.at[ip].set(rv, mode="drop")
    ol = ol.at[olp].set(olv, mode="drop")
    orr = orr.at[orp].set(orv, mode="drop")
    return ol, orr, rank, chars


def _delta_cols(d):
    return (d.ins_pos, d.chars_val, d.rank_val, d.ol_pos, d.ol_val,
            d.or_pos, d.or_val)


@jax.jit
def _scatter_delta(doc, d):
    """Unbatched doc + unbatched delta, or batched doc + unbatched
    delta (the tiled-stream broadcast: the trailing-axis fancy index
    broadcasts over the doc axis, like ``batch._apply_scatter``)."""
    ol = doc.ol_log.at[..., d.ol_pos].set(d.ol_val, mode="drop")
    orr = doc.or_log.at[..., d.or_pos].set(d.or_val, mode="drop")
    rank = doc.rank_log.at[..., d.ins_pos].set(d.rank_val, mode="drop")
    chars = doc.chars_log.at[..., d.ins_pos].set(d.chars_val,
                                                 mode="drop")
    return dataclasses.replace(doc, ol_log=ol, or_log=orr,
                               rank_log=rank, chars_log=chars)


@jax.jit
def _scatter_delta_batch(docs, d):
    """Batched docs [B, OCAP] + batched delta [B, L]: one per-lane
    scatter under vmap."""
    ol, orr, rank, chars = jax.vmap(_scatter_cols)(
        docs.ol_log, docs.or_log, docs.rank_log, docs.chars_log,
        *_delta_cols(d))
    return dataclasses.replace(docs, ol_log=ol, or_log=orr,
                               rank_log=rank, chars_log=chars)


def apply_prefill_delta(doc: FlatDoc, delta) -> FlatDoc:
    """Apply a ``batch.PrefillDelta`` to the by-order logs on device —
    the device-resident twin of ``batch.prefill_logs`` (bit-identical
    logs, no host materialization).  Accepts every doc/delta batching
    combination ``prefill_logs`` does: unbatched/unbatched, batched
    docs + unbatched delta (tiled broadcast), batched/batched.  Pass
    ``None`` deltas through (a no-insert stream writes nothing)."""
    if delta is None:
        return doc
    doc_b = doc.ol_log.ndim == 2
    delta_b = np.asarray(delta.ins_pos).ndim == 2
    if delta_b:
        assert doc_b, "batched delta needs a batched doc"
        return _scatter_delta_batch(doc, delta)
    return _scatter_delta(doc, delta)


@partial(jax.jit, static_argnames=("local_only",))
def _apply_ops(doc: FlatDoc, ops: OpTensors, local_only: bool = False
               ) -> FlatDoc:
    def body(d, op):
        return step(d, op, local_only=local_only), None

    out, _ = lax.scan(body, doc, ops)
    return out


@partial(jax.jit, static_argnames=("local_only",))
def _apply_ops_batch(docs: FlatDoc, ops: OpTensors, local_only: bool = False
                     ) -> FlatDoc:
    vstep = jax.vmap(partial(step, local_only=local_only))

    def body(d, op):
        return vstep(d, op), None

    out, _ = lax.scan(body, docs, ops)
    return out


# -- tick trains (ISSUE 20) ---------------------------------------------------
# T ticks' stacked op tensors replayed as ONE device program: an outer
# ``lax.scan`` over the tick axis wrapping the inner per-tick scan of
# vmapped steps.  The compile cache is keyed by (T bucket, S bucket,
# B, CAP, OCAP, LMAX) — the serve scheduler pads T to a small geometric
# series (powers of two) and re-pads S to the train's max step bucket,
# so the steady-state compile set stays ADDITIVE: |S buckets| x |T
# buckets| train programs + |scatter buckets| scatter programs (the
# concatenated prefill scatter stays a SEPARATE dispatch — folding it
# in would multiply the key space by |scatter buckets|).
#
# The capacity/overflow flag is accumulated ON DEVICE (one bool across
# all T ticks and all lanes) and checked once at the train boundary —
# the host-mirror capacity check (``check_capacity_counts`` against the
# backend's pending-aware mirrors) remains the authoritative gate at
# enqueue time; the device flag is defense in depth.


@partial(jax.jit, static_argnames=("local_only",))
def _apply_train_batch(docs: FlatDoc, ops: OpTensors,
                       local_only: bool = False):
    """``ops`` leaves are train-major [T, S, B, ...]; returns
    ``(docs, overflow_flag)`` where the flag mirrors the
    ``check_capacity_counts`` bounds evaluated after every tick."""
    cap = docs.signed.shape[-1]
    ocap = docs.ol_log.shape[-1]
    lmax = ops.chars.shape[-1]
    vstep = jax.vmap(partial(step, local_only=local_only))

    def tick_body(carry, tick_ops):
        d, flag = carry

        def body(dd, op):
            # A step that is idle on EVERY lane is tick/step padding
            # (the all-zero no-op contract of ``batch.pad_ops``); a
            # scalar cond skips its whole-batch compute.  Re-padding a
            # train's ticks to a common step bucket would otherwise run
            # each short tick at the longest tick's step count — at
            # mixed-bucket shapes that inflates padded device steps
            # ~1.5-2.4x over the serial loop and erases the dispatch
            # win on wall clock.
            active = (jnp.any(op.rows_per_step > 0)
                      | jnp.any(op.ins_len > 0)
                      | jnp.any(op.del_len > 0))
            return lax.cond(active, lambda s: vstep(s, op),
                            lambda s: s, dd), None

        d, _ = lax.scan(body, d, tick_ops)
        flag = (flag | jnp.any(d.n > cap)
                | jnp.any(d.next_order > ocap - lmax))
        return (d, flag), None

    (out, flag), _ = lax.scan(tick_body, (docs, jnp.asarray(False)), ops)
    return out, flag


def apply_train(docs: FlatDoc, ops: OpTensors):
    """Apply a tick train — [T, S, B, ...] op tensors (``batch.
    stack_ticks`` of T stacked tick streams) — to batched docs in ONE
    dispatch.  The caller must have applied the train's concatenated
    prefill delta first (``batch.concat_deltas`` + ``apply_prefill_
    delta``): per-tick scatters land in disjoint fresh order ranges
    (orders are allocated uniquely and monotonically per lane), so
    hoisting them all before the scan is bit-identical to interleaving.
    Returns ``(docs, overflow_flag)``; a set flag means a tick exceeded
    the static capacities mid-train and the docs are corrupt — the
    serve backend's pending-aware host-mirror check refuses such trains
    at enqueue, so a set flag is a contract violation, not flow
    control."""
    return _apply_train_batch(docs, ops, local_only=False)


def _is_local_only(ops: OpTensors) -> bool:
    return bool(np.all(np.asarray(ops.kind) == KIND_LOCAL))


def apply_ops(doc: FlatDoc, ops: OpTensors, prefill: bool = True) -> FlatDoc:
    """Apply a compiled step stream to one document (``lax.scan``).

    The by-order logs must be prefilled for this stream before the scan
    runs, by either of the two bit-identical paths (module header):

    - ``prefill=True`` (default) runs the HOST path, ``batch.
      prefill_logs`` — what the build-time replay engines (``ops.rle``/
      ``ops.rle_mixed``/``parallel.mesh``) and one-shot callers use;
    - ``prefill=False`` + caller-managed prefill: either the logs were
      already host-prefilled for this stream (e.g. re-running it), or
      the caller applied the DEVICE path first — ``apply_prefill_delta
      (doc, batch.prefill_delta(ops))``, the serve tick's
      device-resident route (``ServeConfig.device_prefill``; see
      ``serve.batcher.FlatLaneBackend.apply``).

    Applying an un-prefilled stream gives silently wrong results (NUL
    chars, wrong tiebreak ranks), not a crash.
    """
    from .batch import prefill_logs

    _check_capacity(doc, ops)
    if prefill:
        doc = prefill_logs(doc, ops)
    return _apply_ops(doc, ops, local_only=_is_local_only(ops))


def apply_ops_batch(docs: FlatDoc, ops: OpTensors,
                    prefill: bool = True) -> FlatDoc:
    """Batched apply: ``docs`` has a leading doc axis, ``ops`` is time-major
    [S, B, ...] (see ``batch.stack_ops``/``tile_ops``). The vmap'd step is
    the north-star "one pass across thousands of docs" kernel shape."""
    from .batch import prefill_logs

    _check_capacity(docs, ops)
    if prefill:
        docs = prefill_logs(docs, ops)
    return _apply_ops_batch(docs, ops, local_only=_is_local_only(ops))
