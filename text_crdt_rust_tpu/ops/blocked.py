"""Blocked Pallas replay engine: the whole edit stream in ONE kernel.

The flat engine (``ops.flat``) pays two costs per op: it touches the full
capacity and — dominating in practice — it dispatches ~20 XLA kernels per
scanned step (~100us of fixed overhead on the bench chip). This engine is
the TPU-native answer to the reference's B-tree (`src/range_tree/`): one
``pallas_call`` applies the *entire* compiled local-edit stream, holding the
document in VMEM as fixed-size blocks:

- state is ``signed`` rows (same ±(order+1) encoding as ``span_arrays``)
  laid out as ``NB`` blocks of ``K`` rows, occupied rows packed at each
  block's front — the VMEM analog of B-tree leaves (`mod.rs:36-39`);
- per-block live counts replace the internal nodes' subtree sums
  (`mod.rs:85-93`): position→block is a cumsum+compare over ``NB`` scalars,
  position→row a cumsum over one ``K``-row block — O(NB + K) per op
  instead of O(capacity);
- inserts splice one block with static power-of-two rolls (the
  ``ops.flat`` shift trick) — block b's packed slack absorbs them, the
  analog of the reference's leaf-append fast path (`mutations.rs:57-109`);
- deletes flip signs inside a 2-block window walked across the span
  (`mutations.rs:520-570`);
- a block overflow triggers a global *rebalance* — compact all packed rows
  and redeal them evenly — replacing the B-tree's node-split bubbling
  (`mutations.rs:623-808`) with an O(capacity) pass that amortizes to
  nothing (a block absorbs K-fill inserts between rebalances);
- documents batch in the LANE dimension: every vector op processes
  ``batch`` docs at once, all replaying one shared op stream (the
  `BASELINE.json` config-2 shape: N identical docs, `benches/yjs.rs:41-48`
  run batched). Per-doc divergent streams stay on ``ops.flat``.

Origins a local insert discovers (`doc.rs:447-453`) are emitted per step
and merged into the by-order logs host-side, so the kernel's result
converts to a full ``span_arrays.FlatDoc`` — bit-identical to the flat
engine's.
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
from .batch import KIND_LOCAL, OpTensors, prefill_logs, require_unfused
from .flat import _order_of
from .span_arrays import FlatDoc, I32, U32, make_flat_doc


def _require(cond: bool, msg: str) -> None:
    """Config/capacity precheck that must fire even under ``python -O``
    (a violated precondition corrupts device state silently, no crash)."""
    if not cond:
        raise ValueError(msg)


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


class _BlockOps:
    """The shared VMEM block-grid op set, closed over a kernel's scratch
    refs. Both ``blocked`` and ``blocked_mixed`` build their kernels on
    these — one implementation of the descent, the rebalance (node-split
    analog) and the windowed local delete, so the engines cannot drift.
    """

    def __init__(self, sig, rws, liv, tmp, err_ref, *, K, NB, LMAX):
        self.sig, self.rws, self.liv, self.tmp = sig, rws, liv, tmp
        self.err_ref = err_ref
        self.K, self.NB, self.LMAX = K, NB, LMAX
        self.B = sig.shape[1]
        self.idx_nb = lax.broadcasted_iota(jnp.int32, rws.shape, 0)
        self.idx_k = lax.broadcasted_iota(jnp.int32, (K, self.B), 0)
        self.idx_2k = lax.broadcasted_iota(jnp.int32, (2 * K, self.B), 0)

    def live_before_block(self, b):
        return _lane_scalar(jnp.where(self.idx_nb < b, self.liv[:], 0))

    def raw_before_block(self, b):
        return _lane_scalar(jnp.where(self.idx_nb < b, self.rws[:], 0))

    def block_of_rank(self, rank1):
        """Smallest block whose cumulative live count reaches ``rank1``
        (the B-tree descent `root.rs:54-88` over block sums)."""
        cumlive = _cumsum_rows(
            jnp.where(self.idx_nb < self.NB, self.liv[:], 0))
        hits = (cumlive < rank1) & (self.idx_nb < self.NB)
        return jnp.max(jnp.sum(hits.astype(jnp.int32), axis=0))

    def block_rows(self, b):
        return _lane_scalar(jnp.where(self.idx_nb == b, self.rws[:], 0))

    def total_raw(self):
        return _lane_scalar(jnp.where(self.idx_nb < self.NB, self.rws[:], 0))

    def rebalance(self):
        """Compact all packed rows, redeal evenly (`mutations.rs:623-808`
        analog). O(cap); triggered only on block overflow."""
        K, NB, B = self.K, self.NB, self.B
        sig, rws, liv, tmp = self.sig, self.rws, self.liv, self.tmp
        total = self.total_raw()
        fill = (total + NB - 1) // NB
        err_ref = self.err_ref

        @pl.when(fill > K - self.LMAX)
        def _overflow():
            err_ref[0:1, :] = jnp.ones((1, B), jnp.int32)

        def compact(j, off):
            rows_j = self.block_rows(j)
            tmp[pl.ds(off, K), :] = sig[pl.ds(j * K, K), :]
            return off + rows_j

        lax.fori_loop(0, NB, compact, 0)

        def deal(j, _):
            rows_j = jnp.clip(total - j * fill, 0, fill)
            blk = tmp[pl.ds(j * fill, K), :]
            nblk = jnp.where(self.idx_k < rows_j, blk, 0)
            sig[pl.ds(j * K, K), :] = nblk
            rws[pl.ds(j, 1), :] = jnp.broadcast_to(rows_j, (1, B))
            liv[pl.ds(j, 1), :] = jnp.sum(
                (nblk > 0).astype(jnp.int32), axis=0, keepdims=True)
            return 0

        lax.fori_loop(0, NB, deal, 0)

    def local_delete(self, p, d):
        """Tombstone ``d`` live chars after content pos ``p``
        (`mutations.rs:520-570`); walks 2-block windows across the span."""
        K, NB = self.K, self.NB
        sig, liv = self.sig, self.liv
        err_ref = self.err_ref

        def body(carry):
            rem, iters = carry
            b = jnp.minimum(self.block_of_rank(p + 1), NB - 2)
            base = self.live_before_block(b)
            win = sig[pl.ds(b * K, 2 * K), :]
            wlive = win > 0
            rank = base + _cumsum_rows(wlive.astype(jnp.int32))
            flip = wlive & (rank > p) & (rank <= p + rem)
            sig[pl.ds(b * K, 2 * K), :] = jnp.where(flip, -win, win)
            fcounts = flip.astype(jnp.int32)
            f0 = _lane_scalar(jnp.where(self.idx_2k < K, fcounts, 0))
            f1 = _lane_scalar(jnp.where(self.idx_2k >= K, fcounts, 0))
            liv[pl.ds(b, 1), :] = liv[pl.ds(b, 1), :] - f0
            liv[pl.ds(b + 1, 1), :] = liv[pl.ds(b + 1, 1), :] - f1
            return rem - f0 - f1, iters + 1

        # Iteration bound: each window contains >= 1 target char for a
        # valid stream, so NB+1 windows means the delete ran off the
        # document (invalid op) — flag instead of hanging the chip.
        rem, iters = lax.while_loop(
            lambda c: (c[0] > 0) & (c[1] <= NB), body, (d, 0))

        @pl.when(rem > 0)
        def _bad_delete():
            err_ref[1:2, :] = jnp.ones((1, self.B), jnp.int32)

    def local_insert_block(self, p):
        """(block, occupied rows) an insert at live rank ``p`` targets —
        the cheap pre-check before the overflow rebalance."""
        b = jnp.where(p == 0, 0, self.block_of_rank(p))
        return b, self.block_rows(b)

    def local_insert_target(self, p):
        """(block, row-cursor, block-rows, origins) for a local insert at
        live rank ``p``, with the overflow rebalance already handled.
        Origins per `doc.rs:447-453`: raw successor without skipping
        tombstones."""
        K, NB = self.K, self.NB
        sig, rws = self.sig, self.rws
        idx_k, idx_nb = self.idx_k, self.idx_nb

        b, r0 = self.local_insert_block(p)
        local_rank = p - self.live_before_block(b)
        blk = sig[pl.ds(b * K, K), :]
        bcum = _cumsum_rows((blk > 0).astype(jnp.int32))
        c0 = jnp.max(jnp.sum(
            (bcum < local_rank).astype(jnp.int32), axis=0))
        c = jnp.where(p == 0, 0, c0 + 1)

        left_signed = _lane_scalar(jnp.where(idx_k == c - 1, blk, 0))
        succ_here = _lane_scalar(jnp.where(idx_k == c, blk, 0))
        nb_next = jnp.max(jnp.min(jnp.where(
            (idx_nb > b) & (idx_nb < NB) & (rws[:] > 0), idx_nb, NB),
            axis=0))
        nxt = sig[pl.ds(jnp.minimum(nb_next, NB - 1) * K, K), :]
        succ_next = _lane_scalar(jnp.where(idx_k == 0, nxt, 0))
        succ_signed = jnp.where(c < r0, succ_here,
                                jnp.where(nb_next < NB, succ_next, 0))
        return b, c, r0, left_signed, succ_signed


def _replay_kernel(
    pos_ref, dlen_ref, ilen_ref, start_ref,     # [CHUNK] SMEM op columns
    ol_ref, or_ref,                             # [CHUNK,B] VMEM outputs
    sig_out_ref, rows_out_ref, err_ref,         # final state outputs
    sig, rws, liv, tmp,                         # VMEM scratch
    *, K: int, NB: int, CHUNK: int, LMAX: int,
):
    B = sig.shape[1]
    i = pl.program_id(0)
    last = pl.num_programs(0) - 1
    ops_ = _BlockOps(sig, rws, liv, tmp, err_ref, K=K, NB=NB, LMAX=LMAX)
    idx_k = ops_.idx_k
    root_u = jnp.uint32(ROOT_ORDER)

    # Each grid step owns a fresh [CHUNK, B] origin-output block; rows for
    # steps with ins_len == 0 would otherwise be uninitialized VMEM garbage.
    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when(i == 0)
    def _init():
        # Cold start: empty document (warm start re-uploads via
        # blocked_to_flat -> flat engine for now).
        sig[:] = jnp.zeros_like(sig)
        rws[:] = jnp.zeros_like(rws)
        liv[:] = jnp.zeros_like(liv)
        err_ref[:] = jnp.zeros_like(err_ref)

    def do_insert(k, p, il, st):
        """Splice ``il`` new items after live rank ``p`` into one block
        (`mutations.rs:17-179`; packed slack instead of node splits)."""
        _, r0 = ops_.local_insert_block(p)

        @pl.when(r0 + il > K)
        def _rb():
            ops_.rebalance()

        b, c, r0, left_signed, succ_signed = ops_.local_insert_target(p)
        left = jnp.where(p == 0, root_u, _order_of(left_signed))
        right = jnp.where(succ_signed == 0, root_u, _order_of(succ_signed))

        blk = sig[pl.ds(b * K, K), :]
        shifted = _shift_rows(blk, il, LMAX)
        new_vals = st + (idx_k - c) + 1
        nblk = jnp.where(idx_k < c, blk,
                         jnp.where(idx_k < c + il, new_vals, shifted))
        sig[pl.ds(b * K, K), :] = nblk
        rws[pl.ds(b, 1), :] = rws[pl.ds(b, 1), :] + il
        liv[pl.ds(b, 1), :] = liv[pl.ds(b, 1), :] + il

        ol_ref[pl.ds(k, 1), :] = jnp.broadcast_to(left, (1, B))
        or_ref[pl.ds(k, 1), :] = jnp.broadcast_to(right, (1, B))

    def op_body(k, _):
        p = pos_ref[k]
        d = dlen_ref[k]
        il = ilen_ref[k]
        st = start_ref[k]

        @pl.when(d > 0)
        def _():
            ops_.local_delete(p, d)

        @pl.when(il > 0)
        def _():
            do_insert(k, p, il, st)

        return 0

    lax.fori_loop(0, CHUNK, op_body, 0)

    @pl.when(i == last)
    def _flush():
        sig_out_ref[:] = sig[:]
        rows_out_ref[:] = rws[:]


@dataclasses.dataclass
class BlockedResult:
    """Device outputs of one ``replay_local`` call.

    Everything stays on device until read; call ``check()`` (or convert
    via ``blocked_to_flat``, which checks) to surface kernel error flags;
    the kernel never syncs eagerly.
    """

    signed: jax.Array   # i32[CAP, B] blocked rows (packed per block)
    rows: jax.Array     # i32[NBp, B] occupied rows per block
    ol: jax.Array       # u32[S, B]  per-step local origin_left
    orr: jax.Array      # u32[S, B]  per-step local origin_right
    err: jax.Array      # i32[8, B]  row 0: capacity exhausted; row 1: bad delete
    block_k: int
    num_blocks: int
    batch: int

    def check(self) -> None:
        # Explicit raises, not assert: these surface device error flags and
        # must fire even under ``python -O``.
        err = np.asarray(self.err)
        if err[0].max() != 0:
            raise RuntimeError(
                "blocked engine capacity exhausted (rebalance found fill > "
                "K-lmax); raise capacity")
        if err[1].max() != 0:
            raise RuntimeError(
                "delete ran past the end of the document (invalid op stream)")
        if err[2].max() != 0:
            raise RuntimeError(
                "remote op referenced an order not present in the document "
                "(bad origin or delete target)")


def make_replayer(
    ops: OpTensors,
    capacity: int,
    batch: int = 128,
    block_k: int = 256,
    chunk: int = 1024,
    interpret: bool = False,
):
    """Stage ``ops`` on device and build a reusable jitted replayer.

    Returns a zero-argument callable producing a ``BlockedResult``; the
    op upload and the pallas trace/compile happen once, so repeated calls
    pay only kernel execution (bench steady state).
    """
    kinds = np.asarray(ops.kind)
    _require(kinds.ndim == 1, "blocked engine takes one shared stream")
    _require(bool((kinds == KIND_LOCAL).all()),
             "blocked engine replays local streams; remote ops -> ops.flat")
    require_unfused(ops, "the blocked engine")
    _require(capacity % block_k == 0,
             f"capacity ({capacity}) must be a multiple of block_k "
             f"({block_k})")
    # Rank-1 i32 arrays tile at T(1024) on TPU; the SMEM op blocks must
    # match that layout (smaller streams fall back to one whole-array
    # block via s_pad == chunk).
    _require(interpret or chunk % 1024 == 0 or (
        jax.default_backend() != "tpu"),
        "chunk must be a multiple of 1024 on TPU")
    NB = capacity // block_k
    _require(NB >= 2, "need at least two blocks (delete window)")
    NBp = max(8, NB)
    lmax = ops.lmax
    _require(block_k > lmax, (
        f"block_k ({block_k}) must exceed the insert chunk width "
        f"({lmax}); a full block could never absorb an insert"))
    rows_needed = int(np.asarray(ops.ins_len, dtype=np.int64).sum())
    rows_limit = NB * (block_k - lmax)
    _require(rows_needed <= rows_limit, (
        f"stream inserts {rows_needed} rows but {NB} blocks of "
        f"{block_k} hold at most {rows_limit} at the rebalance fill "
        f"limit (K-lmax); raise capacity"))

    s = ops.num_steps
    s_pad = max(((s + chunk - 1) // chunk) * chunk, chunk)
    pad = ((0, s_pad - s),)

    def padded(a):
        return jnp.asarray(np.pad(np.asarray(a, dtype=np.int32), pad))

    staged = (padded(ops.pos), padded(ops.del_len), padded(ops.ins_len),
              padded(ops.ins_order_start))

    jitted = _build_call(s_pad, batch, capacity, block_k, chunk, lmax,
                         interpret)

    def run() -> BlockedResult:
        ol, orr, signed, rows, err = jitted(*staged)
        return BlockedResult(
            signed=signed, rows=rows, ol=ol[:s], orr=orr[:s], err=err,
            block_k=block_k, num_blocks=NB, batch=batch)

    return run


@functools.lru_cache(maxsize=32)
def _build_call(s_pad: int, batch: int, capacity: int, block_k: int,
                chunk: int, lmax: int, interpret: bool):
    """Shape-keyed cache (the ``rle_lanes._build_call`` pattern):
    same-shape replays share one traced kernel — a per-call
    ``jax.jit(lambda ...)`` re-traces the whole interpret program each
    time, which dominates the fixed-shape test suites."""
    NB = capacity // block_k
    NBp = max(8, NB)

    smem = lambda: pl.BlockSpec(
        (chunk,), lambda i: (i,), memory_space=pltpu.SMEM)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape),
                            memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        partial(_replay_kernel, K=block_k, NB=NB, CHUNK=chunk, LMAX=lmax),
        grid=(s_pad // chunk,),
        in_specs=[smem(), smem(), smem(), smem()],
        out_specs=[
            pl.BlockSpec((chunk, batch), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((chunk, batch), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            whole((capacity, batch)),
            whole((NBp, batch)),
            whole((8, batch)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, batch), jnp.uint32),
            jax.ShapeDtypeStruct((s_pad, batch), jnp.uint32),
            jax.ShapeDtypeStruct((capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((NBp, batch), jnp.int32),
            jax.ShapeDtypeStruct((8, batch), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((capacity, batch), jnp.int32),
            pltpu.VMEM((NBp, batch), jnp.int32),
            pltpu.VMEM((NBp, batch), jnp.int32),
            pltpu.VMEM((capacity + block_k, batch), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            # The default 16MB scoped-vmem cap rejects big documents; the
            # chip has 128MB of VMEM.
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        interpret=interpret,
    )
    return jax.jit(lambda a, b, c, d: call(a, b, c, d))


def replay_local(
    ops: OpTensors,
    capacity: int,
    batch: int = 128,
    block_k: int = 256,
    chunk: int = 1024,
    interpret: bool = False,
) -> BlockedResult:
    """One-shot convenience wrapper over ``make_replayer``."""
    return make_replayer(ops, capacity, batch=batch, block_k=block_k,
                         chunk=chunk, interpret=interpret)()


def blocked_to_flat(
    ops: OpTensors,
    res: BlockedResult,
    capacity: int | None = None,
    order_capacity: int | None = None,
    doc_index: int = 0,
) -> FlatDoc:
    """Kernel result -> a standard ``FlatDoc`` (one doc of the batch):
    concatenate each block's packed rows, prefill the by-order logs, then
    merge the kernel's per-step local origins."""
    res.check()
    sig = np.asarray(res.signed)[:, doc_index]
    r = np.asarray(res.rows)[:, doc_index]
    K, NB = res.block_k, res.num_blocks
    parts = [sig[b * K: b * K + r[b]] for b in range(NB)]
    flat = np.concatenate(parts) if parts else np.zeros(0, np.int32)
    n = len(flat)

    if capacity is None:
        capacity = max(res.signed.shape[0], n)
    doc = make_flat_doc(capacity, order_capacity)
    doc = prefill_logs(doc, ops)
    ol_log = np.array(doc.ol_log)
    or_log = np.array(doc.or_log)
    starts = np.asarray(ops.ins_order_start, dtype=np.int64)
    ilens = np.asarray(ops.ins_len, dtype=np.int64)
    ol_np = np.asarray(res.ol)[:, doc_index]
    or_np = np.asarray(res.orr)[:, doc_index]
    for st, il, left, right in zip(starts, ilens, ol_np, or_np):
        if il > 0:
            ol_log[st] = left
            or_log[st: st + il] = right

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
