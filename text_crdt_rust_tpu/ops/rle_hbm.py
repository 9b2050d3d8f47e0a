"""HBM-state RLE run engine: millions of run rows, one-block VMEM window.

``ops.rle`` holds both run planes in VMEM, which caps capacity near ~50k
run rows. This variant keeps the planes in HBM and caches ONE block in
VMEM — the layout that unlocks the two workloads the VMEM engine can't
hold:

- **kevin** (`benches/yjs.rs:51-62`): 5M single-char prepends — runs
  cannot merge (each new char precedes the previous one in doc order,
  the shape that costs the reference 5M tree nodes), so state is one row
  per op. The logical-block-order SPLIT (shared design with ``ops.rle``)
  makes the always-at-front insert amortized O(1): slot 0 fills, its top
  half moves to a fresh physical block, the window stays valid (the kept
  half is the same physical block) — no global rebalance, ~zero DMA
  misses. This is the round-2 pathology (O(capacity) rebalance per
  overflow) gone for good.
- **documents beyond VMEM** (SURVEY §5 long-context row): run capacity
  is bounded by HBM (GBs), with a two-level ``SUP``-segment live index
  (the `mod.rs:85-93` internal-node sums as two short scans) so
  position→slot stays O(NSUP + SUP) regardless of block count.

The in-block row algebra — run location, insert splice, delete
flip/boundary-split — is ``ops.rle``'s module-level helpers
(`_locate_run` / `_insert_splice` / `_delete_block_math`), so the two
engines cannot drift. Results reuse ``RleResult``/``rle_to_flat``.
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

from ..common import ROOT_ORDER
from .batch import KIND_LOCAL, _require, fused_width_checked
from .rle import (
    RleResult,
    _cumsum_rows,
    _delete_block_math,
    _insert_splice,
    _locate_run,
    _row_scalar,
    _shift_rows,
    _shift_rows_up,
)

SUP = 64  # logical slots per super-segment (level-2 live index fan-out)


def _rle_hbm_kernel(
    pos_ref, dlen_ref, ilen_ref, start_ref,     # [CHUNK] SMEM op columns
    w_ref,                                      # [CHUNK] SMEM rows_per_step
    ol_ref, or_ref,                             # [1,CHUNK,B] VMEM outputs
    ordp, lenp,                                 # [G*CAP,B] ANY/HBM planes
    blk_out, rows_out, meta_out, err_ref,       # tables + flags
    wo, wl, stage,                              # [K,B] window + DMA stage
    blkord, rws, liv, supliv,                   # logical tables (VMEM)
    wmeta, meta, sem,                           # SMEM scalars + DMA sem
    *, K: int, NB: int, NBL: int, NSUP: int, CHUNK: int, WMAX: int,
):
    B = wo.shape[1]
    g = pl.program_id(0)
    i = pl.program_id(1)
    last = pl.num_programs(1) - 1
    idx_k = lax.broadcasted_iota(jnp.int32, (K, B), 0)
    idx_l = lax.broadcasted_iota(jnp.int32, rws.shape, 0)
    idx_s = lax.broadcasted_iota(jnp.int32, supliv.shape, 0)
    root_u = jnp.uint32(ROOT_ORDER)
    gbase = g * (NB * K)

    ol_ref[:] = jnp.zeros_like(ol_ref)
    or_ref[:] = jnp.zeros_like(or_ref)

    @pl.when((g == 0) & (i == 0))
    def _init_err():
        err_ref[:] = jnp.zeros_like(err_ref)

    @pl.when(i == 0)
    def _init():
        # Fresh group: one empty block in logical slot 0, cached zeroed in
        # the window (its HBM backing is written on eviction/flush; fresh
        # split blocks are fully masked-written, so HBM is never read
        # before a write).
        blkord[:] = jnp.zeros_like(blkord)
        rws[:] = jnp.zeros_like(rws)
        liv[:] = jnp.zeros_like(liv)
        supliv[:] = jnp.zeros_like(supliv)
        wo[:] = jnp.zeros_like(wo)
        wl[:] = jnp.zeros_like(wl)
        wmeta[0] = 0
        meta[0] = 1  # blocks in use

    def dma(src, dst):
        cp = pltpu.make_async_copy(src, dst, sem)
        cp.start()
        cp.wait()

    def ensure(b):
        """Cache physical block ``b`` in the window (write-back cache —
        every op may dirty the window, so eviction always writes)."""
        cb = wmeta[0]

        @pl.when(cb != b)
        def _miss():
            dma(wo, ordp.at[pl.ds(gbase + cb * K, K), :])
            dma(wl, lenp.at[pl.ds(gbase + cb * K, K), :])
            dma(ordp.at[pl.ds(gbase + b * K, K), :], wo)
            dma(lenp.at[pl.ds(gbase + b * K, K), :], wl)
            wmeta[0] = b

    def slot_scalar(tbl, l):
        return jnp.max(tbl[pl.ds(l, 1), :])

    def bump_liv(l, delta):
        liv[pl.ds(l, 1), :] = liv[pl.ds(l, 1), :] + delta
        s = l // SUP
        supliv[pl.ds(s, 1), :] = supliv[pl.ds(s, 1), :] + delta

    def resup():
        """Rebuild the super-segment sums from ``liv`` (after a table
        splice moved slot boundaries). O(NBL) total, split-rate only."""

        def seg(s, _):
            part = liv[pl.ds(s * SUP, SUP), :]
            supliv[pl.ds(s, 1), :] = jnp.sum(part, axis=0, keepdims=True)
            return 0

        lax.fori_loop(0, NSUP, seg, 0)

    def live_before_slot(l):
        s = l // SUP
        sup_part = jnp.max(jnp.sum(
            jnp.where(idx_s < s, supliv[:], 0), axis=0))
        segm = liv[pl.ds(s * SUP, SUP), :]
        seg_idx = lax.broadcasted_iota(jnp.int32, (SUP, B), 0)
        seg_part = jnp.max(jnp.sum(
            jnp.where(seg_idx < (l - s * SUP), segm, 0), axis=0))
        return sup_part + seg_part

    def slot_of_live_rank(rank1):
        """Two-level descent (`root.rs:54-88` over segment sums)."""
        nlog = meta[0]
        supcum = _cumsum_rows(jnp.where(idx_s < NSUP, supliv[:], 0))
        s = jnp.minimum(
            jnp.max(jnp.sum(
                ((supcum < rank1) & (idx_s < NSUP)).astype(jnp.int32),
                axis=0)),
            NSUP - 1)
        base = jnp.max(jnp.sum(jnp.where(idx_s < s, supliv[:], 0), axis=0))
        segm = liv[pl.ds(s * SUP, SUP), :]
        segcum = _cumsum_rows(segm)
        within = jnp.max(jnp.sum(
            (segcum < (rank1 - base)).astype(jnp.int32), axis=0))
        return jnp.minimum(s * SUP + within, nlog - 1)

    def split(l):
        """Leaf split (`mutations.rs:623-669`): the cached block's top
        half moves to a fresh physical block (stage DMA), spliced into
        the logical order at ``l+1``. The kept half stays cached."""
        nlog = meta[0]

        @pl.when(nlog >= NB)
        def _cap():
            # NO-OP at table capacity (advisor r3: proceeding overwrote
            # an in-use physical block); flag and leave state readable.
            err_ref[0:1, :] = jnp.ones((1, B), jnp.int32)

        @pl.when(nlog < NB)
        def _do():
            b = slot_scalar(blkord, l)
            ensure(b)
            r = slot_scalar(rws, l)
            keep = r // 2
            mv = r - keep
            nb = nlog
            bo = wo[:]
            bl = wl[:]
            liv_hi = jnp.max(jnp.sum(jnp.where(
                (idx_k >= keep) & (idx_k < r) & (bo > 0), bl, 0), axis=0))
            liv_lo = slot_scalar(liv, l) - liv_hi

            stage[:] = jnp.where(idx_k < mv, _shift_rows_up(bo, keep, K), 0)
            dma(stage, ordp.at[pl.ds(gbase + nb * K, K), :])
            stage[:] = jnp.where(idx_k < mv, _shift_rows_up(bl, keep, K), 0)
            dma(stage, lenp.at[pl.ds(gbase + nb * K, K), :])
            wo[:] = jnp.where(idx_k < keep, bo, 0)
            wl[:] = jnp.where(idx_k < keep, bl, 0)

            for tbl in (blkord, rws, liv):
                shifted = _shift_rows(tbl[:], 1, 1)
                tbl[:] = jnp.where(idx_l <= l, tbl[:], shifted)
            rws[pl.ds(l, 1), :] = jnp.broadcast_to(keep, (1, B))
            liv[pl.ds(l, 1), :] = jnp.broadcast_to(liv_lo, (1, B))
            blkord[pl.ds(l + 1, 1), :] = jnp.broadcast_to(nb, (1, B))
            rws[pl.ds(l + 1, 1), :] = jnp.broadcast_to(mv, (1, B))
            liv[pl.ds(l + 1, 1), :] = jnp.broadcast_to(liv_hi, (1, B))
            meta[0] = nlog + 1
            resup()

    def find_insert_slot(p):
        l = jnp.where(p == 0, 0, slot_of_live_rank(p))
        return l, slot_scalar(rws, l)

    def do_insert(k, p, il, st, w):
        l, r0 = find_insert_slot(p)

        @pl.when(r0 + w + 1 > K)
        def _():
            split(l)

        l, r0 = find_insert_slot(p)
        b = slot_scalar(blkord, l)
        ensure(b)
        base = live_before_slot(l)
        local = p - base
        bo = wo[:]
        bl = wl[:]
        i_r, o_r, l_r, off = _locate_run(bo, bl, idx_k, r0, local)

        left = jnp.where(p == 0, root_u,
                         ((o_r - 1) + (off - 1)).astype(jnp.uint32))
        is_split = (p > 0) & (off < l_r)

        # Raw successor (`doc.rs:452`): within block, else the next
        # slot's first row via an 8-row DMA peek (boundary inserts only).
        nxt_in_blk = _row_scalar(bo, i_r + 1, idx_k)
        nlog = meta[0]
        need_peek = (p > 0) & jnp.logical_not(is_split) & \
            (i_r + 1 >= r0) & (l + 1 < nlog)

        def peek():
            b2 = slot_scalar(blkord, jnp.minimum(l + 1, NBL - 1))
            dma(ordp.at[pl.ds(gbase + b2 * K, 8), :],
                stage.at[pl.ds(0, 8), :])
            return jnp.max(stage[pl.ds(0, 1), :])

        succ_next = lax.cond(need_peek, peek, lambda: jnp.int32(0))
        first_o = _row_scalar(bo, 0, idx_k)
        succ_p0 = jnp.where(r0 > 0, first_o, 0)
        succ = jnp.where(
            p == 0, succ_p0,
            jnp.where(is_split, o_r + off,
                      jnp.where(i_r + 1 < r0, nxt_in_blk, succ_next)))
        right = jnp.where(succ == 0, root_u,
                          (jnp.abs(succ) - 1).astype(jnp.uint32))

        no, nl, amt, _mrg, _sp = _insert_splice(
            bo, bl, idx_k, p, i_r, o_r, l_r, off, il, st, w, WMAX)
        wo[:] = no
        wl[:] = nl
        rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + amt
        bump_liv(l, il)

        ol_ref[:, pl.ds(k, 1), :] = jnp.broadcast_to(left, (1, 1, B))
        or_ref[:, pl.ds(k, 1), :] = jnp.broadcast_to(right, (1, 1, B))

    def do_delete(p, d):
        def body(carry):
            rem, iters = carry
            l = slot_of_live_rank(p + 1)

            @pl.when(slot_scalar(rws, l) + 2 > K)
            def _():
                split(l)

            l = slot_of_live_rank(p + 1)
            b = slot_scalar(blkord, l)
            ensure(b)
            base = live_before_slot(l)
            no, nl, added, tot = _delete_block_math(
                wo[:], wl[:], idx_k, K, base, p, rem)
            wo[:] = no
            wl[:] = nl
            rws[pl.ds(l, 1), :] = rws[pl.ds(l, 1), :] + added
            bump_liv(l, -tot)
            return rem - tot, iters + 1

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
        cb = wmeta[0]
        dma(wo, ordp.at[pl.ds(gbase + cb * K, K), :])
        dma(wl, lenp.at[pl.ds(gbase + cb * K, K), :])
        blk_out[:] = blkord[:][jnp.newaxis]
        rows_out[:] = rws[:][jnp.newaxis]
        row0 = lax.broadcasted_iota(jnp.int32, (1, 8, B), 1) == 0
        meta_out[:] = jnp.where(row0, meta[0], 0)


def make_replayer_rle_hbm(
    ops,
    capacity: int,
    batch: int = 128,
    block_k: int = 512,
    chunk: int = 1024,
    interpret: bool = False,
    store_origins: bool = True,
):
    """HBM-plane variant of ``rle.make_replayer_rle`` (same contract;
    ``capacity`` counts RUN rows and may reach millions).

    ``store_origins=False`` backs the per-op origin outputs with ONE
    chunk-sized window instead of the full stream (every chunk
    overwrites it): at kevin scale (5M steps x 128 lanes) the full
    ``ol``/``or`` planes alone are 5.1 GB of HBM, which together with
    the 10.7 GB state planes cannot fit the chip. The returned
    ``RleResult.ol``/``orr`` are EMPTY in this mode — final state
    (``expand_runs``) is unaffected, but ``rle_to_flat`` needs origins
    and must not be fed a store_origins=False result."""
    grouped = isinstance(ops, (list, tuple))
    streams = list(ops) if grouped else [ops]
    G = len(streams)
    _require(G >= 1, "need at least one op stream")
    for st in streams:
        kinds = np.asarray(st.kind)
        _require(kinds.ndim == 1, "rle_hbm engine takes per-group shared "
                 "streams")
        _require(bool((kinds == KIND_LOCAL).all()),
                 "rle_hbm engine replays local streams; remote ops -> "
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
    WMAX = fused_width_checked(streams, block_k)
    NSUP = (NB + SUP - 1) // SUP
    NBLp = NSUP * SUP
    NSUPp = max(8, NSUP)

    lens = [st.num_steps for st in streams]
    s_pad = max(((max(lens) + chunk - 1) // chunk) * chunk, chunk)

    def staged_col(get):
        cols = []
        for st in streams:
            a = np.asarray(get(st), dtype=np.int32)
            cols.append(np.pad(a, ((0, s_pad - len(a)),)))
        return jnp.asarray(np.concatenate(cols))   # flat [G*s_pad]

    staged = (staged_col(lambda o: o.pos),
              staged_col(lambda o: o.del_len),
              staged_col(lambda o: o.ins_len),
              staged_col(lambda o: o.ins_order_start),
              staged_col(lambda o: o.rows_per_step))

    jitted = _build_call(G, s_pad, batch, capacity, block_k, chunk,
                         WMAX, store_origins, interpret)

    def run():
        ol, orr, ordp, lenp, blk, rows, meta, err = jitted(*staged)
        # G == 1: hand the planes over as-is — a [0:capacity] slice is a
        # device COPY, and at kevin scale that transient doubles a 5 GiB
        # plane and OOMs the chip.
        results = [
            RleResult(
                ordp=ordp if G == 1 else
                ordp[gi * capacity:(gi + 1) * capacity],
                lenp=lenp if G == 1 else
                lenp[gi * capacity:(gi + 1) * capacity],
                blkord=blk[gi], rows=rows[gi], meta=meta[gi],
                ol=ol[gi, :lens[gi] if store_origins else 0],
                orr=orr[gi, :lens[gi] if store_origins else 0], err=err,
                block_k=block_k, num_blocks=NB, batch=batch)
            for gi in range(G)
        ]
        return results if grouped else results[0]

    return run


@functools.lru_cache(maxsize=32)
def _build_call(G: int, s_pad: int, batch: int, capacity: int,
                block_k: int, chunk: int, wmax: int,
                store_origins: bool, interpret: bool):
    """Shape-keyed cache (the ``rle_lanes._build_call`` pattern): every
    same-shape replay shares one traced kernel instead of paying a full
    re-trace per ``make_replayer_rle_hbm`` call."""
    NB = capacity // block_k
    NSUP = (NB + SUP - 1) // SUP
    NBLp = NSUP * SUP
    NSUPp = max(8, NSUP)
    blocks_per_g = s_pad // chunk
    smem = lambda: pl.BlockSpec(
        (chunk,), lambda g, i: (g * blocks_per_g + i,),
        memory_space=pltpu.SMEM)
    # One reused chunk window when origins aren't kept (see docstring).
    o_rows = s_pad if store_origins else chunk
    o_map = (lambda g, i: (g, i, 0)) if store_origins \
        else (lambda g, i: (g, 0, 0))

    call = pl.pallas_call(
        partial(_rle_hbm_kernel, K=block_k, NB=NB, NBL=NBLp, NSUP=NSUP,
                CHUNK=chunk, WMAX=wmax),
        grid=(G, blocks_per_g),
        in_specs=[smem(), smem(), smem(), smem(), smem()],
        out_specs=[
            pl.BlockSpec((1, chunk, batch), o_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk, batch), o_map,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
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
            jax.ShapeDtypeStruct((G, o_rows, batch), jnp.uint32),
            jax.ShapeDtypeStruct((G, o_rows, batch), jnp.uint32),
            jax.ShapeDtypeStruct((G * capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((G * capacity, batch), jnp.int32),
            jax.ShapeDtypeStruct((G, NBLp, batch), jnp.int32),
            jax.ShapeDtypeStruct((G, NBLp, batch), jnp.int32),
            jax.ShapeDtypeStruct((G, 8, batch), jnp.int32),
            jax.ShapeDtypeStruct((8, batch), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, batch), jnp.int32),    # window ord
            pltpu.VMEM((block_k, batch), jnp.int32),    # window len
            pltpu.VMEM((block_k, batch), jnp.int32),    # DMA stage
            pltpu.VMEM((NBLp, batch), jnp.int32),       # blkord
            pltpu.VMEM((NBLp, batch), jnp.int32),       # rws
            pltpu.VMEM((NBLp, batch), jnp.int32),       # liv
            pltpu.VMEM((NSUPp, batch), jnp.int32),      # supliv
            pltpu.SMEM((2,), jnp.int32),                # wmeta
            pltpu.SMEM((2,), jnp.int32),                # meta
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )
    return jax.jit(lambda a, b, c, d, e: call(a, b, c, d, e))


def replay_local_rle_hbm(ops, capacity: int, **kw):
    """One-shot convenience wrapper over ``make_replayer_rle_hbm``."""
    return make_replayer_rle_hbm(ops, capacity, **kw)()
